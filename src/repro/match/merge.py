"""Device-side cross-shard reduction merges (DESIGN.md Sec. 3k).

The paper's scale-out story (Sec. 3.4) is arrays computing independently
and exchanging only *reduced* state -- re-funneling every per-shard
result through the controller host re-creates the Von-Neumann bottleneck
in miniature and hard-breaks the moment shards live on another host's
devices (``np.asarray`` of a non-addressable array).  ``ShardMerger`` is
the one place cross-shard results combine, and they combine **on
device** with collectives under ``shard_map``:

* ``pull`` -- replicate a row-sharded array with an ``all_gather`` (the
  cyclic-layout un-permute happens device-side too) and hand the host a
  fully-replicated value; every process gets the same bytes, so the
  multi-controller SPMD discipline holds on any process count.
* ``topk_update`` / ``topk_finalize`` -- running global top-k as a tree
  merge: shard-local ``lax.top_k`` maxima, an ``all_gather`` of the
  (k_loc per shard) candidates, then a replicated ``lexsort`` realizing
  the total order (score desc, row asc) -- bit-identical to the deleted
  host ``np.lexsort`` merge, because each live row appears exactly once
  and int32 scores (>= -1) negate exactly.  Dead/padding entries carry
  the (-1, ROW_SENTINEL) sentinel pair and sort last; ``topk_finalize``
  trims them by the host-tracked live-candidate count.
* ``hot_mask`` / ``gather_rows`` -- the threshold reduction's sparse
  two-phase pull: a per-row any-hit bitmap (integer-exact: scores are
  ints, so ``s >= t``  <=>  ``s >= ceil(t)``), then a device gather of
  only the hot rows' score vectors.  The full per-chunk score block
  never crosses to the host (the satellite host-transfer fix).
* ``chunk_best`` / ``or_`` -- jitted per-chunk reductions so no eager op
  ever touches a non-addressable array.

Transfer accounting (``collective_bytes`` / ``reduced_pull_bytes`` /
``block_pull_bytes``) feeds ``MatchResult.merge_path`` and
``ServiceStats`` so mispriced merges show up in the feedback loop.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.distributed import sharding as _sharding
from repro.obs import NULL_OBS, Observability

# Sentinel pair for dead / padding top-k entries: any real row scores
# >= 0 and has id strictly below ROW_SENTINEL, so sentinels sort
# strictly after every live candidate under (score desc, row asc).
# Row ids live in int32 on device (jax_enable_x64 is off, so int64
# would be silently truncated inside jit -- a 1<<62 sentinel truncates
# to *zero* and sorts first); int32 max is unreachable as a real id.
ROW_SENTINEL = np.int32(np.iinfo(np.int32).max)
SCORE_SENTINEL = np.int32(-1)


# Shared row scatter for incremental splices into sharded device forms
# (corpus/index `.at[].set` is eager and would touch non-addressable
# shards multi-controller).  Every process packs the touched rows (tiny,
# identical host work by SPMD discipline); XLA updates only the
# addressable slots.
@functools.partial(jax.jit, static_argnames=("axis",))
def scatter_rows(a, i, v, axis: int = 0):
    """Rows ``i`` of a form that holds its rows along ``axis``, set to
    ``v``."""
    return a.at[(slice(None),) * axis + (i,)].set(v)


def _take_rows(a, i):
    return jnp.take(a, i, axis=0)


@functools.lru_cache(maxsize=512)
def _resident_slicer(S: int, j: int, j0: int, j1: int, w: int):
    """Jitted per-shard block slice: multi-process-safe ``_slice_resident``.

    Cached by geometry so repeated chunks reuse the compiled program
    (a fresh closure per call would defeat the jit cache).
    """
    def sl(b):
        return b.reshape(S, j, w)[:, j0:j1].reshape(S * (j1 - j0), w)
    return jax.jit(sl)


class ShardMerger:
    """Cross-shard merges for one engine, device-side under ``shard_map``.

    ``n_shards == 1`` degrades to plain host pulls (``merge_path ==
    "host"``); with shards every merge routes through the collectives --
    including on a single process, so the 8-shard single-process baseline
    exercises exactly the code the 2-process run executes (the
    bit-identity gate in ``BENCH_match_shard.json`` compares the two).
    """

    def __init__(self, mesh: Optional[Mesh], row_axes, n_shards: int,
                 obs: Optional[Observability] = None):
        # Merge/pull spans + transfer counters record here; the engine
        # hands in its own handle, passthrough mergers (PatternBank's
        # single-shard default) keep the shared null one.
        self.obs = obs if obs is not None else NULL_OBS
        self.n_shards = int(n_shards)
        self.mesh = mesh if self.n_shards > 1 else None
        if row_axes is None:
            axes: Tuple[str, ...] = ()
        elif isinstance(row_axes, tuple):
            axes = row_axes
        else:
            axes = (row_axes,)
        self.axes = axes
        self.multiprocess = jax.process_count() > 1
        # Transfer accounting: device-side collective traffic (per-link
        # ring estimate) vs. what actually crossed to the host, split by
        # whether it was reduced state or a score block.
        self.collective_bytes = 0
        self.reduced_pull_bytes = 0
        self.block_pull_bytes = 0
        self.n_collectives = 0
        self.n_pulls = 0
        self._spec = (PartitionSpec(axes if len(axes) > 1 else axes[0])
                      if axes else PartitionSpec())
        self._rep_fns = {}
        self._jit_fns = {}

    @property
    def merge_path(self) -> str:
        """"device" when cross-shard merges run collectives, else "host"."""
        return "device" if self.n_shards > 1 else "host"

    # -- placement -------------------------------------------------------------
    def put_replicated(self, arr):
        """Host array -> device, replicated over the mesh (or local)."""
        if self.mesh is None:
            return jnp.asarray(arr)
        a = np.asarray(arr)
        ns = NamedSharding(self.mesh, PartitionSpec())
        if not self.multiprocess:
            return jax.device_put(a, ns)
        # Non-addressable-safe: each process materializes its own copies.
        return jax.make_array_from_callback(a.shape, ns, lambda idx: a[idx])

    # -- replication (all_gather + device un-permute) --------------------------
    def _sharded(self, x) -> bool:
        return (self.n_shards > 1 and isinstance(x, jax.Array)
                and not x.is_fully_replicated
                and len(x.sharding.device_set) > 1)

    def _localize(self, x):
        """Pull a committed single-device array to host (multi-controller).

        The ref backend computes locally (identically on every process);
        feeding its committed local arrays into a jit whose out_shardings
        span the mesh would be a device mismatch, so hand jit the host
        value instead.
        """
        if (self.multiprocess and isinstance(x, jax.Array)
                and len(x.sharding.device_set) == 1):
            return np.asarray(x)
        return x

    def _replicator(self, unpermute: bool, axis: int):
        fn = self._rep_fns.get((unpermute, axis))
        if fn is None:
            S, axes = self.n_shards, self.axes
            def body(x):
                g = jax.lax.all_gather(x, axes, axis=axis, tiled=True)
                if unpermute:
                    # Physical (shard-major) -> logical order, on device.
                    g = _sharding.cyclic_unpermute(g, S)
                return g
            spec = PartitionSpec(*(None,) * axis, *self._spec)
            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(spec,),
                out_specs=PartitionSpec(), check_vma=False))
            self._rep_fns[(unpermute, axis)] = fn
        return fn

    def pull(self, x, *, unpermute: bool = False,
             kind: str = "reduced", axis: int = 0) -> np.ndarray:
        """Device value -> host ndarray, collectively replicated first.

        Row-sharded inputs (rows along ``axis``) are all-gathered (and
        un-permuted to logical row order when asked, which needs rows
        along axis 0) under ``shard_map`` before the host sees a byte;
        replicated/local inputs pull directly.  ``kind`` buckets the
        transfer accounting ("reduced" state vs. score "block").
        """
        if unpermute and axis:
            raise ValueError("unpermute needs rows along axis 0")
        tr = self.obs.tracer
        with tr.span("pull",
                     {"kind": kind} if tr.enabled else None) as sp:
            sharded = self._sharded(x)
            if sharded:
                x = self._replicator(unpermute, axis)(x)
                self.n_collectives += 1
                self.collective_bytes += (int(x.nbytes)
                                          * (self.n_shards - 1)) \
                    // self.n_shards
            # The device still computing, then the copy to the host: an
            # idle device under ``pull.copy`` is the transfer (or the
            # host), under ``pull.wait`` never.
            with tr.span("pull.wait"):
                jax.block_until_ready(x)
            with tr.span("pull.copy"):
                out = np.asarray(x)
                if not sharded and unpermute and self.n_shards > 1:
                    out = _sharding.cyclic_unpermute(out, self.n_shards)
            self.n_pulls += 1
            if kind == "block":
                self.block_pull_bytes += out.nbytes
            else:
                self.reduced_pull_bytes += out.nbytes
            if tr.enabled:
                sp.set("bytes", int(out.nbytes))
        return out

    # -- jitted per-chunk reductions -------------------------------------------
    def _jit(self, key, build):
        fn = self._jit_fns.get(key)
        if fn is None:
            fn = self._jit_fns[key] = build()
        return fn

    def chunk_best(self, scores):
        """(rows, L[, Q]) -> ((rows[, Q]) argmax, (rows[, Q]) max), jitted."""
        def chunk_best(s):
            return jnp.argmax(s, axis=1), jnp.max(s, axis=1)
        fn = self._jit("best", lambda: jax.jit(chunk_best))
        tr = self.obs.tracer
        with tr.span("merge", {"op": "best"} if tr.enabled else None):
            return fn(scores)

    def hot_mask(self, scores, thr_int: np.ndarray):
        """(rows,) bool: any alignment (any query) reaches the threshold.

        ``thr_int`` is ``ceil(threshold)`` as int32 (() or (Q,)): scores
        are integers, so the integer compare is exact -- no float32
        rounding can create a false negative against the host's float64
        hit extraction.
        """
        def build():
            def hot_mask(s, t):
                m = (s >= t[None, None, :]) if s.ndim == 3 else (s >= t)
                return m.any(axis=tuple(range(1, m.ndim)))
            return jax.jit(hot_mask)
        tr = self.obs.tracer
        with tr.span("merge", {"op": "hot_mask"} if tr.enabled else None):
            return self._jit("hot", build)(scores,
                                           np.asarray(thr_int, np.int32))

    def gather_rows(self, arr, idx: np.ndarray):
        """Rows ``idx`` of a (possibly row-sharded) array, replicated.

        The cross-shard gather happens device-side; the result is fully
        replicated so any process may pull it.  ``idx`` is a host array
        (identical on every process by SPMD discipline).
        """
        idx = np.asarray(idx)
        tr = self.obs.tracer
        with tr.span("merge",
                     {"op": "gather_rows"} if tr.enabled else None):
            if self.mesh is None:
                return jnp.take(arr, jnp.asarray(idx), axis=0)
            arr = self._localize(arr)
            def build():
                ns = NamedSharding(self.mesh, PartitionSpec())
                return jax.jit(_take_rows, out_shardings=ns)
            out = self._jit("gather", build)(arr, idx)
            self.n_collectives += 1
            self.collective_bytes += (int(out.nbytes)
                                      * (self.n_shards - 1)) // self.n_shards
            return out

    # -- top-k tree merge ------------------------------------------------------
    def _shard_index(self):
        s = jax.lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            s = s * self.mesh.shape[a] + jax.lax.axis_index(a)
        return s

    @staticmethod
    def _lexsort_merge(cs, cr, k):
        """(Q, m) candidates -> (Q, k) under (score desc, row asc).

        Scores are int32 >= -1, so negation is exact (no INT32_MIN).
        """
        def pick(s_col, r_col):
            order = jnp.lexsort((r_col, -s_col))[:k]
            return s_col[order], r_col[order]
        return jax.vmap(pick)(cs, cr)

    def _phys_topk(self):
        def build():
            S = self.n_shards

            def body(bs, alive_rep, c0, st_s, st_r):
                # bs: per-shard (Jc[, Q]) best-score block, physical
                # layout; alive_rep: (chunk,) bool over logical in-chunk
                # positions (False past the valid rows); st_*: (k[, Q]).
                s_idx = self._shard_index()
                Jc = bs.shape[0]
                rows = (c0 + jnp.arange(Jc, dtype=jnp.int32) * S
                        + s_idx.astype(jnp.int32))
                alive = alive_rep[jnp.arange(Jc) * S + s_idx]
                bs2 = bs if bs.ndim == 2 else bs[:, None]
                st_s2 = st_s if st_s.ndim == 2 else st_s[:, None]
                st_r2 = st_r if st_r.ndim == 2 else st_r[:, None]
                k = st_s2.shape[0]
                sc = jnp.where(alive[:, None], bs2.astype(jnp.int32),
                               SCORE_SENTINEL)
                rw = jnp.where(alive[:, None],
                               jnp.broadcast_to(rows[:, None], bs2.shape),
                               ROW_SENTINEL)
                # Shard-local maxima: lax.top_k ties break to the lowest
                # index, which in a shard block is the lowest slot and so
                # the lowest logical row -- the lexsort total order.
                k_loc = min(k, Jc)
                ts, ti = jax.lax.top_k(sc.T, k_loc)          # (Q, k_loc)
                tr = jnp.take_along_axis(rw.T, ti, axis=1)
                gs = jax.lax.all_gather(ts, self.axes, axis=1, tiled=True)
                gr = jax.lax.all_gather(tr, self.axes, axis=1, tiled=True)
                cs = jnp.concatenate([st_s2.T, gs], axis=1)
                cr = jnp.concatenate([st_r2.T, gr], axis=1)
                ns_, nr_ = self._lexsort_merge(cs, cr, k)
                out_s, out_r = ns_.T, nr_.T
                if bs.ndim == 1:
                    return out_s[:, 0], out_r[:, 0]
                return out_s, out_r

            P0 = PartitionSpec()
            return jax.jit(jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(self._spec, P0, P0, P0, P0),
                out_specs=(P0, P0), check_vma=False))
        return self._jit("phys_topk", build)

    def _logical_topk(self):
        def build():
            def upd(st_s, st_r, bs, rows, alive):
                # bs: (n[, Q]) best scores in *logical* candidate order
                # (rows= subsets / filter survivors / unsharded scans);
                # rows: (n,) int32 corpus ids; alive: (n,) bool.
                bs2 = bs if bs.ndim == 2 else bs[:, None]
                st_s2 = st_s if st_s.ndim == 2 else st_s[:, None]
                st_r2 = st_r if st_r.ndim == 2 else st_r[:, None]
                k = st_s2.shape[0]
                sc = jnp.where(alive[:, None], bs2.astype(jnp.int32),
                               SCORE_SENTINEL)
                rw = jnp.where(alive[:, None],
                               jnp.broadcast_to(rows[:, None], bs2.shape),
                               ROW_SENTINEL)
                cs = jnp.concatenate([st_s2.T, sc.T], axis=1)
                cr = jnp.concatenate([st_r2.T, rw.T], axis=1)
                ns_, nr_ = self._lexsort_merge(cs, cr, k)
                out_s, out_r = ns_.T, nr_.T
                if bs.ndim == 1:
                    return out_s[:, 0], out_r[:, 0]
                return out_s, out_r
            if self.mesh is not None:
                ns = NamedSharding(self.mesh, PartitionSpec())
                return jax.jit(upd, out_shardings=(ns, ns))
            return jax.jit(upd)
        return self._jit("logical_topk", build)

    def topk_init(self, k: int, n_cols: int):
        """Sentinel-filled running state ((k[, Q]) scores + rows)."""
        shape = (k, n_cols) if n_cols else (k,)
        return (np.full(shape, SCORE_SENTINEL, np.int32),
                np.full(shape, ROW_SENTINEL, np.int32))

    def topk_update(self, state, bs, *, phys: bool, alive_chunk: np.ndarray,
                    c0: int = 0, rows_np: Optional[np.ndarray] = None):
        """Fold one chunk's best scores into the running top-k state.

        ``phys=True``: ``bs`` is the row-sharded physical-layout chunk --
        shard-local top-k + all_gather + replicated lexsort merge, one
        jitted ``shard_map`` call.  ``phys=False``: ``bs`` follows
        logical candidate order and ``rows_np`` carries the corpus ids.
        ``alive_chunk`` is the in-chunk validity/tombstone mask (logical
        positions), identical on every process.
        """
        st_s, st_r = state
        alive_chunk = np.asarray(alive_chunk, bool)
        tr = self.obs.tracer
        with tr.span("merge", {"op": "topk"} if tr.enabled else None):
            if phys:
                fn = self._phys_topk()
                st_s, st_r = fn(bs, alive_chunk, np.int32(c0), st_s, st_r)
                if self.n_shards > 1:
                    k_loc = min(np.shape(st_s)[0],
                                bs.shape[0] // self.n_shards)
                    cols = bs.shape[1] if bs.ndim == 2 else 1
                    self.n_collectives += 1
                    self.collective_bytes += (self.n_shards - 1) * k_loc * \
                        cols * 12
            else:
                fn = self._logical_topk()
                st_s, st_r = fn(st_s, st_r, self._localize(bs),
                                np.asarray(rows_np, np.int32), alive_chunk)
        return st_s, st_r

    def topk_finalize(self, state, n_alive: int, k: int):
        """Pull the replicated state, trim sentinels: ((kk[, Q]) rows,
        scores) with kk = min(k, live candidates seen)."""
        st_s, st_r = state
        rows = self.pull(st_r, kind="reduced").astype(np.int64)
        scores = self.pull(st_s, kind="reduced")
        kk = min(int(k), int(n_alive))
        return rows[:kk], scores[:kk]

