"""Device-resident growable packed corpus for the match engine
(DESIGN.md Sec. 3a/3f).

The paper's core discipline is that the reference never moves once laid out
(CRAM-PM keeps fragments resident in the array rows; Sec. 2-3).  The TPU
analogue: pack the fragment matrix into its kernel-native forms *once*, keep
both forms device-resident, and serve every subsequent query from the cached
arrays.  Two forms exist because the two kernels want different layouts:

* SWAR form  -- (C_pad, W) uint32, 16 two-bit chars per word, rows padded to
  ``match_swar.ROW_TILE``; consumed by the VPU bit-parallel kernel.
* one-hot form -- (C_pad, F4) bf16, char-major flattened one-hot; consumed
  by the MXU correlation kernel.

Both are built lazily on first use and grown *on device* (zero-extension via
``jnp`` concat/pad) when a query needs more padding than a previous one --
host repacking happens at most once per form for a given corpus lifetime.
``host_pack_count`` counts those host->device packing events; the
steady-state invariant (no repacking across repeated queries *or corpus
growth*) is asserted by ``tests/test_match_engine.py``,
``tests/test_match_ingest.py`` and the engine/ingest benchmarks.

The corpus is **growable in place** (Sec. 3f): ``capacity`` row slots are
reserved up front (and doubled on demand), ``n_rows`` counts the *live*
rows, and ``append_rows`` packs only the appended rows on the host and
splices them into the cached device forms with ``.at[].set`` -- the
resident rows are never repacked, mirroring a CRAM row write into an
already-laid-out array.  Capacity growth itself is a device-side
zero-extension (``jnp.concatenate`` with zero rows), not a host repack.
``generation`` bumps on every content mutation (``append_rows`` /
``set_rows`` / ``tombstone`` / ``compact`` / ``invalidate``) so result
caches (match.service) never serve scores computed against older corpus
contents.

**Windowed operation** (DESIGN.md Sec. 3j): ``tombstone(rows)`` marks live
rows dead without moving anything -- the device forms are untouched and
the engine's reductions mask dead rows out on the host (threshold hits
drop, top-k excludes, best/full report the -1 sentinel).  ``compact()``
reclaims the dead slots by shifting the live tail down *in the host
buffer* and splicing only the moved rows into the device forms
(``_splice_device``), so eviction never repacks resident rows either --
the pack counters stay flat through an arbitrary tombstone/compact
history, which is what lets the corpus run as a bounded sliding window
instead of append-only.

**Row sharding** (``shard_rows``, DESIGN.md Sec. 3h): on a mesh the device
forms are stored in the *cyclic physical layout* of
``repro.distributed.sharding`` -- logical row ``r`` lives on shard
``r % S`` at slot ``r // S`` -- and placed with a ``NamedSharding`` over
the mesh row axes.  Block-sharding the permuted array is a cyclic
sharding of logical rows, which buys three properties at once: appends
round-robin across shards (ingest balanced by construction,
fewest-live-rows-first), capacity growth is a per-shard zero-extension
(a row's shard and slot never change), and contiguous logical chunks are
per-shard slot slices (no cross-device traffic while streaming).  The
host buffer and all public row ids stay logical; only the device forms
are permuted.

**Multi-host** (DESIGN.md Sec. 3k): under ``jax.distributed`` some mesh
shards live on other processes' devices, which eager ``device_put`` /
``.at[].set`` / ``reshape`` cannot touch.  The first pack then goes
through ``jax.make_array_from_callback`` -- *each process packs only the
shard blocks it owns* (block ``s`` of the cyclic layout is exactly
``pack(frags[s::S])``, so per-host packing is bit-identical to permuting
a global pack), keeping pack counters flat per host -- and every
subsequent splice or zero-extension runs as a jitted update (replicated
host operands in, XLA writes only addressable slots).  The host
fragment buffer stays fully replicated on every process by SPMD
discipline: ingest calls present identical rows on all processes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import encoding
from repro.distributed import sharding as _sharding
from repro.kernels import match_swar as _swar
from repro.obs import NULL_OBS

from . import merge as _merge

ROW_TILE = _swar.ROW_TILE


def _one_hot_flat(fragments: np.ndarray, width: Optional[int] = None,
                  dtype=np.float32) -> np.ndarray:
    """(R, F) uint8 codes -> (R, width >= F*4) char-major one-hot.

    Columns past ``F*4`` are zero.  Built in row blocks so a genome-scale
    corpus never holds an index temporary the size of the whole matrix.
    """
    R, F = fragments.shape
    out = np.zeros((R, F * 4 if width is None else width), dtype)
    cols = np.arange(F, dtype=np.int32) * 4
    for r0 in range(0, R, 1 << 16):
        blk = fragments[r0:r0 + (1 << 16)]
        np.put_along_axis(out[r0:r0 + blk.shape[0]], cols + blk, 1, axis=1)
    return out


class PackedCorpus:
    """Fragments packed once into device-resident, growable kernel forms.

    ``fragments`` is the (R, F) uint8 code matrix of *live* rows (host copy
    kept as the source of truth for incremental updates and for the ``ref``
    backend); ``capacity`` row slots are reserved so appends are in-place
    row writes.  ``row_pad`` rounds the device row count up; the engine
    raises it above ROW_TILE when sharding over a mesh rows axis.
    """

    def __init__(self, fragments: np.ndarray, *, row_pad: int = ROW_TILE,
                 capacity: Optional[int] = None):
        # Own copy: set_rows/append_rows mutate, and the caller's array
        # must not change underneath the packed device forms.
        fragments = np.array(fragments, np.uint8)
        if fragments.ndim != 2:
            raise ValueError("fragments must be (R, F)")
        if row_pad % ROW_TILE:
            raise ValueError(f"row_pad must be a multiple of {ROW_TILE}")
        self.row_pad = row_pad
        self._n_rows = fragments.shape[0]
        cap = max(self._n_rows, 0 if capacity is None else int(capacity))
        if cap > self._n_rows:
            buf = np.zeros((cap, fragments.shape[1]), np.uint8)
            buf[:self._n_rows] = fragments
            fragments = buf
        self._frags = fragments               # (capacity, F) host buffer
        # Row-shard layout: device forms are cyclically permuted over
        # n_shards and placed with NamedSharding(mesh, row_axes) when a
        # mesh engine configures the corpus via shard_rows().
        self.n_shards = 1
        self._mesh = None
        self._row_axes = None
        # Cached device forms (lazy), sized to the padded capacity.
        self._swar: Optional[jnp.ndarray] = None      # (C_pad, W) uint32
        self._onehot: Optional[jnp.ndarray] = None    # (C_pad, F4) bf16
        # Observability handle (spans around pack/splice/compact, churn
        # counters).  The shared null default records metrics nobody
        # reads; an owning MatchEngine replaces it with its own.
        self.obs = NULL_OBS
        # Host->device full-corpus packing events, per form.
        self.swar_pack_count = 0
        self.onehot_pack_count = 0
        # Incremental row writes (device splice, not a repack).
        self.row_update_count = 0
        # Content generation: bumped on every mutation (append_rows /
        # set_rows / tombstone / compact / invalidate).  Result caches
        # keyed on it (match.service) drop entries computed against older
        # contents.
        self.generation = 0
        # Tombstone mask over the capacity buffer (windowed operation,
        # DESIGN.md Sec. 3j): a dead row stays physically resident (its
        # device-form words are untouched) but reductions mask it out;
        # compact() reclaims the slots.
        self._dead = np.zeros(self.capacity, bool)
        self.n_dead = 0
        self.n_compactions = 0
        # Attached derived forms (match.index.CorpusIndex): observers that
        # mirror the residency protocol -- notified of exactly the touched
        # rows on splices, of capacity growth, and of invalidation, so
        # they stay incrementally up to date without ever re-reading the
        # resident rows.
        self._indexes: list = []

    # -- geometry ------------------------------------------------------------
    @property
    def fragments(self) -> np.ndarray:
        """(n_rows, F) live rows -- a view into the capacity buffer."""
        return self._frags[:self._n_rows]

    @property
    def n_rows(self) -> int:
        """Live (appended) rows; grows under ``append_rows``."""
        return self._n_rows

    @property
    def capacity(self) -> int:
        """Reserved row slots; appends within capacity never reallocate."""
        return self._frags.shape[0]

    @property
    def fragment_chars(self) -> int:
        return self._frags.shape[1]

    @property
    def n_rows_padded(self) -> int:
        """Live rows rounded up to ``row_pad`` (what queries stream over)."""
        return -(-self._n_rows // self.row_pad) * self.row_pad

    @property
    def capacity_padded(self) -> int:
        """Capacity rounded up to ``row_pad`` (device-form row count)."""
        return -(-self.capacity // self.row_pad) * self.row_pad

    @property
    def host_pack_count(self) -> int:
        """Total host-side full-corpus packing events (both forms)."""
        return self.swar_pack_count + self.onehot_pack_count

    # -- tombstones (windowed operation, DESIGN.md Sec. 3j) --------------------
    @property
    def n_live(self) -> int:
        """Rows that are appended and not tombstoned."""
        return self._n_rows - self.n_dead

    @property
    def dead_mask(self) -> np.ndarray:
        """(n_rows,) bool tombstone mask over the live region (read-only)."""
        m = self._dead[:self._n_rows]
        m.flags.writeable = False
        return m

    def live_row_ids(self) -> np.ndarray:
        """Ascending logical ids of non-tombstoned rows."""
        return np.flatnonzero(~self._dead[:self._n_rows])

    # -- row sharding ----------------------------------------------------------
    @property
    def shard_stride(self) -> int:
        """Per-shard physical row stride J: physical(r) = (r%S)*J + r//S."""
        return self.capacity_padded // self.n_shards

    @property
    def shard_live_rows(self) -> np.ndarray:
        """(S,) live logical rows per shard under the cyclic layout.

        Shard ``s`` holds rows ``{r < n_rows : r % S == s}``; contiguous
        appends round-robin, so counts differ by at most one row -- the
        balanced-ingest invariant the service benchmark asserts.
        """
        S, n = self.n_shards, self._n_rows
        return np.array([max(0, (n - s + S - 1) // S) for s in range(S)],
                        np.int64)

    def shard_rows(self, mesh, row_axes, n_shards: int) -> None:
        """Configure the cyclic row layout + NamedSharding placement.

        Called by the engine after resolving the mesh row axes.  Raises
        ``row_pad`` to a multiple of ``ROW_TILE * n_shards`` (so padded
        row counts divide evenly over shards) and drops cached device
        forms when the layout actually changes -- forms built for a
        different shard count are permuted differently and cannot be
        reused.  Reconfiguring to the same layout is a no-op (no repack,
        no generation bump).
        """
        n_shards = max(1, int(n_shards))
        need_pad = ROW_TILE * n_shards
        relayout = (n_shards != self.n_shards
                    or self.row_pad % need_pad != 0
                    or (n_shards > 1 and self._mesh is not None
                        and mesh != self._mesh))
        self._mesh = mesh
        self._row_axes = row_axes
        self.n_shards = n_shards
        if not relayout:
            return
        if self.row_pad % need_pad:
            self.row_pad = need_pad
        if (self._swar is not None or self._onehot is not None
                or self._indexes):
            self.invalidate()

    @property
    def _multiprocess(self) -> bool:
        """Sharded over devices some of which another process owns."""
        return (self.n_shards > 1 and self._mesh is not None
                and jax.process_count() > 1)

    def _row_sharding(self, axis: int = 0) -> NamedSharding:
        """Rows sharded over the row axes along ``axis`` of a form."""
        return NamedSharding(self._mesh,
                             PartitionSpec(*(None,) * axis, self._row_axes))

    def _place(self, arr, axis: int = 0) -> jnp.ndarray:
        """Device placement: NamedSharding over the row axes when sharded.

        ``axis`` is the form's row axis (0 for the row-major corpus forms,
        1 for the lane-dense signature form).  Multi-controller, ``arr``
        is a replicated *host* array (identical on every process); each
        process materializes only the shard blocks its own devices hold.
        """
        if self.n_shards > 1 and self._mesh is not None:
            ns = self._row_sharding(axis)
            if jax.process_count() > 1:
                a = np.asarray(arr)
                return jax.make_array_from_callback(
                    a.shape, ns, lambda idx: a[idx])
            return jax.device_put(arr, ns)
        return jnp.asarray(arr)

    def _grow_form_rows(self, form: jnp.ndarray, c_pad: int,
                        axis: int = 0) -> jnp.ndarray:
        """Zero-extend a device form to ``c_pad`` rows along ``axis``, per
        shard.

        Single-shard: plain pad.  Sharded: the extension happens *inside*
        each shard's block -- split the row axis into (S, J_old), pad the
        slot axis, merge back -- so every resident row keeps its shard and
        slot (growth stays in place per shard) and the result re-places
        onto the same NamedSharding.  Multi-controller the same program
        runs jitted (growth events are O(log capacity) per lifetime, so
        a fresh trace per doubling is fine): eager reshape of a
        non-addressable array would throw.
        """
        S = self.n_shards
        pad = [(0, 0)] * form.ndim
        if S == 1:
            pad[axis] = (0, c_pad - form.shape[axis])
            return self._place(jnp.pad(form, pad), axis)
        j_old, j_new = form.shape[axis] // S, c_pad // S

        def grow(f):
            head, tail = f.shape[:axis], f.shape[axis + 1:]
            f3 = f.reshape(*head, S, j_old, *tail)
            f3 = jnp.pad(f3, pad[:axis] + [(0, 0), (0, j_new - j_old)]
                         + pad[axis + 1:])
            return f3.reshape(*head, S * j_new, *tail)

        if self._multiprocess:
            return jax.jit(grow, out_shardings=self._row_sharding(axis))(form)
        return self._place(grow(form), axis)

    def _grow_form_cols(self, form: jnp.ndarray, grow: int) -> jnp.ndarray:
        """Zero-extend a device form's word/column axis, in place per row."""
        if self._multiprocess:
            return jax.jit(lambda f: jnp.pad(f, ((0, 0), (0, grow))),
                           out_shardings=self._row_sharding())(form)
        return self._place(jnp.pad(form, ((0, 0), (0, grow))))

    def attach_index(self, index) -> None:
        """Register a derived-form observer (see ``match.index``).

        The observer must expose ``_on_rows_written(start, rows)``,
        ``_on_capacity()`` and ``_on_invalidate()``; it is driven by the
        same mutation events that keep the SWAR/one-hot forms current.
        """
        self._indexes.append(index)

    def detach_index(self, index) -> None:
        """Stop notifying (and so stop updating) an attached observer.

        An abandoned index otherwise keeps re-deriving signatures on
        every row splice and pins its device form for the corpus
        lifetime; detach before replacing one configuration with
        another.  Detaching an index that is not attached is a no-op.
        """
        self._indexes = [ix for ix in self._indexes if ix is not index]

    @classmethod
    def from_reference(cls, ref_codes: np.ndarray, fragment_len: int,
                       pattern_len: int, *, row_pad: int = ROW_TILE
                       ) -> "PackedCorpus":
        """Fold a long reference into overlapping rows (Fig. 3 layout)."""
        frags = encoding.fold_reference(ref_codes, fragment_len, pattern_len)
        return cls(frags, row_pad=row_pad)

    # -- SWAR form -----------------------------------------------------------
    def swar_words(self, need_words: int) -> jnp.ndarray:
        """(C_pad, W >= need_words) uint32, device-resident.

        First call packs on the host (one event); later calls reuse the
        cached array, zero-extending on device if a query needs deeper
        word reads than any previous one.  Reserved (not yet live) rows
        pack to zero words -- code 0 packs to 0 -- so the form covers the
        whole capacity and appends are pure row splices.
        """
        if self._swar is None:
            tr = self.obs.tracer
            with tr.span("pack",
                         {"form": "swar", "rows": self.capacity_padded}
                         if tr.enabled else None):
                if self._multiprocess:
                    self._swar = self._build_swar_per_host(need_words)
                else:
                    words = encoding.pack_codes_u32(self._frags)
                    c_pad = self.capacity_padded
                    if c_pad > words.shape[0]:
                        words = np.concatenate(
                            [words,
                             np.zeros((c_pad - words.shape[0],
                                       words.shape[1]), np.uint32)], 0)
                    if words.shape[1] < need_words:
                        words = np.concatenate(
                            [words,
                             np.zeros((c_pad, need_words - words.shape[1]),
                                      np.uint32)], 1)
                    words = _sharding.cyclic_permute(words, self.n_shards)
                    self._swar = self._place(words)
            self.swar_pack_count += 1
            self.obs.metrics.counter("corpus.packs").inc()
        elif self._swar.shape[1] < need_words:
            self._swar = self._grow_form_cols(
                self._swar, need_words - self._swar.shape[1])
        return self._swar

    def _build_swar_per_host(self, need_words: int) -> jnp.ndarray:
        """First SWAR pack, multi-controller: each process packs only the
        shard blocks its devices own.

        Block ``s`` of ``cyclic_permute(pack(frags))`` is exactly
        ``pack(frags[s::S])`` (packing is row-wise), so per-host packing
        reproduces the single-process layout bit for bit while every
        host does ~1/P of the packing work.  Reserved rows are zero
        codes and pack to zero words, matching the zero row padding.
        """
        S, c_pad = self.n_shards, self.capacity_padded
        J = c_pad // S
        W = max(encoding.pack_codes_u32(self._frags[:1]).shape[1],
                need_words)
        blocks: dict = {}

        def cb(index):
            s = (index[0].start or 0) // J
            blk = blocks.get(s)
            if blk is None:
                words = encoding.pack_codes_u32(self._frags[s::S])
                blk = np.zeros((J, W), np.uint32)
                blk[:words.shape[0], :words.shape[1]] = words
                blocks[s] = blk
            return blk
        return jax.make_array_from_callback(
            (c_pad, W), self._row_sharding(), cb)

    # -- one-hot form ----------------------------------------------------------
    def onehot_flat(self, f_chars: int) -> jnp.ndarray:
        """(C_pad, F4 >= f_chars*4) bf16 one-hot, device-resident.

        Padding chars and reserved rows are all-zero one-hot (contribute 0
        to every score), so growing either way is a device-side
        zero-extension.  Rows are padded like the SWAR form so sharded
        chunks divide evenly over the mesh.
        """
        if self._onehot is None:
            tr = self.obs.tracer
            with tr.span("pack",
                         {"form": "onehot", "rows": self.capacity_padded}
                         if tr.enabled else None):
                if self._multiprocess:
                    self._onehot = self._build_onehot_per_host(f_chars)
                else:
                    # Reserved and padding rows stay all-zero one-hot.
                    need = max(f_chars, self.fragment_chars) * 4
                    base = np.zeros((self.capacity_padded, need),
                                    jnp.bfloat16)
                    base[:self._n_rows] = _one_hot_flat(
                        self.fragments, need, jnp.bfloat16)
                    base = _sharding.cyclic_permute(base, self.n_shards)
                    self._onehot = self._place(base)
            self.onehot_pack_count += 1
            self.obs.metrics.counter("corpus.packs").inc()
        elif self._onehot.shape[1] < f_chars * 4:
            self._onehot = self._grow_form_cols(
                self._onehot, f_chars * 4 - self._onehot.shape[1])
        return self._onehot

    def _build_onehot_per_host(self, f_chars: int) -> jnp.ndarray:
        """First one-hot pack, multi-controller: per-host shard blocks.

        Shard ``s`` holds logical rows ``s::S``; its first
        ``ceil((n_rows - s) / S)`` slots are live and the rest must be
        all-zero one-hot (code-0 reserved rows would otherwise read as
        'A' columns), exactly as the single-process build zeroes
        ``base[n_rows:]`` before permuting.
        """
        S, c_pad = self.n_shards, self.capacity_padded
        J = c_pad // S
        need = max(f_chars, self.fragment_chars) * 4
        n = self._n_rows
        blocks: dict = {}

        def cb(index):
            s = (index[0].start or 0) // J
            blk = blocks.get(s)
            if blk is None:
                oh = _one_hot_flat(self._frags[s::S])
                live_s = max(0, (n - s + S - 1) // S)
                oh[live_s:] = 0.0
                blk = np.zeros((J, need), np.float32)
                blk[:oh.shape[0], :oh.shape[1]] = oh
                blocks[s] = blk = np.asarray(blk, dtype=jnp.bfloat16)
            return blk
        return jax.make_array_from_callback(
            (c_pad, need), self._row_sharding(), cb)

    # -- growth ----------------------------------------------------------------
    def reserve(self, capacity: int) -> None:
        """Grow reserved row slots to at least ``capacity``, in place.

        The host buffer extends with zero rows (a memcpy of raw codes, not
        a packing event) and the cached device forms pad-extend with
        device-side ``jnp.concatenate`` -- the resident packed rows are
        never re-read or re-packed on the host, and the pack counters do
        not move.  Contents are unchanged, so ``generation`` holds too.
        """
        capacity = int(capacity)
        if capacity < self._n_rows:
            # A shrink below the live region would drop resident rows the
            # device forms still serve; refuse loudly instead of silently
            # ignoring the request.
            raise ValueError(
                f"cannot reserve capacity {capacity} below the live row "
                f"count: corpus holds {self._n_rows} live rows (capacity "
                f"{self.capacity}); shrinking a PackedCorpus is not "
                "supported")
        if capacity <= self.capacity:
            return
        grow = np.zeros((capacity - self.capacity, self.fragment_chars),
                        np.uint8)
        self._dead = np.concatenate(
            [self._dead, np.zeros(capacity - self.capacity, bool)])
        self._frags = np.concatenate([self._frags, grow], 0)
        c_pad = self.capacity_padded
        if self._swar is not None and self._swar.shape[0] < c_pad:
            self._swar = self._grow_form_rows(self._swar, c_pad)
        if self._onehot is not None and self._onehot.shape[0] < c_pad:
            self._onehot = self._grow_form_rows(self._onehot, c_pad)
        for ix in self._indexes:
            ix._on_capacity()

    def append_rows(self, rows: np.ndarray) -> int:
        """Append live rows in place; returns the first new row's index.

        Packs only the appended rows on the host and splices them into the
        cached device forms (``.at[].set``) -- zero host repacks of the
        resident rows, ever.  Capacity doubles on demand (amortized O(1)
        row writes per append); ``generation`` bumps once per call so
        generation-keyed caches see every append.
        """
        rows = np.asarray(rows, np.uint8)
        if rows.ndim == 1:
            rows = rows[None, :]
        if rows.ndim != 2 or rows.shape[1] != self.fragment_chars:
            raise ValueError(
                f"appended rows must be (n, {self.fragment_chars}); got "
                f"shape {rows.shape}")
        n = rows.shape[0]
        if n == 0:
            # An empty append is a no-op: no device launch, no generation
            # bump (a bump would needlessly drop every generation-keyed
            # result cache for contents that did not change).
            return self._n_rows
        start = self._n_rows
        if start + n > self.capacity:
            self.reserve(max(self.capacity * 2, start + n, ROW_TILE))
        self._frags[start:start + n] = rows
        self._n_rows = start + n
        self._splice_device(start, rows)
        self.generation += 1
        return start

    # -- incremental updates ---------------------------------------------------
    def _splice_device(self, start: int, rows: np.ndarray) -> None:
        """Pack ``rows`` (host, touched rows only) into the cached forms.

        Sharded forms scatter to the rows' *physical* (cyclic) positions;
        logical row ids never leak into the layout.
        """
        tr = self.obs.tracer
        with tr.span("pack",
                     {"form": "splice", "rows": rows.shape[0]}
                     if tr.enabled else None):
            self._splice_impl(start, rows)
        self.obs.metrics.counter("corpus.splice_rows").inc(rows.shape[0])

    def _splice_impl(self, start: int, rows: np.ndarray) -> None:
        n = rows.shape[0]
        phys = None
        mp = self._multiprocess
        if self.n_shards > 1:
            phys = _sharding.cyclic_physical_rows(
                np.arange(start, start + n), self.n_shards,
                self.shard_stride)
        if self._swar is not None:
            words = encoding.pack_codes_u32(rows)
            w = self._swar.shape[1]
            if words.shape[1] < w:
                words = np.concatenate(
                    [words, np.zeros((n, w - words.shape[1]), np.uint32)], 1)
            if phys is None:
                self._swar = self._swar.at[start:start + n, :].set(
                    jnp.asarray(words))
            elif mp:
                # Jitted scatter with replicated host operands: every
                # process computes the same update, XLA writes only the
                # slots its devices hold (eager .at[] would throw on
                # non-addressable shards).
                self._swar = _merge.scatter_rows(self._swar, phys, words)
            else:
                self._swar = self._swar.at[jnp.asarray(phys), :].set(
                    jnp.asarray(words))
        if self._onehot is not None:
            oh = _one_hot_flat(rows)
            w = self._onehot.shape[1]
            if oh.shape[1] < w:
                oh = np.concatenate(
                    [oh, np.zeros((n, w - oh.shape[1]), np.float32)], 1)
            if phys is None:
                self._onehot = self._onehot.at[start:start + n, :].set(
                    jnp.asarray(oh, jnp.bfloat16))
            elif mp:
                self._onehot = _merge.scatter_rows(
                    self._onehot, phys, np.asarray(oh, dtype=jnp.bfloat16))
            else:
                self._onehot = self._onehot.at[jnp.asarray(phys), :].set(
                    jnp.asarray(oh, jnp.bfloat16))
        for ix in self._indexes:
            ix._on_rows_written(start, rows)
        self.row_update_count += n

    def set_rows(self, start: int, rows: np.ndarray) -> None:
        """Overwrite live rows [start, start+n) -- packs only those rows.

        The cached device forms are updated in place (``.at[].set``), so a
        growing store (dedup) never repacks its resident rows.  Writes
        past the live region are rejected: grow with ``append_rows``.
        """
        rows = np.asarray(rows, np.uint8)
        if rows.ndim == 1:
            rows = rows[None, :]
        n = rows.shape[0]
        if rows.shape[1] != self.fragment_chars:
            raise ValueError(
                f"row width mismatch: rows have {rows.shape[1]} chars, "
                f"corpus fragments have {self.fragment_chars}")
        if start < 0 or start + n > self._n_rows:
            raise ValueError(
                f"row range [{start}, {start + n}) out of bounds for "
                f"{self._n_rows} live rows (capacity {self.capacity}); "
                "use append_rows to grow the corpus")
        self._frags[start:start + n] = rows
        self._splice_device(start, rows)
        self.generation += 1

    # -- eviction (windowed operation, DESIGN.md Sec. 3j) ----------------------
    def tombstone(self, rows) -> int:
        """Mark live rows dead; returns how many were newly tombstoned.

        O(1) device work: nothing moves and no form is touched -- the
        mask is host state that the engine's reductions honor (dead rows
        produce no threshold hits, are excluded from top-k, and report
        the -1 best-score sentinel).  ``generation`` bumps when the mask
        actually changed, so result caches never serve scores that
        include since-evicted rows.  Re-tombstoning a dead row is a
        no-op; reclaim the slots with ``compact()``.
        """
        rows = np.atleast_1d(np.asarray(rows, np.int64))
        if rows.size == 0:
            return 0
        if rows.min() < 0 or rows.max() >= self._n_rows:
            raise ValueError(
                f"tombstone rows must be in [0, {self._n_rows}), got "
                f"[{rows.min()}, {rows.max()}]")
        newly = int((~self._dead[rows]).sum())
        if newly:
            self._dead[rows] = True
            self.n_dead += newly
            self.generation += 1
            self.obs.metrics.counter("corpus.tombstoned_rows").inc(newly)
        return newly

    def compact(self) -> int:
        """Reclaim tombstoned slots; returns the number of rows dropped.

        Live rows shift down in the host buffer (order preserved: logical
        ids above a dead row shrink by the dead count below them) and only
        the rows at or after the first dead slot are re-spliced into the
        cached device forms -- the same touched-rows-only
        ``_splice_device`` path appends use, so the pack counters stay
        flat no matter how many eviction cycles the corpus lives through.
        The vacated tail is zeroed (and spliced as zeros) so it behaves
        exactly like reserved capacity.  No-op when nothing is dead.
        """
        if self.n_dead == 0:
            return 0
        tr = self.obs.tracer
        with tr.span("compact",
                     {"n_dead": self.n_dead} if tr.enabled else None):
            old_n = self._n_rows
            dead = self._dead[:old_n]
            first = int(np.argmax(dead))
            live_after = np.flatnonzero(~dead[first:]) + first
            new_n = first + live_after.size
            # Copy before overwrite: source and destination ranges
            # overlap.
            moved = np.array(self._frags[live_after])
            self._frags[first:new_n] = moved
            self._frags[new_n:old_n] = 0
            self._dead[:old_n] = False
            self.n_dead = 0
            self._n_rows = new_n
            # One splice covers the moved rows and the zeroed tail;
            # observers (CorpusIndex) ride the same notification.
            self._splice_device(first, self._frags[first:old_n])
            self.generation += 1
            self.n_compactions += 1
        self.obs.metrics.counter("corpus.compactions").inc()
        return old_n - new_n

    def invalidate(self) -> None:
        """Drop cached device forms (next query repacks)."""
        self._swar = None
        self._onehot = None
        for ix in self._indexes:
            ix._on_invalidate()
        self.generation += 1
