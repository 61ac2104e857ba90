"""Sharded streaming match executor + query compiler (DESIGN.md Sec. 3c/3e).

Single entry point for all string-matching workloads: owns a
``PackedCorpus`` (device-resident, packed once), lowers declarative
``MatchQuery`` objects through the ``Planner`` into ``CompiledMatch``
programs (kernel choice + geometry + packed pattern operands, computed
once and LRU-cached by query content), then streams corpus row-chunks through
the chosen Pallas kernel with a fused per-chunk reduction, so the full
(R, L, Q) score tensor is never materialized unless explicitly requested.

The query IR (``repro.match.query``) is the paper's reconfigurable-logic
discipline at the API: the corpus never moves; a small compiled program
(the query) is shipped to it.  ``match(patterns, **kwargs)`` remains as a
thin shim that builds the query for you.

Reductions (fused per chunk):
  best      -- per-row argmax over alignments (the paper's host extract,
               Sec. 3.2): (R,[Q]) locs + scores.
  topk      -- global top-k rows by best score (running merge across
               chunks): which corpus rows match best.
  threshold -- all (row, loc[, q]) hits with score >= threshold.
  full      -- materialized score tensor (small problems / compat path).

Predicates: exact queries ride the XOR SWAR kernel / one-hot MXU matrix;
accept-set queries (IUPAC, N wildcards, character classes) ride the
bit-plane SWAR variant / multi-hot MXU matrix -- same resident corpus
forms either way.

Sharding (DESIGN.md Sec. 3h/3k): with a ``jax.sharding.Mesh`` the corpus
rows distribute over the mesh axes mapped by the ``rows`` logical axis
(``distributed.sharding``).  Device forms and q-gram signatures live in
the *cyclic physical layout* (logical row r -> shard r % S, slot r // S)
under a ``NamedSharding``; chunks slice per-shard slot blocks (no
cross-device traffic), kernels run under ``shard_map``, and reductions
merge **device-side** through ``repro.match.merge.ShardMerger`` --
shard-local maxima combine with collectives under ``shard_map`` and only
the final reduced state crosses to the host, bit-identical to the
single-shard result at any shard *and process* count.  That is the
direct analogue of the paper's array-level parallelism (Sec. 3.4:
arrays compute independently and exchange reduced state) and of Jun et
al.'s multi-engine fan-out, and it is what lets the same engine run
multi-host on ``jax.distributed`` (``repro.launch.cluster``), where
per-shard results on another host's devices cannot be pulled at all.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import encoding
from repro.core.tech import CostSource
from repro.distributed import sharding as _sharding
from repro.obs import Observability
from repro.kernels import default_interpret
from repro.kernels import filter_qgram as _fq
from repro.kernels import match_mxu as _mxu
from repro.kernels import match_swar as _swar
from repro.kernels import ref as _kref

from .corpus import PackedCorpus
from .feedback import kernel_key
from . import index as _ix
from . import merge as _merge
from .merge import ShardMerger
from .index import CorpusIndex, FilterOperands, build_query_filter
from .planner import FilterContext, Plan, Planner, kernel_name
from .query import _UNSET, MatchQuery, as_query


@dataclasses.dataclass
class MatchResult:
    """Outcome of one engine query (reduced unless ``scores`` requested)."""

    plan: Plan
    best_locs: np.ndarray                 # (R,) or (R, Q) int
    best_scores: np.ndarray               # (R,) or (R, Q) int32
    scores: Optional[np.ndarray] = None   # (R, L[, Q]) when reduction="full"
    topk_rows: Optional[np.ndarray] = None     # (k,[Q]) best-matching rows
    topk_scores: Optional[np.ndarray] = None
    hits: Optional[np.ndarray] = None     # (n, 3|4): row, loc[, q], score
    n_chunks: int = 0
    # Filtered execution (plan.strategy == "filter"): the verify stage ran
    # on these corpus rows only; per-row arrays (best_locs/best_scores)
    # cover survivors in ascending corpus-row order, while ``hits`` stays
    # bit-identical to a full scan (the zero-false-negative invariant).
    survivor_rows: Optional[np.ndarray] = None  # (n_surv,) corpus row ids
    survivor_frac: Optional[float] = None       # n_surv / live rows
    # Resolved mesh row shards the query executed over (1 = unsharded).
    n_shards: int = 1
    # Where cross-shard results combined: "device" (collectives under
    # shard_map; only reduced state crossed to the host) or "host"
    # (single shard -- nothing to merge).  ``collective_bytes`` is the
    # estimated per-link collective traffic this run moved (ring
    # all_gather model), the quantity the Planner prices.
    merge_path: str = "host"
    collective_bytes: int = 0
    # Per-stage wall-second breakdown (plan/pack/filter/launch/merge/
    # pull) from the span tree -- populated only when the engine's
    # tracer is enabled (None otherwise), and kept out of ``repr``:
    # results print compactly either way.
    timings: Optional[dict] = dataclasses.field(default=None, repr=False)


def _valid_mask(P: int, wp: int) -> np.ndarray:
    """(1, Wp) low-bit-of-lane mask of the P valid pattern positions."""
    mask_codes = np.zeros(wp * 16, np.uint32)
    mask_codes[:P] = 1
    return encoding.pack_codes_u32(mask_codes[None, :])


def _pack_patterns_swar(codes: np.ndarray, wp: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-pack (tiny) exact pattern words + valid mask (SWAR kernel)."""
    return encoding.pack_codes_u32(codes), _valid_mask(codes.shape[-1], wp)


def _pack_mask_planes(masks: np.ndarray, wp: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-pack accept masks into (Q, 4*Wp) uint32 bit-planes + valid mask.

    Plane c has the low bit of lane i set iff code c is accepted at
    pattern position i (``match_swar_masks`` layout).
    """
    planes = [encoding.pack_codes_u32(((masks >> c) & 1).astype(np.uint32))
              for c in range(4)]
    return (np.concatenate(planes, axis=-1),
            _valid_mask(masks.shape[-1], wp))


def _pack_patterns_mxu(masks: np.ndarray, p_chars: int, q_pad: int
                       ) -> np.ndarray:
    """Host-pack (tiny) multi-hot pattern matrix (p_chars*4, q_pad).

    Column q gets a 1 at (position i, channel c) iff code c is accepted at
    position i of pattern q -- one-hot for exact queries (bit-identical to
    the historical packing), multi-hot for accept-set predicates.  The MXU
    contraction itself is unchanged: wildcards are free here.
    """
    Q, P = masks.shape
    pat_mat = np.zeros((p_chars, 4, q_pad), np.float32)
    bits = (masks[:, :, None] >> np.arange(4, dtype=np.uint8)) & 1
    pat_mat[:P, :, :Q] = bits.astype(np.float32).transpose(1, 2, 0)
    return pat_mat.reshape(p_chars * 4, q_pad)


class CompiledMatch:
    """One ``MatchQuery`` lowered against one engine: reusable, growth-safe.

    Construction does all per-query host work exactly once -- mode
    resolution (pinned: see below), planning (kernel + geometry), pattern
    packing (SWAR words / bit-planes / MXU multi-hot matrix), row-subset
    validation and padding.  ``run()`` then streams the engine's *current*
    resident corpus through the lowered program, so one compiled query
    serves every later call and every corpus generation (``set_rows``
    content updates *and* ``append_rows`` growth) without re-packing.

    Growth protocol (DESIGN.md Sec. 3f): the query **mode** is resolved
    once at compile time against the compile-time row count and pinned --
    the "(Q, P) with Q == n_rows reads as per_row" inference can never
    silently flip meaning as rows are appended.  Plan *geometry* (row
    count, chunking, padded tiling) is revalidated per run when the live
    row count moved; the packed pattern operands are row-count-independent
    and survive, unless growth shifts the roofline to a different kernel,
    in which case only the (tiny) pattern operands are re-packed -- the
    resident corpus forms are never touched.  A pinned ``per_row`` query
    is geometry-bound to its compile-time row count and refuses to run
    after growth.  Obtain via ``MatchEngine.compile`` (cached by query
    content) and treat results as read-only.
    """

    __slots__ = ("engine", "query", "plan", "_packed", "_pats2d", "_sel",
                 "_idx", "_pad_idx", "_idx_stride", "_k_eff", "_k_vec",
                 "_thr_vec", "_empty", "_mode", "_lowered", "_filter_ops",
                 "_filter_dev", "_fb_version", "_sel_max")

    def __init__(self, engine: "MatchEngine", query: MatchQuery):
        self.engine = engine
        self.query = query
        corpus = engine.corpus

        sel = query.rows
        self._sel = None if sel is None else np.asarray(sel, np.int64)
        self._empty = self._sel is not None and self._sel.size == 0
        self._packed = self._pats2d = self._idx = self._pad_idx = None
        self._idx_stride = 0
        self._sel_max = -1
        self._k_eff, self._k_vec, self._thr_vec = 0, None, None
        self._filter_ops: Optional[FilterOperands] = None
        self._filter_dev = None
        self._lowered = False
        self._fb_version = engine.planner.feedback.version
        if self._empty:
            # A legal query whose answer is no rows; geometry is still
            # validated (pattern longer than fragment, empty pattern).
            self.plan = engine._empty_plan(query)
            self._mode = self.plan.mode
            return

        if self._sel is not None:
            if self._sel.min() < 0 or self._sel.max() >= corpus.n_rows:
                # jnp gathers clamp out-of-range indices silently; fail
                # loudly instead of returning the wrong rows' scores.
                raise IndexError(
                    f"rows must be in [0, {corpus.n_rows}), got "
                    f"[{self._sel.min()}, {self._sel.max()}]")
            self._sel_max = int(self._sel.max())
            R = len(self._sel)
            R_pad = -(-R // corpus.row_pad) * corpus.row_pad
            pad_idx = np.zeros(R_pad, np.int64)
            pad_idx[:R] = self._sel
            # Logical padded ids are stable across growth; the device
            # gather indices are layout-dependent (the cyclic stride moves
            # when a sharded corpus's capacity grows) and are rebuilt
            # lazily by run() when stale.
            self._pad_idx = pad_idx
            self._idx = engine._device_gather_idx(pad_idx)
            self._idx_stride = corpus.shard_stride

        n_rows = len(self._sel) if self._sel is not None else corpus.n_rows
        # Mode pinned at compile time, before any growth can happen.
        self._mode = engine._infer_mode(query, n_rows)
        if n_rows == 0:
            # Reserved-but-empty corpus: geometry is validated now (the
            # empty plan raises on bad patterns); lowering is deferred to
            # the first run that sees live rows.
            self.plan = engine._empty_plan(query, mode=self._mode)
            return
        self._lower(n_rows)

    def _lower(self, n_rows: int) -> None:
        """Plan + pack against ``n_rows`` corpus rows (pinned mode)."""
        engine, query = self.engine, self.query
        # Filter operands are row-count independent (query content + index
        # parameters only), exactly like the packed pattern operands: they
        # are built once, survive growth and strategy changes, and the
        # device upload happens once, lazily.  Only the plan decides
        # whether run() uses them.
        ctx, self._filter_ops = engine._filter_context(
            query, self._mode, ops=self._filter_ops)
        self.plan = engine._plan_query(query, n_rows, mode=self._mode,
                                       filter_ctx=ctx)
        self._fb_version = engine.planner.feedback.version
        plan = self.plan

        # Per-query reduction parameters (batched runs only).
        k_vec = np.asarray(query.k if query.k else (10,), np.int64)
        if k_vec.size != 1 and (plan.mode != "batched"
                                or k_vec.size != plan.n_patterns):
            raise ValueError("per-query k needs a batched query with one "
                             "entry per pattern")
        self._k_vec = k_vec
        self._k_eff = int(k_vec.max())
        thr_vec = None
        if query.reduction == "threshold":
            thr_vec = np.asarray(query.threshold, np.float64)
            if plan.mode == "batched":
                if thr_vec.size == 1:
                    thr_vec = np.full(plan.n_patterns, thr_vec[0])
                elif thr_vec.size != plan.n_patterns:
                    raise ValueError("per-query thresholds need one entry "
                                     "per pattern")
            elif thr_vec.size != 1:
                raise ValueError("per-query thresholds need a batched query")
        self._thr_vec = thr_vec

        # Pattern operands, packed once (the compile-time win: repeated
        # runs skip all host-side pattern work).
        masks2d = query.masks if len(query.shape) == 2 else \
            query.masks[None, :]
        if plan.predicate == "exact":
            codes = query.codes
            self._pats2d = codes if codes.ndim == 2 else codes[None, :]
        else:
            self._pats2d = masks2d
        if plan.backend == "swar":
            if plan.predicate == "accept":
                pat_rows, valid = _pack_mask_planes(masks2d, plan.wp)
            else:
                pat_rows, valid = _pack_patterns_swar(self._pats2d, plan.wp)
            if engine.merger.multiprocess:
                # Multi-controller: keep the (tiny) operands as host
                # arrays -- every process holds identical copies, and the
                # jitted shard_map dispatch places them per its in_specs.
                # A committed single-device upload could not be resharded
                # onto a mesh spanning other processes' devices.
                self._packed = (pat_rows, valid)
            else:
                # Upload once at compile time; run() chunks reuse the
                # resident device operands.
                self._packed = (jnp.asarray(pat_rows), jnp.asarray(valid))
        elif plan.backend == "mxu":
            mat = _pack_patterns_mxu(masks2d, plan.p_chars_pad, plan.q_pad)
            self._packed = (np.asarray(mat, jnp.bfloat16)
                            if engine.merger.multiprocess
                            else jnp.asarray(mat, jnp.bfloat16))
        else:
            self._packed = None
        self._lowered = True

    def _revalidate(self, n_rows: int) -> None:
        """Refresh plan geometry for a corpus whose live row count moved.

        Mode stays pinned; the packed pattern operands are row-count
        independent, so only the plan (chunking, padded row count, cost
        estimate) is recomputed -- unless the roofline now picks a
        different kernel, in which case the tiny pattern operands are
        re-packed too.  The resident corpus forms are untouched either
        way.  The filter strategy is re-decided here too (scale and
        measured selectivity move the two-stage tradeoff); the cached
        filter operands are row-count independent and passed back so only
        the survivor estimate refreshes.
        """
        ctx, self._filter_ops = self.engine._filter_context(
            self.query, self._mode, ops=self._filter_ops)
        new_plan = self.engine._plan_query(self.query, n_rows,
                                           mode=self._mode, filter_ctx=ctx)
        self._fb_version = self.engine.planner.feedback.version
        if new_plan.backend != self.plan.backend:
            self._lower(n_rows)
        else:
            self.plan = new_plan

    # -- execution ------------------------------------------------------------
    def run(self) -> MatchResult:
        """Execute against the engine's current corpus contents.

        Safe across corpus growth: geometry is revalidated when the live
        row count changed since the last run (see class docstring).  A
        ``plan.strategy == "filter"`` query runs the two-stage pipeline:
        the q-gram filter kernel prunes rows that provably cannot reach
        the threshold, then the survivors verify through the same gather
        machinery that serves explicit ``rows=`` subsets -- ``hits`` are
        bit-identical to the full scan by the conservativeness of the
        filter (DESIGN.md Sec. 3g).

        With the engine's tracer enabled the whole execution runs under
        a ``match.run`` span (plan / pack / filter / launch / merge /
        pull children, one ``chunk.host`` per chunk and a ``result``
        around the answer's assembly) and the result carries the
        per-stage breakdown in ``timings``; disabled (the default) this
        wrapper is two branch instructions.
        """
        tr = self.engine.obs.tracer
        if not tr.enabled:
            return self._run()
        with tr.span("match.run",
                     {"reduction": self.query.reduction}) as root:
            res = self._run()
        res.timings = root.stage_seconds()
        return res

    def _note_plan(self, sp) -> None:
        """Planner-decision attributes onto an open ``plan`` span."""
        p = self.plan
        sp.set("kernel", kernel_name(p.backend, p.predicate))
        sp.set("strategy", p.strategy)
        sp.set("cost_source", p.cost_source)
        sp.set("est_seconds", p.est_seconds)
        sp.set("est_collective_bytes", p.est_collective_bytes)
        sp.set("n_rows", p.n_rows)
        sp.set("n_shards", p.n_shards)

    def _run(self) -> MatchResult:
        """The streaming executor behind ``run()`` (span-instrumented)."""
        if self._empty:
            return self.engine._empty_result(self.query, self.plan)
        engine, query = self.engine, self.query
        tr = engine.obs.tracer
        reduction = query.reduction
        sel = self._sel
        survivor_frac = None
        # Tombstone mask (windowed corpus, DESIGN.md Sec. 3j): dead rows
        # stay physically resident so the kernels run unchanged; the
        # reductions below mask them out on the host.  None when nothing
        # is dead -- the append-only fast path pays zero extra work.
        dead_full = (engine.corpus.dead_mask if engine.corpus.n_dead
                     else None)
        if sel is not None:
            with tr.span("plan") as sp_plan:
                if self._sel_max >= engine.corpus.n_rows:
                    # compact() shrank the live region below a row this
                    # subset names; the gather would silently clamp to a
                    # wrong row.
                    raise IndexError(
                        f"rows subset names row {self._sel_max} but the "
                        f"corpus now holds {engine.corpus.n_rows} live rows "
                        "(did compact() reclaim evicted rows?); recompile "
                        "with current row ids")
                R = len(sel)
                if (engine._row_shards > 1
                        and self._idx_stride != engine.corpus.shard_stride):
                    # Sharded capacity growth moved the cyclic stride: the
                    # logical ids are unchanged, re-derive their physical
                    # positions.
                    self._idx = engine._device_gather_idx(self._pad_idx)
                    self._idx_stride = engine.corpus.shard_stride
                if tr.enabled:
                    self._note_plan(sp_plan)
            idx, idx_log = self._idx, self._pad_idx
            R_pad = idx.shape[0]
        else:
            idx = idx_log = None
            R = engine.corpus.n_rows
            if R == 0:
                # Reserved-but-empty corpus: the answer is no rows (yet).
                return engine._empty_result(query, self.plan)
            R_pad = engine.corpus.n_rows_padded
            with tr.span("plan") as sp_plan:
                if not self._lowered:
                    self._lower(R)
                elif (self.plan.n_rows != R
                      or engine.planner.feedback.version != self._fb_version):
                    # Row count moved *or* the feedback store re-priced some
                    # bucket since this program was planned: either can flip
                    # the kernel or strategy choice, so re-plan (a backend
                    # flip re-packs only the tiny pattern operands).
                    self._revalidate(R)
                if tr.enabled:
                    self._note_plan(sp_plan)
            if self.plan.strategy == "filter":
                with tr.span("filter") as sp_fil:
                    t0 = time.perf_counter()
                    sel = engine._run_filter(self, R)
                    t_fil = time.perf_counter() - t0
                    if dead_full is not None:
                        # Tombstoned rows can survive the signature test
                        # but must not reach the verify stage (nor the
                        # hits).
                        with tr.span("filter.union"):
                            sel = sel[~dead_full[sel]]
                    survivor_frac = len(sel) / R
                    if tr.enabled:
                        sp_fil.set("survivor_frac", survivor_frac)
                ops = self._filter_ops
                engine.index.record_selectivity(
                    engine.index.estimate_survivor_frac(
                        ops.n_bits, ops.slacks, calibrated=False),
                    survivor_frac)
                # Plan-vs-actual: one record per executed filter stage,
                # same key and same floats as the feedback observation
                # (computed once, handed to both sinks -- the registry is
                # pure accounting and records unconditionally).
                p0 = self.plan
                r_sh = -(-p0.n_rows // p0.n_shards)
                f_key = kernel_key("filter", r_sh, p0.filter_words,
                                   ops.qsig_words.shape[0])
                engine.obs.record_plan_actual(
                    f_key, p0.est_filter_base_seconds, t_fil)
                if engine.record_runtimes:
                    engine.planner.feedback.observe(
                        f_key, p0.est_filter_base_seconds, t_fil)
                if len(sel) == 0:
                    res = engine._empty_result(query, self.plan)
                    res.survivor_rows = sel
                    res.survivor_frac = 0.0
                    return res
                R = len(sel)
                # Survivor counts differ per query and per generation;
                # padding them to a power of two keeps the verify
                # stage's compiled shapes to one per octave.
                row_pad = engine.corpus.row_pad
                R_pad = -(-(1 << (R - 1).bit_length()) // row_pad) * row_pad
                pad_idx = np.zeros(R_pad, np.int64)
                pad_idx[:R] = sel
                idx_log = pad_idx
                idx = engine._device_gather_idx(pad_idx)
        plan = self.plan
        step = plan.chunk_rows
        S = engine._row_shards
        merger = engine.merger
        coll0 = merger.collective_bytes
        if S > 1:
            tile = _swar.ROW_TILE * S
            step = max(tile, (step // tile) * tile)
        # Resident sharded streaming: device forms are in the cyclic
        # physical layout, so per-chunk kernel output rows come back in
        # physical (shard-major) order; the merge layer un-permutes
        # *inside* its collective pulls.  Gather paths (rows= subsets,
        # filter survivors) already follow logical order -- the gather
        # indices are physical, their order is not -- and the ref backend
        # reads the logical host buffer directly.
        shard_phys = S > 1 and idx is None and plan.backend != "ref"
        # A resident scan's last chunk runs on into reserved capacity (the
        # device forms cover it, as zero rows) up to a whole step, so the
        # chunk shapes -- and the programs compiled for them -- depend on
        # the step and the capacity, not on the live row count: appends,
        # tombstones and compaction within capacity compile nothing new.
        end = R_pad if idx is not None else engine.corpus.capacity_padded

        best_l: List[np.ndarray] = []
        best_s: List[np.ndarray] = []
        full: List[np.ndarray] = []
        hit_rows: List[np.ndarray] = []
        topk_state = None                 # running global top-k (device)
        n_topk_alive = 0
        n_chunks = 0
        thr_vec = self._thr_vec
        thr_int = None
        if thr_vec is not None:
            # Integer-exact device threshold: scores are ints, so
            # s >= t  <=>  s >= ceil(t).  The device hot-mask compares
            # int32; the host recomputes final hits with the original
            # float threshold over the gathered block -- the two select
            # exactly the same set (no float32 rounding can differ).
            thr_int = np.clip(np.ceil(thr_vec), -(2 ** 31),
                              2 ** 31 - 1).astype(np.int32)

        t_scan0 = time.perf_counter()
        for c0 in range(0, R_pad, step):
            c1 = min(c0 + step, end)
            valid = min(c1, R) - c0       # rows in this chunk that are real
            if valid <= 0:
                break                     # pure-padding tail chunk
            # Everything the host does for one chunk; the launch, merge
            # and pull spans nest inside, so this span's self time is
            # the chunk's host bookkeeping.
            with tr.span("chunk.host"):
                # The launch span measures kernel *dispatch* (JAX is async);
                # the device wait lands in the merge layer's pull spans.
                with tr.span("launch", {"c0": c0, "rows": valid}
                             if tr.enabled else None):
                    scores = engine._chunk_scores(plan, self._pats2d, c0,
                                                  c1, self._packed, idx,
                                                  idx_log)
                n_chunks += 1
                # Per-chunk tombstone mask in logical row order (None when the
                # whole chunk is alive).
                alive = None
                if dead_full is not None:
                    chunk_ids = (np.arange(c0, c0 + valid, dtype=np.int64)
                                 if sel is None
                                 else np.asarray(sel[c0:c0 + valid]))
                    alive = ~dead_full[chunk_ids]
                    if alive.all():
                        alive = None
                if reduction == "full":
                    # Host materialization is the point of this reduction (the
                    # one case where the whole block crosses); the pull
                    # replicates + un-permutes device-side first.
                    sc = merger.pull(scores, unpermute=shard_phys,
                                     kind="block")[:valid]
                    if alive is not None:
                        # Dead rows report the -1 sentinel (scores are >= 0
                        # for live rows, so the sentinel is unambiguous).
                        sc = sc.copy()
                        sc[~alive] = -1
                    full.append(sc)
                    continue
                # Fused per-chunk reduction, jitted through the merge layer:
                # only reduced per-row state ever crosses to the host, and no
                # eager op touches a (possibly non-addressable) sharded array.
                bl, bs = merger.chunk_best(scores)
                bl_np = merger.pull(bl, unpermute=shard_phys)[:valid]
                bs_np = merger.pull(bs, unpermute=shard_phys)[:valid]
                if alive is not None:
                    bl_np, bs_np = bl_np.copy(), bs_np.copy()
                    bl_np[~alive] = 0
                    bs_np[~alive] = -1        # dead-row best-score sentinel
                best_l.append(bl_np)
                best_s.append(bs_np)
                # topk / threshold report *corpus* row ids; with a rows= subset
                # that means mapping chunk positions through the selection.
                if reduction == "threshold":
                    # Two-phase sparse pull (the per-chunk host-transfer fix):
                    # first a per-row any-hit bitmap, then a device gather of
                    # only the hot rows' score vectors -- the full (chunk, L
                    # [, Q]) block never crosses to the host.
                    hot = merger.hot_mask(scores, thr_int)
                    hot_np = merger.pull(hot, unpermute=shard_phys)[:valid]
                    if alive is not None:
                        hot_np = hot_np & alive
                    hot_rows = np.flatnonzero(hot_np)
                    if hot_rows.size == 0:
                        continue
                    if shard_phys:
                        # Physical positions of the hot logical rows inside
                        # this chunk's shard-major layout.
                        jc = int(scores.shape[0]) // S
                        pos = (hot_rows % S) * jc + hot_rows // S
                    else:
                        pos = hot_rows
                    # Pad the gather to a power of two so hot-count jitter
                    # doesn't recompile the gather every chunk.
                    n_hot = pos.size
                    pad_n = max(8, 1 << (int(n_hot) - 1).bit_length())
                    pos_pad = np.zeros(pad_n, np.int64)
                    pos_pad[:n_hot] = pos
                    sc = merger.pull(merger.gather_rows(scores, pos_pad),
                                     kind="block")[:n_hot]
                    if plan.mode == "batched":
                        local = np.argwhere(sc >= thr_vec[None, None, :])
                    else:
                        local = np.argwhere(sc >= float(thr_vec[0]))
                    if local.size:
                        vals = sc[tuple(local.T)]
                        # Hot rows are ascending, so argwhere order over the
                        # gathered block equals the full-block hit order.
                        rows_chunk = hot_rows[local[:, 0]]
                        local[:, 0] = (sel[rows_chunk + c0] if sel is not None
                                       else rows_chunk + c0)
                        hit_rows.append(np.concatenate(
                            [local, vals[:, None].astype(np.int64)], 1))
                elif reduction == "topk":
                    # Device-side tree merge (ShardMerger): shard-local maxima
                    # + all_gather + replicated lexsort, or -- on logical-order
                    # paths -- a jitted sentinel merge.  Dead/padding rows ride
                    # the (-1, ROW_SENTINEL) sentinel pair and sort last.
                    if topk_state is None:
                        topk_state = merger.topk_init(
                            self._k_eff,
                            plan.n_patterns if plan.mode == "batched" else 0)
                    n_bs = int(bs.shape[0])
                    alive_chunk = np.zeros(n_bs, bool)
                    alive_chunk[:valid] = True if alive is None else alive
                    n_topk_alive += (valid if alive is None
                                     else int(alive.sum()))
                    if shard_phys:
                        topk_state = merger.topk_update(
                            topk_state, bs, phys=True,
                            alive_chunk=alive_chunk, c0=c0)
                    else:
                        rows_full = np.zeros(n_bs, np.int64)
                        rows_full[:valid] = (np.arange(c0, c0 + valid)
                                             if sel is None
                                             else sel[c0:c0 + valid])
                        topk_state = merger.topk_update(
                            topk_state, bs, phys=False,
                            alive_chunk=alive_chunk, rows_np=rows_full)

        # Assembling the answer: concatenations, the top-k finalize and
        # hits, plan-vs-actual and feedback records.
        with tr.span("result"):
            if n_chunks:
                # Observed scan/verify-stage wall time vs. the feedback-free
                # estimate at the *actual* rows scanned (for a filtered run the
                # plan priced estimated survivors; recomputing at the measured
                # count keeps selectivity error out of the kernel-cost EWMA --
                # selectivity has its own feedback in CorpusIndex).  The ref
                # backend is priced at total rows, kernels per shard.  The
                # plan-vs-actual registry always gets the record; the feedback
                # store (which mutates future plans) only when enabled.
                r_price = (R if plan.backend == "ref"
                           else -(-R // plan.n_shards))
                base = engine.planner.backend_seconds(
                    plan.backend, r_price, plan.n_locs, plan.pattern_chars,
                    plan.n_patterns, plan.predicate, base=True)
                s_key = kernel_key(kernel_name(plan.backend,
                                               plan.predicate), r_price,
                                   plan.pattern_chars, plan.n_patterns)
                t_scan = time.perf_counter() - t_scan0
                engine.obs.record_plan_actual(s_key, base, t_scan)
                if engine.record_runtimes:
                    engine.planner.feedback.observe(s_key, base, t_scan)

            if reduction == "full":
                all_scores = np.concatenate(full, 0)
                return MatchResult(plan=plan, best_locs=all_scores.argmax(1),
                                   best_scores=all_scores.max(1),
                                   scores=all_scores, n_chunks=n_chunks,
                                   n_shards=S, merge_path=merger.merge_path,
                                   collective_bytes=merger.collective_bytes
                                   - coll0)
            best_locs = np.concatenate(best_l, 0)
            best_scores = np.concatenate(best_s, 0)
            res = MatchResult(plan=plan, best_locs=best_locs,
                              best_scores=best_scores, n_chunks=n_chunks,
                              n_shards=S, merge_path=merger.merge_path)
            if survivor_frac is not None:
                res.survivor_rows = sel
                res.survivor_frac = survivor_frac
            if reduction == "threshold":
                width = 3 + (1 if plan.mode == "batched" else 0)
                res.hits = (np.concatenate(hit_rows, 0) if hit_rows
                            else np.zeros((0, width), np.int64))
            elif reduction == "topk":
                if topk_state is None or n_topk_alive == 0:
                    # Every scanned row was tombstoned: a well-formed empty
                    # top-k (matches the empty-subset result shape).
                    shape0 = ((0, plan.n_patterns) if plan.mode == "batched"
                              else (0,))
                    res.topk_rows = np.zeros(shape0, np.int64)
                    res.topk_scores = np.zeros(shape0, np.int32)
                else:
                    res.topk_rows, res.topk_scores = merger.topk_finalize(
                        topk_state, n_topk_alive, self._k_eff)
            res.collective_bytes = merger.collective_bytes - coll0
            return res

    __call__ = run


class MatchEngine:
    """Planner + packed corpus + query compiler + streaming executor.

    ``corpus`` may be a PackedCorpus or a raw (R, F) uint8 fragment matrix.
    ``mesh`` (optional) shards corpus rows over the mesh axes the ``rows``
    logical rule maps to; pass ``rules`` to use a non-default rule table.
    ``compile(query)`` is the primary API; ``match`` / ``scores`` are
    kwarg shims that build (and content-cache) the query for you.
    """

    def __init__(self, corpus: Union[PackedCorpus, np.ndarray], *,
                 planner: Optional[Planner] = None,
                 cost_source: Optional[CostSource] = None,
                 record_runtimes: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 mesh: Optional[Mesh] = None, rules=None,
                 compile_cache_size: int = 128,
                 index: Union[bool, CorpusIndex] = True,
                 obs: Optional[Observability] = None):
        # Observability handle (DESIGN.md Sec. 3l): spans off by default
        # (and free when off); the metrics registry is always on -- it
        # only observes, never feeds back into plans, so it is safe at
        # any process count.  Shared with the corpus, index, merger, and
        # any MatchService/PatternBank built on this engine.
        self.obs = obs if obs is not None else Observability()
        n_row_slots = (corpus.capacity if isinstance(corpus, PackedCorpus)
                       else np.asarray(corpus).shape[0])
        if n_row_slots < 1:
            # Fail at construction, not deep inside the planner on the
            # first query ("corpus has no rows" with no context).  A
            # growable corpus with reserved capacity but no live rows yet
            # is fine: queries answer "no rows" until the first append.
            raise ValueError("MatchEngine needs a non-empty corpus: got 0 "
                             "fragment rows and no reserved capacity "
                             "(PackedCorpus(..., capacity=N) to start "
                             "empty)")
        self.mesh = mesh
        self.rules = rules
        self._row_shards = 1
        self._row_axes: Optional[Tuple[str, ...]] = None
        row_pad = _swar.ROW_TILE
        if mesh is not None:
            # warn=True: an indivisible row count silently replicating is
            # the invisible perf cliff of the satellite fix -- the caller
            # asked for a mesh and gets 1 shard; say so.
            r = _sharding.resolve_axis(
                "rows", -(-n_row_slots // _swar.ROW_TILE) * _swar.ROW_TILE,
                mesh, rules, warn=True)
            if r is not None:
                self._row_axes = r if isinstance(r, tuple) else (r,)
                self._row_shards = int(
                    np.prod([mesh.shape[a] for a in self._row_axes]))
                row_pad = _swar.ROW_TILE * self._row_shards
        if isinstance(corpus, PackedCorpus):
            self.corpus = corpus
        else:
            self.corpus = PackedCorpus(np.asarray(corpus, np.uint8),
                                       row_pad=row_pad)
        # Pack/splice/compact spans record into this engine's tracer
        # (engines sharing a corpus share whichever was attached last).
        self.corpus.obs = self.obs
        # Configure the cyclic row layout + NamedSharding placement (a
        # no-op when the corpus already has this exact layout).
        self.corpus.shard_rows(
            mesh if self._row_shards > 1 else None,
            self._row_axes if self._row_axes is None or
            len(self._row_axes) > 1 else self._row_axes[0],
            self._row_shards)
        # Cross-shard merge layer (DESIGN.md Sec. 3k): every reduction
        # and host pull routes through it, so cross-shard combines run
        # device-side under shard_map and work at any process count.
        self.merger = ShardMerger(
            self.mesh if self._row_shards > 1 else None,
            self._row_axes, self._row_shards, obs=self.obs)
        # Jitted sharded launches (keyed by kernel + static parameters;
        # jit itself keys the shapes): a fresh shard_map per chunk would
        # retrace and recompile every call.
        self._launch_cache: dict = {}
        if planner is None:
            planner = Planner(cost_source=cost_source)
        elif cost_source is not None:
            planner.cost_source = cost_source
        self.planner = planner
        # Runtime feedback (DESIGN.md Sec. 3i): record observed per-launch
        # wall times into the planner's FeedbackStore so drifted (kernel,
        # shape-bucket) estimates get re-priced online.  Default: on when
        # the source is calibrated (feedback is the serving half of that
        # discipline), off for the static fallback -- whose decisions are
        # a deterministic baseline that must not drift mid-session.
        if record_runtimes is None:
            # Multi-controller: per-process wall clocks differ, so
            # feedback re-pricing would drift the SPMD plans apart across
            # processes (divergent plans mean divergent collective
            # programs -- a hang).  Default off beyond one process.
            record_runtimes = (self.planner.cost_source.name != "static"
                               and jax.process_count() == 1)
        self.record_runtimes = bool(record_runtimes)
        self.interpret = default_interpret() if interpret is None else interpret
        self.compile_cache_size = int(compile_cache_size)
        self._compiled: "OrderedDict[MatchQuery, CompiledMatch]" = \
            OrderedDict()
        # Q-gram filter index (DESIGN.md Sec. 3g): attached up front (the
        # signature pack itself is lazy, so an engine that never runs a
        # filtered query pays nothing); ``index=False`` disables the
        # two-stage strategy, a ``CorpusIndex`` instance overrides the
        # default (q, n_bits) configuration.
        if isinstance(index, CorpusIndex):
            if index.corpus is not self.corpus:
                raise ValueError("index is attached to a different corpus")
            self.index: Optional[CorpusIndex] = index
        elif index and self.corpus.fragment_chars >= _ix.DEFAULT_Q:
            # Engines sharing a corpus share its index (and its resident
            # signatures + selectivity calibration) instead of stacking a
            # fresh observer per engine.
            self.index = next(
                (ix for ix in self.corpus._indexes
                 if isinstance(ix, CorpusIndex)), None) \
                or CorpusIndex(self.corpus)
        else:
            self.index = None

    def __repr__(self) -> str:
        c = self.corpus
        axes = (None if self._row_axes is None else
                ",".join(self._row_axes))
        return (f"MatchEngine(rows={c.n_rows}, capacity={c.capacity}, "
                f"shards={self._row_shards}"
                + (f" over {axes}" if axes else "")
                + f", interpret={self.interpret}"
                + f", cost={self.planner.cost_source.tag})")

    @property
    def n_shards(self) -> int:
        """Resolved mesh row shards (1 when unsharded or replicated)."""
        return self._row_shards

    def shard_live_rows(self) -> np.ndarray:
        """(S,) live rows per shard (cyclic layout: balanced to +-1 row)."""
        return self.corpus.shard_live_rows

    def _device_gather_idx(self, pad_idx: np.ndarray) -> np.ndarray:
        """Gather indices (host array) for logical padded row ids.

        Sharded forms store row r at physical position (r % S) * J +
        r // S; gathers must address that layout.  The gather *output*
        follows the order of ``pad_idx`` (logical query order), so
        downstream reductions never see physical order on this path.
        Kept as a host array: identical on every process, handed to the
        (jitted) gather at dispatch time.
        """
        return _sharding.cyclic_physical_rows(
            pad_idx, self._row_shards, self.corpus.shard_stride)

    # -- compilation ----------------------------------------------------------
    def compile(self, query: MatchQuery, *,
                cached: bool = True) -> CompiledMatch:
        """Lower a query once (plan + pack); LRU-cached by query content.

        The returned ``CompiledMatch`` is reusable across calls and corpus
        generations -- the warm path pays zero planning or pattern-packing
        work.  ``cached=False`` forces a fresh lowering (benchmarks use it
        to measure exactly that work).
        """
        if not isinstance(query, MatchQuery):
            raise TypeError("compile() takes a MatchQuery; use "
                            "MatchQuery.exact/from_masks/iupac or the "
                            "match(patterns, ...) shim")
        if cached:
            hit = self._compiled.get(query)
            if hit is not None:
                self._compiled.move_to_end(query)
                return hit
        cm = CompiledMatch(self, query)
        if cached:
            self._compiled[query] = cm
            while len(self._compiled) > self.compile_cache_size:
                self._compiled.popitem(last=False)
        return cm

    # -- planning -------------------------------------------------------------
    def _infer_mode(self, query: MatchQuery, n_rows: int) -> str:
        ndim = len(query.shape)
        if ndim == 1:
            return "shared"
        mode = query.mode
        if mode is not None:
            if mode == "per_row" and query.shape[0] != n_rows:
                raise ValueError(
                    "per_row patterns must have one row per corpus row: "
                    f"got {query.shape[0]} pattern rows for {n_rows} live "
                    "rows (did the corpus grow since the query was "
                    "compiled?)")
            return mode
        # (Q, P) with Q == n_rows is ambiguous; resolve like the historical
        # ops API: the mxu kernel is inherently batched, everything else
        # reads a row-count match as per-row.  Pass mode= to be explicit.
        # CompiledMatch pins this resolution at compile time, so appends
        # can never flip an inferred per_row into batched (or vice versa).
        if query.backend == "mxu":
            return "batched"
        return "per_row" if query.shape[0] == n_rows else "batched"

    def _plan_query(self, query: MatchQuery, n_rows: int,
                    mode: Optional[str] = None,
                    filter_ctx: Optional[FilterContext] = None) -> Plan:
        if mode is None:
            mode = self._infer_mode(query, n_rows)
        elif mode == "per_row" and query.shape[0] != n_rows:
            raise ValueError(
                f"per_row query compiled for {query.shape[0]} corpus rows "
                f"cannot run against {n_rows} live rows; per_row queries "
                "are geometry-bound to their compile-time corpus -- "
                "recompile with one pattern per current corpus row")
        topk_k = 0
        if query.reduction == "topk":
            kv = np.asarray(query.k if query.k else (10,), np.int64)
            topk_k = int(kv.max()) if kv.size else 10
        return self.planner.plan(
            n_rows=n_rows,
            fragment_chars=self.corpus.fragment_chars,
            pattern_chars=query.pattern_chars,
            n_patterns=query.n_patterns if mode == "batched" else None,
            per_row=mode == "per_row", backend=query.backend,
            chunk_rows=query.chunk_rows, predicate=query.predicate,
            filter_ctx=filter_ctx, n_shards=self._row_shards,
            reduction=query.reduction, topk_k=topk_k)

    # -- q-gram filter stage (DESIGN.md Sec. 3g) ------------------------------
    def _filter_context(self, query: MatchQuery, mode: Optional[str],
                        ops: Optional[FilterOperands] = None
                        ) -> Tuple[Optional[FilterContext],
                                   Optional[FilterOperands]]:
        """Filter eligibility + pricing inputs + operands for one query.

        Returns ``(None, None)`` when the two-stage strategy is not legal:
        the filter prunes whole rows, so only the row-sparse ``threshold``
        reduction (whose deliverable, ``hits``, provably loses nothing to
        conservative pruning) qualifies; explicit row subsets keep their
        own gather path; per-row patterns have no shared signature.
        Sharded engines participate like single-shard ones (the signature
        form mirrors the corpus layout and the filter kernel runs per
        shard under shard_map).  Ineligible or unprunable queries simply
        scan -- the filter is an optimization, never a semantic change.

        ``ops`` short-circuits the operand build: the operands derive
        from (query content, index q, index B) only, so a caller holding
        them from an earlier lowering (CompiledMatch revalidating across
        growth) passes them back and only the survivor estimate -- which
        tracks measured density and selectivity -- is refreshed.
        """
        if query.filter is True and self._row_shards > 1:
            # Sharded engines must never *silently* drop filter=True to a
            # full scan (the pre-Sec.-3h engine did exactly that): when
            # the forced strategy is structurally impossible, say so.
            why = None
            if self.index is None:
                why = "no CorpusIndex is attached (index=False)"
            elif query.rows_b is not None:
                why = "row-subset queries keep their own gather path"
            elif mode == "per_row":
                why = "per-row patterns have no shared signature"
            elif query.pattern_chars < self.index.q:
                why = (f"pattern ({query.pattern_chars} chars) is shorter "
                       f"than the index q-gram (q={self.index.q})")
            if why is not None:
                raise ValueError(
                    f"sharded engine cannot honor filter=True: {why}; "
                    "pass filter=None to let the planner decide or "
                    "filter=False to scan")
        if (self.index is None or query.filter is False
                or query.reduction != "threshold"
                or query.rows_b is not None or mode == "per_row"
                or query.pattern_chars < self.index.q):
            return None, None
        masks2d = query.masks if len(query.shape) == 2 else \
            query.masks[None, :]
        if ops is None:
            thr = query.threshold
            if len(thr) == 1 and masks2d.shape[0] > 1:
                thr = thr * masks2d.shape[0]
            ops = build_query_filter(masks2d, thr, self.index.q,
                                     self.index.n_bits)
        # A query whose slack covers all its required bits passes every
        # row (so does one with no fully-exact q-grams): with a survivor
        # union, one such member makes the whole filter pointless.
        # Prunability is content-derived and never changes across growth,
        # so the operands are still returned (and cached by the caller) --
        # a held unprunable query must not rebuild them on every
        # revalidation just to re-learn it scans.
        prunable = all(s < 0 or (b > 0 and s < b)
                       for b, s in zip(ops.n_bits, ops.slacks))
        if not prunable:
            return None, ops
        frac = self.index.estimate_survivor_frac(ops.n_bits, ops.slacks)
        ctx = FilterContext(sig_words=self.index.sig_words,
                            n_queries=masks2d.shape[0], prunable=True,
                            survivor_frac=frac,
                            force=query.filter is True)
        return ctx, ops

    def _run_filter(self, cm: CompiledMatch, n_rows: int) -> np.ndarray:
        """Filter stage: ascending ids of the candidate rows of one query.

        One ``filter_qgram`` dispatch for the whole group: each row tile
        of the resident signatures is read once and tested against every
        pattern, and a row survives if any pattern admits it (the batched
        union).  Signatures stream from the device-resident index -- the
        exact scan's data is never touched for pruned rows.

        Sharded engines run the kernel per shard under ``shard_map`` over
        the sharded signature form: each shard tests its own rows (the
        q-gram lemma is a per-row property, so it holds per shard), and
        the cross-shard survivor union is a device all_gather through the
        merge layer -- the host receives only the final replicated
        bitmap, one bit per row, at any process count.
        """
        ops = cm._filter_ops
        merger = self.merger
        if cm._filter_dev is None:
            # Multi-controller: keep the tiny pattern operands as host
            # arrays (identical everywhere); the jitted dispatch places
            # them replicated per its in_specs.
            dev = _fq.pattern_operands(ops.qsig_words, ops.slacks)
            cm._filter_dev = (dev if merger.multiprocess
                              else tuple(jnp.asarray(a) for a in dev))
        # The kernel tests the signature form's whole extent (reserved
        # rows are zero and their flags are dropped), so its compiled
        # shape follows the capacity, not the live row count.
        rows = self.index.signatures()
        tr = self.obs.tracer

        def filter_launch(r, q, s):
            return _fq.filter_qgram(r, q, s, interpret=self.interpret)
        with tr.span("filter.launch"):
            flags = self._shard_wrap(filter_launch, ("filter",), rep_args=2,
                                     row_axis=1)(rows, *cm._filter_dev)
        metrics = self.obs.metrics
        metrics.counter("filter.dispatches").inc()
        metrics.counter("filter.patterns").inc(len(ops.slacks))
        with tr.span("filter.union"):
            # The cross-shard union is the device-side all_gather; the
            # host receives one bit per row and maps each shard's slots
            # to logical rows.
            sel = _fq.survivor_rows(
                merger.pull(flags, kind="reduced", axis=1),
                self.index.sig_words, merger.n_shards)
            return sel[:np.searchsorted(sel, n_rows)]

    def plan(self, patterns, *, backend=_UNSET, mode=_UNSET, rows=_UNSET,
             chunk_rows=_UNSET) -> Plan:
        """Plan without executing (kwarg shim over ``_plan_query``)."""
        query = as_query(patterns, backend=backend, mode=mode, rows=rows,
                         chunk_rows=chunk_rows)
        n_rows = (len(query.rows) if query.rows is not None
                  else self.corpus.n_rows)
        return self._plan_query(query, n_rows)

    # -- kernel dispatch (one chunk, pure device) -----------------------------
    def _shard_wrap(self, call, cache_key, row_args: int = 1,
                    rep_args: int = 1, row_axis: int = 0):
        """``call`` as one jitted ``shard_map`` launch over the row axes.

        ``call`` takes ``row_args`` row-sharded operands, then
        ``rep_args`` replicated ones; operands and output hold their rows
        along ``row_axis``.  Launches are cached by
        ``cache_key``, which must name everything ``call`` closes over: a
        fresh closure per chunk would trace and compile every chunk
        again.  Jitted at any process count (multi-controller, eager
        dispatch on global arrays is not supported; host-array operands
        get placed per the in_specs).  Unsharded engines get ``call``.
        """
        if self.mesh is None or self._row_axes is None:
            return call
        fn = self._launch_cache.get(cache_key)
        if fn is None:
            spec = PartitionSpec(*(None,) * row_axis,
                                 self._row_axes if len(self._row_axes) > 1
                                 else self._row_axes[0])
            fn = jax.jit(jax.shard_map(
                call, mesh=self.mesh,
                in_specs=(spec,) * row_args + (PartitionSpec(),) * rep_args,
                out_specs=spec, check_vma=False))
            self._launch_cache[cache_key] = fn
        return fn

    def _swar_chunk(self, words: jnp.ndarray, pat_rows: jnp.ndarray,
                    mask: jnp.ndarray, plan: Plan) -> jnp.ndarray:
        kern = (_swar.match_swar_masks if plan.predicate == "accept"
                else _swar.match_swar)

        def swar_launch(w, p, m):
            return kern(w, p, m, n_locs=plan.n_locs,
                        pattern_chars=plan.pattern_chars,
                        interpret=self.interpret)
        key = ("swar", plan.predicate, plan.n_locs, plan.pattern_chars)
        return self._shard_wrap(swar_launch, key, row_args=2)(
            words, pat_rows, mask)

    def _swar_chunk_mp(self, words, pat_rows, mask, plan: Plan):
        """Multi-controller SWAR dispatch: one jitted shard_map launch.

        The (tiny, replicated) host pattern operands enter with a
        replicated spec and broadcast to each shard's block *inside* the
        body -- an eager full-size broadcast would be a committed local
        array that cannot be resharded onto other processes' devices.
        Shared-pattern queries only: per-row and batched SWAR layouts
        interleave pattern rows across shards (tile/repeat on a sharded
        chunk), which has no multi-process lowering yet.
        """
        if plan.mode in ("per_row", "batched"):
            raise NotImplementedError(
                f"{plan.mode} SWAR queries are not supported on a "
                "multi-process mesh (shared-pattern queries and the "
                "batched MXU backend are); use backend=\"mxu\" or run "
                "the patterns as separate queries")
        kern = (_swar.match_swar_masks if plan.predicate == "accept"
                else _swar.match_swar)

        def swar_launch(w, p, m):
            pr = jnp.broadcast_to(p[0][None, :], (w.shape[0], p.shape[1]))
            return kern(w, pr, m, n_locs=plan.n_locs,
                        pattern_chars=plan.pattern_chars,
                        interpret=self.interpret)
        key = ("swar_mp", plan.predicate, plan.n_locs, plan.pattern_chars)
        return self._shard_wrap(swar_launch, key, rep_args=2)(
            words, np.asarray(pat_rows), np.asarray(mask))

    def _mxu_chunk(self, ref_flat: jnp.ndarray, pat_mat: jnp.ndarray,
                   plan: Plan) -> jnp.ndarray:
        mp = self.merger.multiprocess

        def mxu_launch(r, p):
            out = _mxu.match_mxu(r, p, l_pad=plan.l_pad,
                                 interpret=self.interpret)
            if mp:
                # Fold the round/slice into the staged launch: no eager
                # op may touch the sharded output multi-controller.  The
                # arithmetic is identical to the host-side epilogue.
                out = jnp.round(out[:, :plan.n_locs, :plan.n_patterns]
                                ).astype(jnp.int32)
                if plan.mode != "batched":
                    out = out[:, :, 0]
            return out
        key = ("mxu", plan.l_pad, plan.n_locs, plan.n_patterns, plan.mode)
        return self._shard_wrap(mxu_launch, key)(ref_flat, pat_mat)

    def _slice_resident(self, base: jnp.ndarray, c0: int,
                        c1: int) -> jnp.ndarray:
        """Rows [c0, c1) of a resident form, in its own layout.

        Unsharded: a plain slice.  Sharded: logical rows [c0, c1) are
        slots [c0/S, c1/S) *on every shard* under the cyclic layout, so
        the chunk is a per-shard block slice -- reshape (S, J, w), slice
        the slot axis, reshape back -- which XLA lowers without any
        cross-device movement (the chunk stays sharded like the form).
        The result is in physical (shard-major) order; ``run()``
        un-permutes after the kernel.
        """
        S = self._row_shards
        if S == 1:
            return base[c0:c1]
        j = base.shape[0] // S
        if self.merger.multiprocess:
            # Jitted (cached by geometry): the eager reshape would touch
            # non-addressable shards.
            return _merge._resident_slicer(S, j, c0 // S, c1 // S,
                                           base.shape[1])(base)
        return base.reshape(S, j, base.shape[1])[:, c0 // S:c1 // S].reshape(
            c1 - c0, base.shape[1])

    def _chunk_scores(self, plan: Plan, pats2d: np.ndarray, c0: int,
                      c1: int, packed, idx: Optional[jnp.ndarray],
                      idx_log: Optional[np.ndarray] = None) -> jnp.ndarray:
        """Scores for query rows [c0, c1): (rows, L) or (rows, L, Q).

        ``pats2d`` is the 2-D pattern operand for the ref backend -- codes
        for exact plans, accept masks for accept plans.  ``idx`` (padded
        *physical* gather indices) is set for row-subset queries: the
        chunk is gathered from the resident device forms instead of
        sliced -- still no host repacking; ``idx_log`` carries the same
        rows as logical ids for the host-side ref backend.  Resident
        sharded chunks come back in physical order (see
        ``_slice_resident``).
        """
        if plan.backend == "ref":
            if idx is not None:
                sel = idx_log[c0:min(c1, plan.n_rows)]
                frags = jnp.asarray(self.corpus.fragments[sel])
            else:
                frags = jnp.asarray(self.corpus.fragments[c0:min(c1,
                                    self.corpus.n_rows)])
            fn = (_kref.match_scores_masks_ref if plan.predicate == "accept"
                  else _kref.match_scores_ref)
            if plan.mode == "batched":
                outs = [fn(frags, pats2d[q]) for q in range(plan.n_patterns)]
                return jnp.stack(outs, -1)
            pats = pats2d[c0:c1] if plan.mode == "per_row" else pats2d
            return fn(frags, pats)

        if plan.backend == "swar":
            base = self.corpus.swar_words(plan.need_words)
            if idx is not None:
                # Cross-shard gather: device-side (replicated output)
                # multi-controller, plain fancy-index otherwise.
                words = (self.merger.gather_rows(base, idx[c0:c1])
                         if self.merger.multiprocess else base[idx[c0:c1]])
            else:
                words = self._slice_resident(base, c0, c1)
            pat_rows, mask = packed
            if self.merger.multiprocess:
                return self._swar_chunk_mp(words, pat_rows, mask, plan)
            pat_rows = jnp.asarray(pat_rows)   # (Q, Wp) words or (Q, 4*Wp)
            mask = jnp.asarray(mask)
            if plan.mode == "per_row":
                r_pad = words.shape[0]
                rows = pat_rows[c0:min(c1, pat_rows.shape[0])]
                if rows.shape[0] < r_pad:
                    rows = jnp.concatenate(
                        [rows, jnp.zeros((r_pad - rows.shape[0],
                                          rows.shape[1]), jnp.uint32)], 0)
                if idx is None and self._row_shards > 1:
                    # Resident chunk rows are physical: permute the per-row
                    # patterns the same way so row i still meets pattern i.
                    rows = _sharding.cyclic_permute(rows, self._row_shards)
                return self._swar_chunk(words, rows, mask, plan)
            if plan.mode == "batched":
                # Fused batched launch: tile the chunk Q times and ride
                # each pattern as a per-row pattern -- one kernel dispatch
                # for all Q queries (the lock-step multi-pattern search of
                # the paper's Sec. 3.4) instead of a Q-pass Python loop.
                Q = plan.n_patterns
                Rc = words.shape[0]
                words_t = jnp.tile(words, (Q, 1))
                pw_t = jnp.repeat(pat_rows, Rc, axis=0)
                out = self._swar_chunk(words_t, pw_t, mask, plan)
                return out.reshape(Q, Rc, plan.n_locs).transpose(1, 2, 0)
            pw = jnp.broadcast_to(pat_rows[0][None, :],
                                  (words.shape[0], pat_rows.shape[1]))
            return self._swar_chunk(words, pw, mask, plan)

        # mxu
        base = self.corpus.onehot_flat(plan.f_chars)
        if idx is not None:
            ref_flat = (self.merger.gather_rows(base, idx[c0:c1])
                        if self.merger.multiprocess else base[idx[c0:c1]])
        else:
            ref_flat = self._slice_resident(base, c0, c1)
        out = self._mxu_chunk(ref_flat, packed, plan)
        if self.merger.multiprocess:
            return out                    # epilogue folded into the launch
        scores = jnp.round(out[:, :plan.n_locs, :plan.n_patterns]
                           ).astype(jnp.int32)
        return scores[:, :, 0] if plan.mode != "batched" else scores

    # -- empty subsets --------------------------------------------------------
    def _empty_plan(self, query: MatchQuery,
                    mode: Optional[str] = None) -> Plan:
        """Zero-row plan for a query with no rows to scan (geometry checked).

        The planner (rightly) refuses zero-row workloads and the streaming
        loop would otherwise ``np.concatenate`` empty chunk lists; an empty
        row subset -- or a reserved-but-still-empty growable corpus -- is a
        legal query whose answer is simply no rows.  ``mode`` carries the
        pinned compile-time resolution when the caller has one.
        """
        P = query.pattern_chars
        F = self.corpus.fragment_chars
        if P < 1:
            raise ValueError("pattern must have at least one character")
        L = F - P + 1
        if L <= 0:
            raise ValueError("pattern longer than fragment")
        if len(query.shape) == 1:
            mode, Q = "shared", 1
        else:
            if mode is None:
                mode = query.mode if query.mode is not None else "batched"
            Q = query.n_patterns
        return Plan(backend="ref", mode=mode, n_rows=0, fragment_chars=F,
                    pattern_chars=P, n_patterns=Q if mode == "batched"
                    else 1, n_locs=L, chunk_rows=0,
                    reason="empty row subset", predicate=query.predicate)

    def _empty_result(self, query: MatchQuery, plan: Plan) -> MatchResult:
        """Well-formed all-empty MatchResult for a zero-row subset query."""
        batched = plan.mode == "batched"
        Q = plan.n_patterns
        shape0 = (0, Q) if batched else (0,)
        res = MatchResult(plan=plan,
                          best_locs=np.zeros(shape0, np.int32),
                          best_scores=np.zeros(shape0, np.int32),
                          n_shards=self._row_shards,
                          merge_path=self.merger.merge_path)
        if query.reduction == "full":
            res.scores = np.zeros((0, plan.n_locs, Q) if batched
                                  else (0, plan.n_locs), np.int32)
        elif query.reduction == "topk":
            res.topk_rows = np.zeros(shape0, np.int32)
            res.topk_scores = np.zeros(shape0, np.int32)
        elif query.reduction == "threshold":
            res.hits = np.zeros((0, 4 if batched else 3), np.int64)
        return res

    # -- execution ------------------------------------------------------------
    def match(self, patterns, *, backend=_UNSET, mode=_UNSET, rows=_UNSET,
              reduction=_UNSET, k=_UNSET, threshold=_UNSET,
              chunk_rows=_UNSET, filter=_UNSET) -> MatchResult:
        """Run one query; see module docstring for reductions.

        ``patterns`` is either a ``MatchQuery`` (the declarative API; any
        explicit kwarg alongside it is rejected) or a uint8 code array --
        (P,) shared, (R, P) per-row, (Q, P) batched -- with the legacy
        kwargs (defaults: reduction="best", k=10), which this shim folds
        into a ``MatchQuery`` and compiles (content-cached, so repeated
        calls hit the warm path).  ``rows`` restricts the query to a
        subset of corpus rows (device gather from the resident forms;
        results are in subset order; an empty subset yields an all-empty
        result).  ``threshold`` is in characters (absolute score).  In
        batched mode ``k`` and ``threshold`` may be per-query sequences of
        length Q (the top-k merge runs at max(k); slice
        ``topk_rows[:k_q, q]`` per query).
        """
        query = as_query(patterns, backend=backend, mode=mode, rows=rows,
                         reduction=reduction, k=k, threshold=threshold,
                         chunk_rows=chunk_rows, filter=filter)
        return self.compile(query).run()

    def scores(self, patterns, *, backend=_UNSET, mode=_UNSET, rows=_UNSET,
               chunk_rows=_UNSET) -> np.ndarray:
        """Full materialized score tensor (compat path for small problems)."""
        query = as_query(patterns, backend=backend, mode=mode, rows=rows,
                         chunk_rows=chunk_rows)
        query = dataclasses.replace(query, reduction="full", k=(),
                                    threshold=None)
        return self.match(query).scores
