"""Query planner: workload shape -> kernel + geometry (DESIGN.md Sec. 3b).

Replaces the caller-supplied backend string of the old ``ops.match_scores``
with a selection driven by roofline arithmetic: estimate each kernel's
compute and memory terms, take ``max`` per kernel, pick the minimum.
Structural constraints are applied first (the MXU formulation has no
per-row-pattern path; a batched query on the SWAR kernel re-reads the
corpus per pattern, where the MXU amortizes the reference read across
patterns), and an explicit ``backend=`` override always wins.

Pricing is layered (DESIGN.md Sec. 3i).  The *analytic* layer
(``analytic_*_seconds`` module functions) turns a shape into roofline
seconds against ``TPURoofline`` constants -- pure arithmetic, no
overheads.  The active ``CostSource`` turns analytic seconds into wall
seconds: the static datasheet model (``TPU_V5E`` constants plus a fixed
dispatch overhead -- the uncalibrated fallback) or measured per-kernel
curves fitted by ``repro.match.calibrate``.  A ``FeedbackStore`` of
observed/estimated runtime ratios then re-prices any (kernel,
shape-bucket) whose estimates have drifted past a bound.  Every ``Plan``
records which source priced it (``Plan.cost_source``, also tagged into
``Plan.reason``).

The ``Plan`` carries every derived geometry number (word counts, tile
paddings, chunking) so the executor never re-derives layout -- one source
of truth per query.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.core.tech import (DISPATCH_OVERHEAD_S, REF_CALL_OVERHEAD_S,
                             TPU_V5E, CostSource, StaticCostSource,
                             TPURoofline)
from repro.kernels import filter_qgram as _fq
from repro.kernels import match_mxu as _mxu
from repro.kernels import match_swar as _swar
from repro.match.feedback import FeedbackStore, kernel_key

BACKENDS = ("swar", "mxu", "ref")

# Below this many (row, loc, patchar, query) ops the Pallas launch
# dominates and the plain jnp reference is fastest.  This structural
# escape hatch encodes the *static* model's launch-overhead belief; a
# calibrated source has measured per-kernel intercepts, so under it the
# tiny-shape decision is a genuine three-way price comparison instead.
TINY_OPS = 4096
# SWAR integer ops per (row, loc, word): shift/or/xor/and + popcount tree.
SWAR_OPS_PER_WORD = 12
# Accept-set SWAR variant: four lane-equality tests + plane ANDs replace
# the single XOR (see match_swar_masks) -- ~2.5x the integer work.
SWAR_OPS_PER_WORD_MASKS = 30
# The SWAR kernel runs on the VPU, whose integer throughput is a small
# fraction of MXU bf16 peak (8x128 lanes vs. the systolic array); this
# divisor calibrates swar compute against ``peak_bf16_flops``.
VPU_SLOWDOWN = 64
# Host jnp reference throughput + per-call overhead: only has to rank the
# ref backend sanely against the kernels when pricing batches.
REF_OPS_PER_S = 1e9
# Q-gram filter stage (filter_qgram kernel): and/not + full SWAR popcount
# + compare per signature word and pattern.
FILTER_OPS_PER_WORD = 18


def kernel_name(backend: str, predicate: str = "exact") -> str:
    """Cost-model kernel identifier for a (backend, predicate) pair.

    The accept-set SWAR variant is a different kernel with a different
    cost curve (bit-plane operands, ~2.5x the integer ops), so it
    calibrates and feeds back separately from exact-match SWAR.
    """
    if backend == "swar" and predicate == "accept":
        return "swar_masks"
    return backend


# -- analytic layer: shape -> roofline seconds, no overheads ------------------

def analytic_swar_seconds(roofline: TPURoofline, R: int, L: int, P: int,
                          Q: int = 1, predicate: str = "exact") -> float:
    """Roofline seconds for one fused SWAR dispatch over Q pattern sets."""
    wp, need = _swar_geometry(P, L)
    if predicate == "accept":
        ops_per_word, pat_words = SWAR_OPS_PER_WORD_MASKS, 4 * wp
    else:
        ops_per_word, pat_words = SWAR_OPS_PER_WORD, wp
    ops = Q * R * L * wp * ops_per_word
    bytes_hbm = Q * (R * need * 4 + R * pat_words * 4 + R * L * 4)
    t_compute = ops / (roofline.peak_bf16_flops / VPU_SLOWDOWN)
    t_mem = bytes_hbm / roofline.hbm_bw
    return max(t_compute, t_mem)


def analytic_mxu_seconds(roofline: TPURoofline, R: int, L: int, P: int,
                         Q: int = 1) -> float:
    """Roofline seconds for one batched MXU pass over all Q patterns."""
    l_pad, p_chars, q_pad, f_chars = _mxu_geometry(P, L, Q)
    n_chunks = p_chars // _mxu.CHARS_PER_CHUNK
    flops = R * l_pad * (n_chunks * _mxu.K_CHUNK) * 2 * q_pad
    bytes_hbm = (R * f_chars * 4 * 2 + p_chars * 4 * q_pad * 2
                 + R * l_pad * q_pad * 4)
    t_compute = flops / roofline.peak_bf16_flops
    t_mem = bytes_hbm / roofline.hbm_bw
    return max(t_compute, t_mem)


def analytic_ref_seconds(roofline: TPURoofline, R: int, L: int, P: int,
                         Q: int = 1) -> float:
    """Host jnp reference compute for Q passes (overhead priced per call)."""
    del roofline  # host path: independent of the accelerator target
    return Q * R * L * P / REF_OPS_PER_S


def analytic_filter_seconds(roofline: TPURoofline, R: int, sig_words: int,
                            n_queries: int = 1) -> float:
    """Roofline seconds for one filter dispatch testing Q patterns against
    R signatures: the signatures are read once, each of the kernel's
    ``pattern_pad(Q)`` patterns (pads included) adds VPU work, and one
    flag bit per row is written."""
    ops = _fq.pattern_pad(n_queries) * R * sig_words * FILTER_OPS_PER_WORD
    bytes_hbm = R * sig_words * 4 + R / 8
    t_compute = ops / (roofline.peak_bf16_flops / VPU_SLOWDOWN)
    t_mem = bytes_hbm / roofline.hbm_bw
    return max(t_compute, t_mem)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Everything the executor needs to run one query."""

    backend: str                # "swar" | "mxu" | "ref"
    mode: str                   # "shared" | "per_row" | "batched"
    n_rows: int                 # R (unpadded)
    fragment_chars: int         # F
    pattern_chars: int          # P
    n_patterns: int             # Q (1 unless batched)
    n_locs: int                 # L = F - P + 1
    # SWAR geometry.
    wp: int = 0                 # pattern words
    need_words: int = 0         # min corpus word width incl. look-ahead pad
    # MXU geometry.
    l_pad: int = 0              # alignment rows produced (mult of L_TILE)
    p_chars_pad: int = 0        # pattern chars padded to CHARS_PER_CHUNK
    q_pad: int = 0              # patterns padded to 128
    f_chars: int = 0            # one-hot reference chars needed
    # Streaming.
    chunk_rows: int = 0         # rows per executor chunk (mult of row tile)
    est_seconds: float = 0.0    # roofline estimate for the whole query
    reason: str = ""            # human-readable selection rationale
    # Predicate.
    predicate: str = "exact"    # "exact" | "accept" (accept-set masks)
    # Two-stage execution (DESIGN.md Sec. 3g).
    strategy: str = "scan"      # "scan" | "filter" (filter-then-verify)
    filter_words: int = 0       # signature words per row (filter plans)
    est_survivor_frac: float = 1.0  # estimated post-filter row fraction
    # Sharded execution (DESIGN.md Sec. 3h): kernel terms priced at the
    # per-shard row count (shards run concurrently; the critical path is
    # one shard's work plus the small host merge).
    n_shards: int = 1
    # Device-side merge traffic (DESIGN.md Sec. 3k): estimated cross-
    # shard collective bytes for the reduction (ring all_gather of
    # reduced per-row state, per-chunk top-k candidate exchanges, the
    # threshold hot bitmap).  Priced into est_seconds at ici_link_bw but
    # kept out of the backend comparison -- every backend moves the same
    # reduced state.  MatchResult.collective_bytes is the measured
    # counterpart the feedback loop can hold against this.
    est_collective_bytes: float = 0.0
    # Cost provenance (DESIGN.md Sec. 3i): which source priced this plan
    # ("static" | "calibrated:<digest8>"), the feedback-free estimate of
    # the scan/verify stage (what observed runtimes are recorded against
    # -- see feedback.FeedbackStore), and the filter stage's share of
    # est_seconds when strategy == "filter".
    cost_source: str = "static"
    est_base_seconds: float = 0.0
    est_filter_seconds: float = 0.0
    est_filter_base_seconds: float = 0.0


def _swar_geometry(P: int, L: int) -> tuple[int, int]:
    wp = -(-P // 16)
    need = (L - 1) // 16 + wp + 1
    return wp, need


def _mxu_geometry(P: int, L: int, Q: int) -> tuple[int, int, int, int]:
    n_chunks = -(-P // _mxu.CHARS_PER_CHUNK)
    p_chars = n_chunks * _mxu.CHARS_PER_CHUNK
    l_pad = max(-(-L // _mxu.L_TILE) * _mxu.L_TILE, _mxu.L_TILE)
    q_pad = -(-Q // 128) * 128
    return l_pad, p_chars, q_pad, l_pad + p_chars


@dataclasses.dataclass(frozen=True)
class FilterContext:
    """Filter-stage pricing inputs for one eligible threshold query.

    Built by the engine (``MatchEngine._filter_context``) from the query
    content and the corpus index configuration; the planner prices the
    two-stage pipeline (filter + estimated-survivor verify) against the
    full scan and records the verdict in ``Plan.strategy``.
    """

    sig_words: int              # uint32 signature words per row
    n_queries: int              # patterns of the one filter dispatch
    prunable: bool              # every query can exclude rows
    survivor_frac: float        # estimated post-filter row fraction
    force: bool = False         # query hint filter=True: skip the pricing


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """Pricing verdict for Q compatible shared-mode queries (one tick).

    ``coalesced`` means one fused ``mode="batched"`` launch beats Q
    sequential single-query launches; ``plan`` is the plan to execute
    (batched geometry when coalesced, single-query geometry otherwise).
    """

    coalesced: bool
    plan: Plan
    n_queries: int
    est_coalesced_s: float
    est_sequential_s: float
    reason: str


@dataclasses.dataclass(frozen=True)
class BankPlan:
    """Pricing verdict for one ingest batch against a standing bank.

    The inverted regime (DESIGN.md Sec. 3j): the pattern bank is the
    resident axis, the arriving document batch the transient one.
    ``strategy == "scan"`` verifies every live pattern against the batch
    in one fused accept-set SWAR launch; ``"filter"`` first runs one
    ``bank_prefilter`` dispatch (pattern signatures vs. per-doc
    occurrence signatures) and verifies only the estimated survivors.
    Either way the batch costs exactly one verify launch -- the filter
    only shrinks its pattern axis.
    """

    strategy: str               # "scan" | "filter"
    n_docs: int                 # arriving batch size D
    n_patterns: int             # live bank slots Qp
    est_seconds: float          # chosen-path estimate
    est_scan_seconds: float     # full bank scan estimate
    est_filter_seconds: float   # prefilter stage share (0 for scan)
    est_survivor_frac: float    # estimated surviving-pattern fraction
    est_verify_patterns: int    # pattern axis priced into the verify
    reason: str
    cost_source: str = "static"


class Planner:
    """Kernel selection: analytic roofline x cost source x runtime feedback.

    ``cost_source`` prices analytic seconds into wall seconds (static
    datasheet fallback, or measured calibration from
    ``repro.match.calibrate.load_cost_source``).  ``feedback`` multiplies
    in the published observed/estimated factor for the (kernel,
    shape-bucket), so mispredicted buckets heal online; pass
    ``feedback=None`` semantics via a fresh store -- every planner owns
    one unless the caller shares theirs (the engine shares its store so
    compiled plans and ad-hoc queries see the same corrections).
    """

    def __init__(self, roofline: TPURoofline = TPU_V5E,
                 memory_budget_bytes: float = 256 * 2**20,
                 cost_source: Optional[CostSource] = None,
                 feedback: Optional[FeedbackStore] = None):
        self.roofline = roofline
        self.memory_budget_bytes = memory_budget_bytes
        self.cost_source = cost_source or StaticCostSource()
        self.feedback = feedback if feedback is not None else FeedbackStore()

    # -- cost terms -----------------------------------------------------------
    def _price(self, kernel: str, analytic_s: float, n_dispatch: int,
               R: int, x: int, Q: int, base: bool) -> float:
        """Analytic seconds -> wall seconds via source, then feedback.

        ``base=True`` skips the feedback factor: that is the estimate
        observed runtimes are recorded against, so the EWMA converges to
        truth/model rather than chasing its own corrections (the
        geometric-mean trap -- see ``feedback`` module docstring).
        """
        priced = self.cost_source.price(kernel, analytic_s, n_dispatch)
        if base:
            return priced
        return priced * self.feedback.factor(kernel_key(kernel, R, x, Q))

    def swar_seconds(self, R: int, L: int, P: int, Q: int = 1,
                     predicate: str = "exact", *, base: bool = False) -> float:
        """One fused SWAR dispatch over Q pattern sets.

        The executor tiles the corpus chunk Q times and rides each pattern
        as a per-row pattern, so a batched query is a single launch whose
        compute and memory (the corpus is re-read per pattern) scale with
        Q -- where the MXU formulation amortizes the reference read across
        patterns instead.  Accept-set predicates pay ~2.5x the integer ops
        (four lane-equality tests per word) and read 4 plane words per
        pattern word -- the MXU, where wildcards are free, wins sooner.
        """
        analytic = analytic_swar_seconds(self.roofline, R, L, P, Q, predicate)
        return self._price(kernel_name("swar", predicate), analytic, 1,
                           R, P, Q, base)

    def ref_seconds(self, R: int, L: int, P: int, Q: int = 1,
                    *, base: bool = False) -> float:
        """Q jnp reference passes on the host (batched ref still loops Q)."""
        analytic = analytic_ref_seconds(self.roofline, R, L, P, Q)
        return self._price("ref", analytic, Q, R, P, Q, base)

    def filter_seconds(self, R: int, sig_words: int, n_queries: int = 1,
                       *, base: bool = False) -> float:
        """One filter-kernel dispatch of Q patterns over R row signatures.

        The dispatch reads ``sig_words`` uint32 per row once, does a
        handful of integer ops per word and pattern on the VPU, and
        writes one flag per row -- orders of magnitude less data touched
        than the exact scan, which is the whole point of the stage.
        """
        analytic = analytic_filter_seconds(self.roofline, R, sig_words,
                                           n_queries)
        return self._price("filter", analytic, 1,
                           R, sig_words, n_queries, base)

    def mxu_seconds(self, R: int, L: int, P: int, Q: int = 1,
                    *, base: bool = False) -> float:
        """One batched MXU pass over all Q patterns.

        Identical for exact and accept-set predicates: a wildcard is just a
        multi-hot column in the pattern matrix, same contraction shape --
        the "wildcards are nearly free on the MXU" property the planner
        exploits.
        """
        analytic = analytic_mxu_seconds(self.roofline, R, L, P, Q)
        return self._price("mxu", analytic, 1, R, P, Q, base)

    def backend_seconds(self, backend: str, R: int, L: int, P: int,
                        Q: int = 1, predicate: str = "exact",
                        *, base: bool = False) -> float:
        """Price any scan backend by name (the verify-stage dispatcher)."""
        if backend == "swar":
            return self.swar_seconds(R, L, P, Q, predicate, base=base)
        if backend == "mxu":
            return self.mxu_seconds(R, L, P, Q, base=base)
        return self.ref_seconds(R, L, P, Q, base=base)

    # -- chunking -------------------------------------------------------------
    def _chunk_rows(self, R_pad: int, plan_bytes_per_row: int,
                    row_tile: int, override: Optional[int],
                    n_shards: int = 1) -> int:
        """Rows per streaming chunk (a multiple of the row tile).

        The memory budget is per device; a sharded chunk spreads its rows
        over ``n_shards`` devices, so the global chunk can be S times
        larger for the same per-device footprint.
        """
        if override is not None:
            chunk = -(-override // row_tile) * row_tile
        else:
            rows = int(self.memory_budget_bytes * n_shards
                       // max(plan_bytes_per_row, 1))
            chunk = max(row_tile, (rows // row_tile) * row_tile)
            # Large chunks stay lane-tile aligned per shard, so the SWAR
            # kernel never pads a chunk (a copy) to its 128-row grid.
            lane = _swar.LANE_TILE * n_shards
            if chunk >= lane:
                chunk = chunk // lane * lane
        return min(chunk, R_pad)

    # -- the planner ----------------------------------------------------------
    def plan(self, *, n_rows: int, fragment_chars: int, pattern_chars: int,
             n_patterns: Optional[int] = None, per_row: bool = False,
             backend: Optional[str] = None,
             chunk_rows: Optional[int] = None,
             predicate: str = "exact",
             filter_ctx: Optional[FilterContext] = None,
             n_shards: int = 1, reduction: Optional[str] = None,
             topk_k: int = 0) -> Plan:
        R, F, P = n_rows, fragment_chars, pattern_chars
        if R < 1:
            raise ValueError("corpus has no rows")
        if P < 1:
            raise ValueError("pattern must have at least one character")
        L = F - P + 1
        if L <= 0:
            raise ValueError("pattern longer than fragment")
        if per_row and n_patterns is not None:
            raise ValueError("per_row and batched are mutually exclusive")
        if predicate not in ("exact", "accept"):
            raise ValueError(f"unknown predicate {predicate!r}")
        Q = 1 if n_patterns is None else int(n_patterns)
        mode = "per_row" if per_row else ("batched" if n_patterns is not None
                                          else "shared")
        if backend is not None and backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "mxu" and per_row:
            raise ValueError("mxu kernel has no per-row-pattern formulation")

        # Shard-aware pricing (DESIGN.md Sec. 3h): the kernels run per
        # shard on R/S rows concurrently, so their roofline terms use the
        # per-shard row count -- the critical path, not the total work.
        # The ref backend scans the host buffer single-threaded and the
        # tiny-workload escape hatch keys on total ops, so both keep R.
        S = max(1, int(n_shards))
        R_shard = -(-R // S)
        t_swar = self.swar_seconds(R_shard, L, P, Q, predicate)
        t_mxu = self.mxu_seconds(R_shard, L, P, Q)

        if backend is not None:
            chosen, reason = backend, "explicit override"
        elif per_row:
            chosen, reason = "swar", "per-row patterns: SWAR only"
        elif (self.cost_source.name == "static"
              and R * L * P * Q <= TINY_OPS):
            # Q multiplies the work: a large batched query on a small corpus
            # is not tiny, and routing it to the Python-loop ref backend
            # would cost Q sequential passes.  This structural rule encodes
            # the static model's launch-overhead belief; a calibrated
            # source has measured per-kernel intercepts, so tiny shapes
            # fall through to the three-way price comparison below.
            chosen, reason = "ref", "tiny workload: launch overhead dominates"
        elif self.cost_source.name != "static":
            # Calibrated: genuine three-way comparison.  The measured
            # intercepts decide the tiny-shape regime (on a host-heavy
            # substrate the jnp reference's per-call overhead can exceed
            # an interpret-mode Pallas launch by orders of magnitude --
            # exactly the kind of fact only calibration can know).
            t_ref = self.ref_seconds(R, L, P, Q)
            chosen, t_best = "swar", t_swar
            if t_mxu < t_best:
                chosen, t_best = "mxu", t_mxu
            if t_ref < t_best:
                chosen, t_best = "ref", t_ref
            reason = (f"measured: {chosen} {t_best:.3g}s (swar {t_swar:.3g}s,"
                      f" mxu {t_mxu:.3g}s, ref {t_ref:.3g}s, Q={Q})")
        elif t_mxu < t_swar:
            chosen = "mxu"
            reason = f"roofline: mxu {t_mxu:.3g}s < swar {t_swar:.3g}s (Q={Q})"
        else:
            chosen = "swar"
            reason = f"roofline: swar {t_swar:.3g}s <= mxu {t_mxu:.3g}s (Q={Q})"

        wp, need = _swar_geometry(P, L)
        l_pad, p_chars, q_pad, f_chars = _mxu_geometry(P, L, Q)
        row_pad = _swar.ROW_TILE * S
        R_pad = -(-R // row_pad) * row_pad

        if chosen == "swar":
            # Batched swar tiles each chunk Q times (one fused launch), so
            # a chunk's footprint scales with Q; accept-set planes are 4
            # words per pattern word.
            pat_words = 4 * wp if predicate == "accept" else wp
            bytes_per_row = (need * 4 + pat_words * 4 + L * 4) * Q
            row_tile = _swar.ROW_TILE
            est = t_swar
            est_base = self.swar_seconds(R_shard, L, P, Q, predicate,
                                         base=True)
        elif chosen == "mxu":
            bytes_per_row = f_chars * 4 * 2 + l_pad * q_pad * 4
            row_tile = 1
            est = t_mxu
            est_base = self.mxu_seconds(R_shard, L, P, Q, base=True)
        else:
            bytes_per_row = F + L * 4 * Q
            row_tile = 1
            est = self.ref_seconds(R, L, P, Q)
            est_base = self.ref_seconds(R, L, P, Q, base=True)
        chunk = self._chunk_rows(R_pad, bytes_per_row,
                                 row_tile if chosen == "ref" else
                                 row_tile * S, chunk_rows, n_shards=S)

        # Two-stage pricing (DESIGN.md Sec. 3g): for an eligible threshold
        # query, compare filter + estimated-survivor verify against the
        # full scan just chosen.  The verify stage keeps the scan's kernel
        # (the packed pattern operands are shared between strategies); the
        # survivor estimate carries the index's measured-selectivity
        # calibration.  A query-level filter=True hint skips the pricing
        # (but never the prunability requirement).
        strategy, filter_words, surv = "scan", 0, 1.0
        est_fil = est_fil_base = 0.0
        if filter_ctx is not None and filter_ctx.prunable:
            frac = filter_ctx.survivor_frac
            # Per-shard pricing: the filter kernel scans R/S signatures
            # per shard, and survivors spread ~uniformly over shards
            # (cyclic placement), so the verify stage is r_surv/S per
            # shard too.
            r_surv = max(1, math.ceil(frac * R / S))
            t_fil = self.filter_seconds(R_shard, filter_ctx.sig_words,
                                        filter_ctx.n_queries)
            t_ver = self.backend_seconds(chosen, r_surv, L, P, Q, predicate)
            if filter_ctx.force or t_fil + t_ver < est:
                strategy = "filter"
                filter_words = filter_ctx.sig_words
                surv = frac
                reason += (f"; filter+verify {t_fil + t_ver:.3g}s "
                           f"{'forced' if filter_ctx.force else '<'} scan "
                           f"{est:.3g}s (est survivors {frac:.3g})")
                est = t_fil + t_ver
                est_fil = t_fil
                est_fil_base = self.filter_seconds(
                    R_shard, filter_ctx.sig_words, filter_ctx.n_queries,
                    base=True)
                est_base = self.backend_seconds(chosen, r_surv, L, P, Q,
                                                predicate, base=True)

        # Collective-merge pricing (DESIGN.md Sec. 3k): cross-shard
        # reductions exchange reduced state on device.  Ring all_gather
        # moves (S-1)/S of the replicated payload per link; the per-row
        # best loc+score pulls (8 bytes/row/query) underlie every scan
        # reduction, top-k adds per-chunk candidate exchanges
        # ((score, row) pairs from S-1 peers), threshold adds the hot
        # bitmap, and "full" replicates the whole score block.  Added to
        # est_seconds *after* the backend choice: every backend moves the
        # same reduced state, so it must not tilt the comparison.
        est_coll = 0.0
        if S > 1 and reduction is not None:
            ring = (S - 1) / S
            if reduction == "full":
                est_coll = R_pad * L * 4.0 * Q * ring
            else:
                est_coll = R_pad * 8.0 * Q * ring
                if reduction == "topk":
                    n_ch = max(1, -(-R_pad // max(chunk, 1)))
                    k_loc = min(max(int(topk_k), 1),
                                max(chunk // S, 1))
                    est_coll += n_ch * (S - 1) * k_loc * Q * 12.0
                elif reduction == "threshold":
                    est_coll += R_pad * 1.0 * ring
            est += est_coll / self.roofline.ici_link_bw

        if S > 1:
            reason += f"; priced per shard (S={S})"
        reason += f" [cost={self.cost_source.tag}]"
        return Plan(backend=chosen, mode=mode, n_rows=R, fragment_chars=F,
                    pattern_chars=P, n_patterns=Q, n_locs=L, wp=wp,
                    need_words=need, l_pad=l_pad, p_chars_pad=p_chars,
                    q_pad=q_pad, f_chars=f_chars, chunk_rows=chunk,
                    est_seconds=est, reason=reason, predicate=predicate,
                    strategy=strategy, filter_words=filter_words,
                    est_survivor_frac=surv, n_shards=S,
                    est_collective_bytes=est_coll,
                    cost_source=self.cost_source.tag,
                    est_base_seconds=est_base,
                    est_filter_seconds=est_fil,
                    est_filter_base_seconds=est_fil_base)

    # -- standing-bank pricing (DESIGN.md Sec. 3j) ----------------------------
    def plan_bank(self, *, n_docs: int, fragment_chars: int,
                  pattern_chars: int, n_patterns: int, sig_words: int,
                  survivor_frac: float, prunable: bool = True,
                  force: Optional[bool] = None) -> BankPlan:
        """Price one ingest batch against the bank: prefilter or full scan.

        The roles are swapped relative to ``plan``: the batch's ``n_docs``
        rides the row axis, the bank's live slots ride the pattern axis,
        and the backend is always the accept-set SWAR kernel (the bank's
        resident operands are bit planes; re-deriving MXU operands per
        batch would repack the resident side, which the residency
        protocol forbids).  The prefilter is a *single* dispatch whose
        work is patterns x docs x signature words, so it is priced
        through the filter kernel's calibrated curve with the doc count
        as the inner extent.  ``force=True`` pins the filtered strategy
        whenever the bank is prunable (never overrides prunability);
        ``force=False`` pins the full scan.
        """
        D, F, P, Qp = int(n_docs), int(fragment_chars), int(pattern_chars), \
            int(n_patterns)
        if D < 1:
            raise ValueError("batch has no documents")
        if Qp < 1:
            raise ValueError("bank has no live patterns")
        L = F - P + 1
        if L <= 0:
            raise ValueError("pattern longer than fragment")
        t_scan = self.swar_seconds(D, L, P, Qp, "accept")
        strategy, est, t_fil, q_surv = "scan", t_scan, 0.0, Qp
        frac = min(1.0, max(float(survivor_frac), 0.0))
        if prunable and force is not False:
            q_surv_est = max(1, math.ceil(frac * Qp))
            analytic = analytic_filter_seconds(self.roofline, Qp,
                                               sig_words, D)
            t_fil = self._price("filter", analytic, 1, Qp, sig_words, D,
                                False)
            t_ver = self.swar_seconds(D, L, P, q_surv_est, "accept")
            if force or t_fil + t_ver < t_scan:
                strategy = "filter"
                est = t_fil + t_ver
                q_surv = q_surv_est
                reason = (f"bank prefilter+verify {est:.3g}s "
                          f"{'forced' if force else '<'} scan "
                          f"{t_scan:.3g}s (est survivors {frac:.3g} of "
                          f"{Qp})")
            else:
                reason = (f"bank scan {t_scan:.3g}s <= prefilter+verify "
                          f"{t_fil + t_ver:.3g}s")
                t_fil = 0.0
        elif force is False:
            reason = f"bank scan forced ({Qp} patterns x {D} docs)"
        else:
            reason = f"bank scan: no prunable patterns ({Qp} x {D} docs)"
        reason += f" [cost={self.cost_source.tag}]"
        return BankPlan(strategy=strategy, n_docs=D, n_patterns=Qp,
                        est_seconds=est, est_scan_seconds=t_scan,
                        est_filter_seconds=t_fil,
                        est_survivor_frac=frac if strategy == "filter"
                        else 1.0,
                        est_verify_patterns=q_surv, reason=reason,
                        cost_source=self.cost_source.tag)

    # -- batch pricing --------------------------------------------------------
    def plan_batch(self, *, n_rows: int, fragment_chars: int,
                   pattern_chars: int, n_queries: int,
                   backend: Optional[str] = None,
                   chunk_rows: Optional[int] = None,
                   predicate: str = "exact",
                   n_shards: int = 1) -> BatchPlan:
        """Price Q compatible shared-mode queries: coalesced vs. sequential.

        Sequential is Q independent single-pattern launches (each paying
        its own dispatch); coalesced is one ``mode="batched"`` plan over
        all Q patterns (a single fused launch on every backend).  Ties go
        to coalesced: beyond the kernel cost, one launch amortizes
        planning, host packing and result assembly, which the roofline
        does not model.
        """
        if n_queries < 1:
            raise ValueError("n_queries must be >= 1")
        single = self.plan(n_rows=n_rows, fragment_chars=fragment_chars,
                           pattern_chars=pattern_chars, backend=backend,
                           chunk_rows=chunk_rows, predicate=predicate,
                           n_shards=n_shards)
        if n_queries == 1:
            return BatchPlan(coalesced=False, plan=single, n_queries=1,
                             est_coalesced_s=single.est_seconds,
                             est_sequential_s=single.est_seconds,
                             reason="single query: nothing to coalesce "
                                    f"[cost={self.cost_source.tag}]")
        batched = self.plan(n_rows=n_rows, fragment_chars=fragment_chars,
                            pattern_chars=pattern_chars,
                            n_patterns=n_queries, backend=backend,
                            chunk_rows=chunk_rows, predicate=predicate,
                            n_shards=n_shards)
        est_seq = n_queries * single.est_seconds
        est_co = batched.est_seconds
        coalesced = est_co <= est_seq
        if coalesced:
            reason = (f"coalesce {n_queries} queries: {batched.backend} "
                      f"{est_co:.3g}s <= {n_queries}x {single.backend} "
                      f"{est_seq:.3g}s")
        else:
            reason = (f"sequential: {n_queries}x {single.backend} "
                      f"{est_seq:.3g}s < {batched.backend} {est_co:.3g}s")
        reason += f" [cost={self.cost_source.tag}]"
        return BatchPlan(coalesced=coalesced,
                         plan=batched if coalesced else single,
                         n_queries=n_queries, est_coalesced_s=est_co,
                         est_sequential_s=est_seq, reason=reason)
