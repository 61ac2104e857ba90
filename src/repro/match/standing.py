"""Standing queries: device-resident pattern bank (DESIGN.md Sec. 3j).

Everything before this module treats patterns as transient and the corpus
as resident.  The temporal-correlation PCM work (Sebastian et al.,
PAPERS.md) runs the *inverted* regime -- a fixed set of resident detectors
scored against every arriving sample -- and the in-storage sparse pattern
processor (Jun et al.) shows a filter cascade is what makes that regime
affordable.  ``PatternBank`` is that inversion for the TPU engine:

* **Registration freezes.**  ``register`` normalizes any pattern spelling
  (IUPAC string, code array, 1-D ``MatchQuery``) through ``as_masks``,
  validates it against the bank geometry, and freezes it as a threshold
  ``MatchQuery`` -- the same IR an ad-hoc caller would compile, which is
  what the bit-identity tests compare against.  Each pattern carries an
  id, a threshold, an optional TTL and an optional hit callback.
* **Residency protocol.**  The bank owns the same device-residency
  discipline as ``PackedCorpus``: host buffers are the source of truth,
  device forms (accept-mask bit planes for the verify kernel; required-bit
  q-gram signatures + per-pattern slacks for the prefilter) pack lazily
  **once** (``plane_pack_count`` / ``sig_pack_count`` stay <= 1),
  ``register``/``unregister`` splice only the touched slots
  (``.at[].set``), and growth is capacity-reserved zero-extension.  Live
  patterns always occupy slots ``[0, n_live)``: ``unregister`` swap-moves
  the last live slot into the hole (<= 2 slot splices), so the verify
  operand is a plain slice, never a per-scan gather.
* **One fused launch per batch.**  ``scan`` scores an arriving document
  batch against every live pattern in a single ``match_swar_masks``
  dispatch with the roles swapped: the docs ride the row axis (the
  "corpus chunk"), the bank rides the pattern axis -- the engine's
  ``mode="batched"`` formulation exactly, so hits are bit-identical to
  compiling each pattern as an ad-hoc threshold query over the batch.
* **Pattern-side prefilter.**  The q-gram lemma read backwards: a doc
  admitting a qualifying alignment of pattern p contains all of that
  window's q-grams, so ``popcount(psig & ~docsig) > slack_p`` proves p
  cannot fire on it -- zero false negatives, same argument as
  ``CorpusIndex`` with rows and queries exchanged.  One
  ``bank_prefilter`` dispatch prunes the pattern axis for the whole
  batch; ``Planner.plan_bank`` prices prefilter-then-verify against the
  full bank scan through the calibrated cost source, with a bank-local
  measured-selectivity EWMA feeding the survivor estimate.

``MatchService`` drives the bank from ``ingest``: every batch is scanned
*before* it splices into the corpus, so a standing alert fires even when
the corpus runs as a sliding window that would evict the doc later.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import encoding
from repro.kernels import default_interpret
from repro.kernels import filter_qgram as _fq
from repro.kernels import match_swar as _swar
from repro.match import index as _idx
from repro.match.engine import _pack_mask_planes, _valid_mask
from repro.match.feedback import EwmaRatio
from repro.match.planner import BankPlan, Planner, _swar_geometry
from repro.match.query import MatchQuery, as_masks
from repro.obs import NULL_OBS

# Hit array columns (HitTicket.hits): batch-local doc index, alignment
# location, pattern id, similarity score.
HIT_DOC, HIT_LOC, HIT_PATTERN, HIT_SCORE = 0, 1, 2, 3


@dataclasses.dataclass(frozen=True)
class StandingPattern:
    """One registered pattern's frozen metadata (the bank's slot record)."""

    pattern_id: int
    query: MatchQuery            # frozen threshold IR (ad-hoc equivalent)
    threshold: float
    deadline: float              # clock seconds; +inf = no TTL
    n_sig_bits: int              # distinct required signature bits
    slack: int                   # q-gram mismatch budget (< 0: unsat.)


@dataclasses.dataclass
class HitTicket:
    """Result of scanning one ingest batch against the bank.

    ``hits`` is (n, 4) int64 ``[doc, loc, pattern_id, score]`` in the
    engine's batched-threshold order (ascending doc, then loc, then the
    pattern's launch column) -- per pattern, identical to the ``hits`` of
    an ad-hoc threshold query over the same docs.  ``base_row`` anchors
    the batch: the service scans pre-splice, so doc ``d`` becomes corpus
    row ``base_row + d`` once appended.
    """

    n_docs: int
    base_row: Optional[int] = None
    hits: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 4), np.int64))
    plan: Optional[BankPlan] = None
    n_patterns: int = 0          # live bank slots at scan time
    n_verified: int = 0          # patterns that reached the verify launch
    survivor_frac: Optional[float] = None  # measured (None: no prefilter)
    n_bank_launches: int = 0     # verify dispatches this scan (0 or 1)
    wall_s: float = 0.0

    @property
    def corpus_rows(self) -> Optional[np.ndarray]:
        """Per-hit corpus row ids (None when the scan was unanchored)."""
        if self.base_row is None:
            return None
        return self.base_row + self.hits[:, HIT_DOC]

    def by_pattern(self) -> Dict[int, np.ndarray]:
        """Hits grouped per pattern id (insertion order = launch order)."""
        out: Dict[int, np.ndarray] = {}
        for pid in np.unique(self.hits[:, HIT_PATTERN]):
            out[int(pid)] = self.hits[self.hits[:, HIT_PATTERN] == pid]
        return out


class PatternBank:
    """Thousands of standing patterns, resident once, scanned per batch.

    ``fragment_chars`` / ``pattern_chars`` fix the launch geometry at
    construction (every registered pattern has the same length, like every
    corpus row has the same width); ``filter`` is the routing hint with
    ``MatchQuery.filter`` semantics (None: price it, True: force the
    prefilter whenever the bank is prunable, False: always full scan).
    ``clock`` injects time for TTL tests.
    """

    def __init__(self, fragment_chars: int, pattern_chars: int, *,
                 q: int = _idx.DEFAULT_Q, n_bits: int = _idx.DEFAULT_BITS,
                 capacity: int = 256, planner: Optional[Planner] = None,
                 filter: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 clock: Callable[[], float] = time.perf_counter):
        F, P = int(fragment_chars), int(pattern_chars)
        if P < 1:
            raise ValueError("pattern_chars must be >= 1")
        if F - P + 1 <= 0:
            raise ValueError(
                f"pattern_chars={P} longer than fragment_chars={F}")
        q = int(q)
        n_bits = int(n_bits)
        if q < 1 or q > 16:
            raise ValueError(f"q must be in [1, 16], got {q}")
        if n_bits < 32 or n_bits & (n_bits - 1):
            raise ValueError(
                f"n_bits must be a power of two >= 32, got {n_bits}")
        if filter is not None and not isinstance(filter, bool):
            raise ValueError("filter must be None, True or False")
        self.fragment_chars = F
        self.pattern_chars = P
        self.n_locs = F - P + 1
        self.q = q
        self.n_bits = n_bits
        self.sig_words = n_bits // 32
        self.filter = filter
        self.capacity = max(1, int(capacity))
        self.planner = planner or Planner()
        self.interpret = (default_interpret() if interpret is None
                          else interpret)
        self.clock = clock
        self.wp, self.need_words = _swar_geometry(P, self.n_locs)
        # Host source-of-truth buffers, dense over slots [0, n_live).
        self._masks = np.zeros((self.capacity, P), np.uint8)
        self._sig_host = np.zeros((self.capacity, self.sig_words), np.uint32)
        self._thr = np.zeros(self.capacity, np.float64)
        self._slack = np.full(self.capacity, -1, np.int64)
        self._nbits = np.zeros(self.capacity, np.int32)
        self._ids = np.full(self.capacity, -1, np.int64)
        self._deadline = np.full(self.capacity, np.inf, np.float64)
        self._slots: Dict[int, int] = {}          # pattern id -> slot
        self._patterns: Dict[int, StandingPattern] = {}
        self._callbacks: Dict[int, Callable] = {}
        self.n_live = 0
        self._next_id = 0
        # Device forms (lazy pack-once; splices keep them current).
        self._planes: Optional[jnp.ndarray] = None   # (cap, 4*Wp) uint32
        self._sigs: Optional[jnp.ndarray] = None     # (capF, Wb) uint32
        self._slacks_dev: Optional[jnp.ndarray] = None  # (capF, 1) int32
        self._valid = jnp.asarray(_valid_mask(P, self.wp))
        # Residency + scan counters (the invariants tests assert on).
        self.plane_pack_count = 0
        self.sig_pack_count = 0
        self.slot_update_count = 0
        self.generation = 0
        self.n_registered = 0
        self.n_expired = 0
        self.n_scans = 0
        self.n_bank_launches = 0
        self.n_prefilter_launches = 0
        self.n_hits = 0
        self.last_survivor_frac: Optional[float] = None
        self._hit_counts: Dict[int, int] = {}
        # Bank-local measured-selectivity calibration, same discipline as
        # CorpusIndex.record_selectivity (ratios against the uncalibrated
        # estimate; tight clamp against absorbing outliers).
        self._selectivity = EwmaRatio(decay=0.3, clamp=(0.1, 10.0))
        # Host pulls route through a ShardMerger for transfer accounting
        # (DESIGN.md Sec. 3k).  Bank forms are replicated bank-local
        # state (patterns + arriving docs, identical on every process),
        # so the default merger is a pass-through counter; a service
        # attaches its engine's merger so bank traffic lands in the same
        # ledger as the corpus reductions.
        from .merge import ShardMerger
        self.merger = ShardMerger(None, None, 1)
        # Observability handle: scan/prefilter/verify spans record here.
        # A MatchService replaces it with its engine's so bank activity
        # lands in the same trace as the corpus reductions.
        self.obs = NULL_OBS

    # -- geometry --------------------------------------------------------------
    @property
    def _cap_filter(self) -> int:
        """Filter-form slot count: capacity padded to the filter row tile."""
        tile = _fq.FILTER_ROW_TILE
        return -(-self.capacity // tile) * tile

    # -- registration ----------------------------------------------------------
    def register(self, pattern, *, threshold: float,
                 ttl_s: Optional[float] = None,
                 on_hit: Optional[Callable] = None) -> int:
        """Freeze one pattern into the bank; returns its pattern id.

        ``pattern`` is an IUPAC string, a uint8 code array, or a 1-D
        ``MatchQuery``; it must match the bank's ``pattern_chars``.
        ``on_hit(pattern_id, hits)`` fires from ``scan`` with that
        pattern's (n, 4) hit rows.  The new slot is spliced into the
        cached device forms; nothing repacks.
        """
        masks = as_masks(pattern)
        if masks.shape[0] != self.pattern_chars:
            raise ValueError(
                f"bank patterns are {self.pattern_chars} chars; got "
                f"{masks.shape[0]}")
        query = MatchQuery.from_masks(masks, reduction="threshold",
                                      threshold=float(threshold))
        fo = _idx.build_query_filter(masks[None, :], (float(threshold),),
                                     self.q, self.n_bits)
        if self.n_live == self.capacity:
            self.reserve(self.capacity * 2)
        slot = self.n_live
        pid = self._next_id
        self._next_id += 1
        deadline = (np.inf if ttl_s is None
                    else self.clock() + float(ttl_s))
        self._masks[slot] = masks
        self._sig_host[slot] = fo.qsig_words[0]
        self._thr[slot] = float(threshold)
        self._slack[slot] = fo.slacks[0]
        self._nbits[slot] = fo.n_bits[0]
        self._ids[slot] = pid
        self._deadline[slot] = deadline
        self._slots[pid] = slot
        self._patterns[pid] = StandingPattern(
            pattern_id=pid, query=query, threshold=float(threshold),
            deadline=float(deadline), n_sig_bits=int(fo.n_bits[0]),
            slack=int(fo.slacks[0]))
        if on_hit is not None:
            self._callbacks[pid] = on_hit
        self._splice_slot(slot)
        self.n_live += 1
        self.n_registered += 1
        self.generation += 1
        return pid

    def unregister(self, pattern_id: int) -> None:
        """Drop one pattern; the last live slot swap-fills the hole.

        Touches at most two slots on device (the hole and the cleared
        tail), keeping operands dense over ``[0, n_live)`` with flat pack
        counters -- the splice discipline of ``PackedCorpus.set_rows``.
        """
        slot = self._slots.pop(int(pattern_id), None)
        if slot is None:
            raise ValueError(f"unknown pattern id {pattern_id}")
        self._patterns.pop(int(pattern_id))
        self._callbacks.pop(int(pattern_id), None)
        last = self.n_live - 1
        if slot != last:
            for buf in (self._masks, self._sig_host, self._thr,
                        self._slack, self._nbits, self._ids,
                        self._deadline):
                buf[slot] = buf[last]
            self._slots[int(self._ids[slot])] = slot
            self._splice_slot(slot)
        # Clear the vacated tail slot: the verify operand slices
        # [:n_live] so stale planes there are unreachable, but the
        # prefilter scans padded slots -- slack -1 guarantees they never
        # survive.
        self._masks[last] = 0
        self._sig_host[last] = 0
        self._thr[last] = 0.0
        self._slack[last] = -1
        self._nbits[last] = 0
        self._ids[last] = -1
        self._deadline[last] = np.inf
        if self._slacks_dev is not None:
            self._slacks_dev = self._slacks_dev.at[last, 0].set(-1)
            self.slot_update_count += 1
        self.n_live -= 1
        self.generation += 1

    def expire(self, now: Optional[float] = None) -> List[int]:
        """Unregister every pattern whose TTL deadline has passed."""
        now = self.clock() if now is None else float(now)
        stale = [int(pid) for pid in self._ids[:self.n_live]
                 if self._deadline[self._slots[int(pid)]] <= now]
        for pid in stale:
            self.unregister(pid)
        self.n_expired += len(stale)
        return stale

    def reserve(self, capacity: int) -> None:
        """Grow slot capacity in place; device forms zero-extend.

        Like ``PackedCorpus.reserve``: no repack (pack counters flat), new
        filter slots carry slack -1 so they can never survive the
        prefilter.
        """
        capacity = int(capacity)
        if capacity <= self.capacity:
            return
        grow = capacity - self.capacity
        old_capf = self._cap_filter
        self._masks = np.concatenate(
            [self._masks, np.zeros((grow, self.pattern_chars), np.uint8)])
        self._sig_host = np.concatenate(
            [self._sig_host, np.zeros((grow, self.sig_words), np.uint32)])
        self._thr = np.concatenate([self._thr, np.zeros(grow)])
        self._slack = np.concatenate(
            [self._slack, np.full(grow, -1, np.int64)])
        self._nbits = np.concatenate(
            [self._nbits, np.zeros(grow, np.int32)])
        self._ids = np.concatenate([self._ids, np.full(grow, -1, np.int64)])
        self._deadline = np.concatenate(
            [self._deadline, np.full(grow, np.inf)])
        self.capacity = capacity
        if self._planes is not None:
            self._planes = jnp.concatenate(
                [self._planes,
                 jnp.zeros((grow, 4 * self.wp), jnp.uint32)], 0)
        capf = self._cap_filter
        if capf > old_capf and self._sigs is not None:
            pad = capf - old_capf
            self._sigs = jnp.concatenate(
                [self._sigs, jnp.zeros((pad, self.sig_words), jnp.uint32)],
                0)
            self._slacks_dev = jnp.concatenate(
                [self._slacks_dev,
                 jnp.full((pad, 1), -1, jnp.int32)], 0)

    def pattern(self, pattern_id: int) -> StandingPattern:
        """Frozen record for one live pattern (raises if unknown)."""
        try:
            return self._patterns[int(pattern_id)]
        except KeyError:
            raise ValueError(f"unknown pattern id {pattern_id}") from None

    def live_ids(self) -> np.ndarray:
        """(n_live,) pattern ids in slot order (the launch column order)."""
        return np.array(self._ids[:self.n_live])

    # -- device residency ------------------------------------------------------
    def _splice_slot(self, slot: int) -> None:
        """Write one slot's host row into every cached device form."""
        touched = False
        if self._planes is not None:
            planes, _ = _pack_mask_planes(self._masks[slot][None, :],
                                          self.wp)
            self._planes = self._planes.at[slot, :].set(
                jnp.asarray(planes[0]))
            touched = True
        if self._sigs is not None:
            self._sigs = self._sigs.at[slot, :].set(
                jnp.asarray(self._sig_host[slot]))
            self._slacks_dev = self._slacks_dev.at[slot, 0].set(
                int(self._slack[slot]))
            touched = True
        if touched:
            self.slot_update_count += 1

    def planes(self) -> jnp.ndarray:
        """(capacity, 4*Wp) uint32 verify operand, packed at most once."""
        if self._planes is None:
            planes = np.zeros((self.capacity, 4 * self.wp), np.uint32)
            if self.n_live:
                live, _ = _pack_mask_planes(self._masks[:self.n_live],
                                            self.wp)
                planes[:self.n_live] = live
            self._planes = jnp.asarray(planes)
            self.plane_pack_count += 1
        return self._planes

    def filter_operands(self) -> tuple:
        """((capF, Wb) signatures, (capF, 1) slacks), packed at most once."""
        if self._sigs is None:
            capf = self._cap_filter
            sigs = np.zeros((capf, self.sig_words), np.uint32)
            sigs[:self.capacity] = self._sig_host
            slacks = np.full((capf, 1), -1, np.int32)
            slacks[:self.capacity, 0] = np.clip(
                self._slack, -1, np.iinfo(np.int32).max)
            self._sigs = jnp.asarray(sigs)
            self._slacks_dev = jnp.asarray(slacks)
            self.sig_pack_count += 1
        return self._sigs, self._slacks_dev

    # -- selectivity model -----------------------------------------------------
    @property
    def prunable(self) -> bool:
        """True iff the prefilter can exclude at least one live pattern."""
        n = self.n_live
        return bool(n and (self._slack[:n] < self._nbits[:n]).any())

    def estimate_survivor_frac(self, *, calibrated: bool = True) -> float:
        """Estimated fraction of live patterns surviving one doc batch.

        Per pattern: P(#absent required bits <= slack) against a document
        modeled at the analytic occupancy density (the bank never indexes
        the transient docs, so there is no measured density to use) --
        mean over patterns, not the corpus filter's union-over-queries
        (each pattern survives or dies independently).  ``calibrated``
        folds in the bank-local measured EWMA, recorded against the
        uncalibrated estimate like ``CorpusIndex``.
        """
        n = self.n_live
        if not n:
            return 0.0
        d = _idx.expected_density(self.fragment_chars, self.q, self.n_bits)
        total = sum(_idx.pass_probability(int(self._nbits[i]),
                                          int(self._slack[i]), d)
                    for i in range(n))
        frac = total / n
        if calibrated and self._selectivity.value is not None:
            frac *= self._selectivity.value
        return float(min(1.0, frac))

    # -- the scan --------------------------------------------------------------
    def scan(self, docs: np.ndarray, *, base_row: Optional[int] = None
             ) -> HitTicket:
        """Score one arriving batch against every live pattern.

        One fused ``match_swar_masks`` launch regardless of bank size
        (``n_bank_launches`` increments by exactly one), optionally
        preceded by one ``bank_prefilter`` dispatch when the planner
        prices the two-stage path cheaper.  Empty batches and empty banks
        launch nothing.
        """
        t0 = time.perf_counter()
        docs = np.asarray(docs, np.uint8)
        if docs.ndim == 1:
            docs = docs[None, :]
        if docs.ndim != 2 or docs.shape[1] != self.fragment_chars:
            raise ValueError(
                f"docs must be (n, {self.fragment_chars}); got "
                f"{docs.shape}")
        D = docs.shape[0]
        ticket = HitTicket(n_docs=D, base_row=base_row,
                           n_patterns=self.n_live)
        if D == 0 or self.n_live == 0:
            return ticket
        self.n_scans += 1
        tr = self.obs.tracer
        with tr.span("bank.scan",
                     {"n_docs": D, "n_patterns": self.n_live}
                     if tr.enabled else None):
            with tr.span("plan") as sp_plan:
                plan = self.planner.plan_bank(
                    n_docs=D, fragment_chars=self.fragment_chars,
                    pattern_chars=self.pattern_chars,
                    n_patterns=self.n_live, sig_words=self.sig_words,
                    survivor_frac=self.estimate_survivor_frac(),
                    prunable=self.prunable, force=self.filter)
                if tr.enabled:
                    sp_plan.set("strategy", plan.strategy)
                    sp_plan.set("est_seconds", plan.est_seconds)
            ticket.plan = plan
            slots = np.arange(self.n_live, dtype=np.int64)
            if plan.strategy == "filter":
                with tr.span("filter",
                             {"op": "bank_prefilter"}
                             if tr.enabled else None) as sp_fil:
                    slots = self._prefilter(docs)
                    ticket.survivor_frac = len(slots) / self.n_live
                    if tr.enabled:
                        sp_fil.set("survivor_frac", ticket.survivor_frac)
            ticket.n_verified = len(slots)
            if len(slots):
                with tr.span("launch",
                             {"op": "bank_verify", "n_verified": len(slots)}
                             if tr.enabled else None):
                    hits = self._verify(docs, slots)
                ticket.n_bank_launches = 1
                ticket.hits = hits
                self.n_hits += hits.shape[0]
                self._deliver(hits)
        ticket.wall_s = time.perf_counter() - t0
        return ticket

    def _prefilter(self, docs: np.ndarray) -> np.ndarray:
        """One ``bank_prefilter`` dispatch -> surviving live slot ids."""
        doc_sigs, _ = _idx.row_signatures(docs, self.q, self.n_bits)
        d_pad = -(-doc_sigs.shape[0] // _swar.ROW_TILE) * _swar.ROW_TILE
        if d_pad > doc_sigs.shape[0]:
            # All-zero pad docs admit only patterns with slack >= their
            # required bits -- patterns that survive any real doc too, so
            # padding never changes the survivor set.
            doc_sigs = np.concatenate(
                [doc_sigs, np.zeros((d_pad - doc_sigs.shape[0],
                                     self.sig_words), np.uint32)])
        sigs, slacks = self.filter_operands()
        flags = self.merger.pull(_fq.bank_prefilter(
            sigs, jnp.asarray(doc_sigs), slacks,
            interpret=self.interpret))[:, 0]
        self.n_prefilter_launches += 1
        survivors = np.flatnonzero(flags[:self.n_live]).astype(np.int64)
        measured = len(survivors) / self.n_live
        self._selectivity.update(
            measured / max(self.estimate_survivor_frac(calibrated=False),
                           1e-9))
        self.last_survivor_frac = measured
        return survivors

    def _verify(self, docs: np.ndarray, slots: np.ndarray) -> np.ndarray:
        """One fused roles-swapped batched launch -> (n, 4) hit rows.

        The engine's ``mode="batched"`` execution verbatim: tile the doc
        words per pattern, repeat each pattern's planes per doc row, one
        ``match_swar_masks`` dispatch, reshape to (docs, locs, patterns).
        Threshold hits come out of the same ``argwhere`` the engine runs,
        so per-pattern hit streams are bit-identical to ad-hoc compiles.
        """
        D = docs.shape[0]
        Qs = len(slots)
        d_pad = -(-D // _swar.ROW_TILE) * _swar.ROW_TILE
        words = encoding.pack_codes_u32(docs)
        padded = np.zeros((d_pad, self.need_words), np.uint32)
        w = min(words.shape[1], self.need_words)
        padded[:D, :w] = words[:, :w]
        planes_all = self.planes()
        if Qs == self.n_live:
            planes_sel = planes_all[:self.n_live]   # dense slice, no gather
        else:
            planes_sel = planes_all[jnp.asarray(slots)]
        words_t = jnp.tile(jnp.asarray(padded), (Qs, 1))
        planes_t = jnp.repeat(planes_sel, d_pad, axis=0)
        out = _swar.match_swar_masks(
            words_t, planes_t, self._valid, n_locs=self.n_locs,
            pattern_chars=self.pattern_chars, interpret=self.interpret)
        self.n_bank_launches += 1
        sc = self.merger.pull(out, kind="block").reshape(
            Qs, d_pad, self.n_locs).transpose(1, 2, 0)[:D]
        thr = self._thr[slots]
        local = np.argwhere(sc >= thr[None, None, :])
        if not local.size:
            return np.zeros((0, 4), np.int64)
        vals = sc[tuple(local.T)]
        pids = self._ids[slots[local[:, 2]]]
        return np.column_stack([local[:, 0], local[:, 1], pids,
                                vals]).astype(np.int64)

    def _deliver(self, hits: np.ndarray) -> None:
        """Per-pattern hit accounting + callback dispatch."""
        for pid in np.unique(hits[:, HIT_PATTERN]):
            pid = int(pid)
            mine = hits[hits[:, HIT_PATTERN] == pid]
            self._hit_counts[pid] = (self._hit_counts.get(pid, 0)
                                     + mine.shape[0])
            cb = self._callbacks.get(pid)
            if cb is not None:
                cb(pid, mine)

    # -- stats -----------------------------------------------------------------
    def hit_counts(self) -> Dict[int, int]:
        """Cumulative per-pattern hit counts (live and expired patterns)."""
        return dict(self._hit_counts)

    def stats(self) -> dict:
        return {
            "n_live": self.n_live,
            "capacity": self.capacity,
            "n_registered": self.n_registered,
            "n_expired": self.n_expired,
            "generation": self.generation,
            "q": self.q,
            "n_bits": self.n_bits,
            "plane_pack_count": self.plane_pack_count,
            "sig_pack_count": self.sig_pack_count,
            "slot_update_count": self.slot_update_count,
            "n_scans": self.n_scans,
            "n_bank_launches": self.n_bank_launches,
            "n_prefilter_launches": self.n_prefilter_launches,
            "n_hits": self.n_hits,
            "last_survivor_frac": self.last_survivor_frac,
            "calibration": (None if self._selectivity.value is None
                            else round(self._selectivity.value, 4)),
            "hits_by_pattern": self.hit_counts(),
        }
