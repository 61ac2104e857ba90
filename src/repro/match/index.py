"""Device-resident q-gram filter index (DESIGN.md Sec. 3g).

The paper's premise is that at-scale matching is bound by touching every
byte of the resident database; the companion in-storage accelerator
literature (Jun et al.'s sparse pattern processor; Mutlu et al.'s
minimize-data-touched discipline) prunes with a cheap filter stage before
exact matching.  This module is that stage for the TPU engine:

* ``CorpusIndex`` maintains, per corpus row, a **B-bit q-gram occurrence
  signature**: every q-gram (q consecutive 2-bit characters) of the row is
  hashed to one of B bits and OR'd in.  Signatures are packed as uint32
  words, one column of a ``(Wb, rows)`` form per row (rows ride the
  filter kernel's lanes), and kept device-resident alongside the corpus's
  SWAR/one-hot forms
  -- same lazy-pack-once protocol, same incremental row splices
  (``append_rows`` / ``set_rows`` index only the touched rows; pack
  counters stay flat), same generation discipline (the index never stores
  content of its own; it derives from the corpus host buffer it observes).
* ``build_query_filter`` lowers a query to the signature of the q-grams it
  *requires*.  Only q-grams whose q positions are all exact (one-hot
  accept masks) participate -- a q-gram spanning a wildcard/ambiguity
  position is dropped, which can only lose pruning power, never
  correctness.  **Zero false negatives by construction** (the q-gram
  lemma): an alignment scoring >= t has at most e = floor(P - t)
  mismatches; each mismatch destroys at most q required q-grams; each
  signature bit absent from the row witnesses >= 1 destroyed q-gram.  So
  ``popcount(qsig & ~rowsig) > e*q`` proves the row has no qualifying
  alignment.  Hash collisions only ever *add* candidates.
* **Selectivity feedback**: the index tracks measured row-signature
  density and an EWMA of (measured / predicted) survivor fractions from
  executed filtered queries, which calibrates the planner's two-stage
  cost model (``Planner.plan`` with a ``FilterContext``).

The filter stage itself is ``repro.kernels.filter_qgram``; the engine
gathers survivors and verifies them through the existing exact path
(the ``rows=`` subset machinery), bit-identical to a full scan.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.distributed import sharding as _sharding
from repro.kernels import filter_qgram as _fq
from repro.match.feedback import EwmaRatio

from . import merge as _merge

# Host signature packing proceeds in bounded row chunks: pack_bit_rows
# materializes an (n, n_bits) occupancy matrix, which at 1M rows x 256
# bits would be a 1 GiB temporary.  64K-row chunks cap it at ~64 MiB
# with no change in output.
_BUILD_CHUNK_ROWS = 1 << 16

# Fibonacci-multiplicative hash constant (Knuth); the top log2(B) bits of
# the wrapped product spread consecutive q-gram values well.
_HASH_MUL = np.uint32(2654435761)

DEFAULT_Q = 4
DEFAULT_BITS = 256
# One-hot accept mask -> character code (0 for non-one-hot entries; callers
# select with the one-hot test first).
_ONEHOT_CODE = np.zeros(256, np.uint8)
for _c in range(4):
    _ONEHOT_CODE[1 << _c] = _c


def qgram_values(codes: np.ndarray, q: int) -> np.ndarray:
    """(..., n) uint8 codes -> (..., n-q+1) uint32 base-4 q-gram values."""
    codes = np.asarray(codes, np.uint8)
    n = codes.shape[-1]
    if n < q:
        return np.zeros(codes.shape[:-1] + (0,), np.uint32)
    # Shift and OR in the narrowest type that holds 2q bits, widening
    # once at the end: a corpus-sized index build is memory-bound here.
    dt = np.uint8 if q <= 4 else np.uint16 if q <= 8 else np.uint32
    c = codes.astype(dt, copy=False)
    vals = c[..., :n - q + 1].copy()
    for j in range(1, q):
        vals |= c[..., j:n - q + 1 + j] << dt(2 * j)
    return vals.astype(np.uint32, copy=False)


def hash_bits(vals: np.ndarray, n_bits: int) -> np.ndarray:
    """q-gram values -> signature bit indices in [0, n_bits)."""
    shift = np.uint32(32 - int(n_bits).bit_length() + 1)
    return ((np.asarray(vals, np.uint32) * _HASH_MUL) >> shift).astype(
        np.int64)


def pack_bit_rows(bit_idx_rows: Sequence[np.ndarray], n_bits: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row bit indices -> ((n, Wb) uint32 words, (n,) distinct counts).

    Bit ``b`` of a signature lives at bit ``b % 32`` of word ``b // 32``.
    ``bit_idx_rows`` is a (n, G) array or a ragged sequence of 1-D index
    arrays; duplicates are free (OR is idempotent).  One vectorized
    scatter packs all rows at once -- the first index build on a large
    corpus is O(total q-grams) numpy work, not an O(rows) Python loop --
    and the distinct-bit counts fall out of the packed words.
    """
    n = len(bit_idx_rows)
    wb = n_bits // 32
    if n == 0:
        return np.zeros((0, wb), np.uint32), np.zeros(0, np.int32)
    if isinstance(bit_idx_rows, np.ndarray) and bit_idx_rows.ndim == 2:
        row_ids = np.arange(n)[:, None]          # broadcast, no repeat
        flat_bits = bit_idx_rows
    else:
        lens = np.fromiter((len(b) for b in bit_idx_rows), np.int64, n)
        row_ids = np.repeat(np.arange(n), lens)
        flat_bits = (np.concatenate([np.asarray(b, np.int64)
                                     for b in bit_idx_rows])
                     if lens.sum() else np.zeros(0, np.int64))
    # Boolean occupancy matrix packed little-endian 32 bits to a word:
    # one fancy assignment and one packbits, no unbuffered ufunc.at
    # scatter.  Duplicate bits are free.
    occupancy = np.zeros((n, n_bits), bool)
    occupancy[row_ids, flat_bits] = True
    words = np.packbits(occupancy, axis=1, bitorder="little").view(
        "<u4").astype(np.uint32)
    counts = np.count_nonzero(occupancy, axis=1).astype(np.int32)
    return words, counts


def row_signatures(rows: np.ndarray, q: int, n_bits: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(n, F) uint8 code rows -> packed signatures + per-row bit counts."""
    rows = np.asarray(rows, np.uint8)
    bits = hash_bits(qgram_values(rows, q), n_bits)
    return pack_bit_rows(bits, n_bits)


@dataclasses.dataclass(frozen=True)
class FilterOperands:
    """Per-query filter-stage operands, row-count independent.

    Derived from (query content, index q, index B) only, so -- like the
    packed pattern operands -- they survive every corpus generation and
    every growth step unchanged.
    """

    qsig_words: np.ndarray        # (Q, Wb) uint32 required-bit signatures
    slacks: Tuple[int, ...]       # per-query e*q (negative: unsatisfiable)
    n_bits: Tuple[int, ...]       # per-query distinct required bits


def build_query_filter(masks2d: np.ndarray,
                       thresholds: Sequence[float], q: int,
                       n_bits: int) -> FilterOperands:
    """Lower query accept-masks + thresholds to filter operands.

    ``masks2d`` is (Q, P) uint8 accept masks; a pattern position is
    *exact* iff its mask is one-hot.  Q-grams spanning any non-exact
    position are dropped (conservative).  ``slack = floor(P - t) * q``:
    the mismatch budget times the per-mismatch q-gram damage bound.
    """
    masks2d = np.asarray(masks2d, np.uint8)
    Q, P = masks2d.shape
    onehot = (masks2d & (masks2d - 1)) == 0          # mask 0 never occurs
    codes = _ONEHOT_CODE[masks2d]
    sig_rows = []
    for i in range(Q):
        if P < q:
            sig_rows.append(np.zeros(0, np.int64))
            continue
        vals = qgram_values(codes[i], q)
        usable = np.ones(P - q + 1, bool)
        for j in range(q):
            usable &= onehot[i, j:P - q + 1 + j]
        sig_rows.append(hash_bits(vals[usable], n_bits))
    words, counts = pack_bit_rows(sig_rows, n_bits)
    slacks = tuple(
        (math.floor(P - float(t)) * q) if float(t) <= P else -1
        for t in thresholds)
    return FilterOperands(qsig_words=words, slacks=slacks,
                          n_bits=tuple(int(c) for c in counts))


def binom_cdf(k: int, n: int, p: float) -> float:
    """P(Binomial(n, p) <= k), direct log-space sum (no scipy dep)."""
    if k < 0:
        return 0.0
    if k >= n or p <= 0.0:
        return 1.0
    if p >= 1.0:
        return 0.0
    lg = math.lgamma
    total = 0.0
    for a in range(k + 1):
        total += math.exp(lg(n + 1) - lg(a + 1) - lg(n - a + 1)
                          + a * math.log(p) + (n - a) * math.log1p(-p))
    return min(1.0, total)


def expected_density(n_chars: int, q: int, n_bits: int) -> float:
    """Analytic prior for hashed q-gram signature occupancy.

    A length-``n_chars`` row throws ``n_chars - q + 1`` q-grams into
    ``n_bits`` bins; the expected fraction of bits set is the classic
    occupancy formula.  Shared between the corpus index (before the
    first pack measures the real density) and the pattern bank (which
    models the *arriving documents'* density without ever packing
    them).
    """
    g = int(n_chars) - int(q) + 1
    return 1.0 - (1.0 - 1.0 / int(n_bits)) ** max(g, 0)


def pass_probability(n_query_bits: int, slack: int, density: float) -> float:
    """Probability one random row admits one query under the filter.

    Required bits are modeled as independently present at ``density``;
    the query passes iff at most ``slack`` of its ``n_query_bits``
    required bits are absent.  Negative slack is the unsatisfiable
    sentinel (prunes everything); ``n_query_bits == 0`` or
    ``slack >= n_query_bits`` passes everything.
    """
    if slack < 0:
        return 0.0
    return binom_cdf(int(slack), int(n_query_bits), 1.0 - float(density))


class CorpusIndex:
    """Per-row q-gram signatures, device-resident and grown in place.

    Attaches to a ``PackedCorpus`` as an observer: every row splice
    (``append_rows`` / ``set_rows``) re-derives signatures for exactly the
    touched rows and splices them into the cached device form
    (``.at[].set``), capacity growth zero-extends on device, and
    ``invalidate`` drops the form -- the same residency protocol as the
    SWAR/one-hot forms, with its own ``sig_pack_count`` asserting the
    at-most-one-host-pack invariant.
    """

    def __init__(self, corpus, *, q: int = DEFAULT_Q,
                 n_bits: int = DEFAULT_BITS):
        q = int(q)
        n_bits = int(n_bits)
        if q < 1 or q > 16:
            raise ValueError(f"q must be in [1, 16], got {q}")
        if n_bits < 32 or n_bits & (n_bits - 1):
            raise ValueError(
                f"n_bits must be a power of two >= 32, got {n_bits}")
        if corpus.fragment_chars < q:
            raise ValueError(
                f"fragment_chars={corpus.fragment_chars} shorter than "
                f"q={q}: no q-grams to index")
        self.corpus = corpus
        self.q = q
        self.n_bits = n_bits
        self.sig_words = n_bits // 32
        self._sigs: Optional[jnp.ndarray] = None     # (Wb, S_pad) uint32
        self._row_bits = np.zeros(corpus.capacity, np.int32)
        # Multi-controller: per-row distinct-bit counts live on device
        # ((S_pad, 1) int32, same cyclic layout as the signatures) --
        # each host only ever computes counts for the rows it packs, and
        # density() must be identical on every process, so the mean
        # reduces device-side.
        self._bits_dev: Optional[jnp.ndarray] = None
        self._dsum_fn = None
        self._dcache: Optional[tuple] = None
        self.sig_pack_count = 0
        self.row_update_count = 0
        # Selectivity feedback: EWMA of measured/predicted survivor-
        # fraction ratios from executed filtered queries (the planner's
        # calibration term), plus plain counters for stats surfaces.
        # The shared EwmaRatio idiom (repro.match.feedback) with the
        # historically tight one-decade clamp -- see record_selectivity.
        self._selectivity = EwmaRatio(decay=0.3, clamp=(0.1, 10.0))
        self.n_filter_runs = 0
        self.last_survivor_frac: Optional[float] = None
        corpus.attach_index(self)

    # -- geometry --------------------------------------------------------------
    @property
    def _rows_padded(self) -> int:
        """Device-form row count: per-shard capacity padded for the filter
        kernel (``filter_qgram.padded_rows``).

        The signature form mirrors the corpus's cyclic row layout (same
        shard for every logical row) but pads each shard's slot count
        independently -- its stride ``Jf`` is therefore generally larger
        than the corpus forms' ``J``.
        """
        s = self.corpus.n_shards
        return s * _fq.padded_rows(self.corpus.capacity_padded // s)

    @property
    def shard_stride(self) -> int:
        """Per-shard physical stride of the signature form."""
        return self._rows_padded // self.corpus.n_shards

    # -- residency -------------------------------------------------------------
    def signatures(self) -> jnp.ndarray:
        """(Wb, S_pad) uint32 device-resident row signatures, one column
        per row (rows on the lanes, the filter kernel's layout).

        First call packs the live rows on the host (one event; reserved
        and padding rows are all-zero); later calls reuse the cached
        array, which row splices keep up to date incrementally.
        """
        if self._sigs is None:
            tr = self.corpus.obs.tracer
            with tr.span("pack",
                         {"form": "qgram_sigs", "rows": self._rows_padded}
                         if tr.enabled else None):
                if self.corpus._multiprocess:
                    self._build_sigs_per_host()
                else:
                    n = self.corpus.n_rows
                    s = self.corpus.n_shards
                    stride = self.shard_stride
                    words = np.zeros((self.sig_words, self._rows_padded),
                                     np.uint32)
                    # Chunked pack (bounded occupancy temporary) straight
                    # into the cyclic physical layout the corpus forms use.
                    for b0 in range(0, n, _BUILD_CHUNK_ROWS):
                        b1 = min(b0 + _BUILD_CHUNK_ROWS, n)
                        live, counts = row_signatures(
                            self.corpus.fragments[b0:b1], self.q,
                            self.n_bits)
                        words[:, _sharding.cyclic_physical_rows(
                            np.arange(b0, b1), s, stride)] = live.T
                        self._row_bits[b0:b1] = counts
                    self._sigs = self.corpus._place(words, axis=1)
            self.sig_pack_count += 1
            self.corpus.obs.metrics.counter("corpus.packs").inc()
        return self._sigs

    def _build_sigs_per_host(self) -> None:
        """First signature pack, multi-controller: per-host shard blocks.

        Signature block ``s`` holds rows ``s::S`` (slot ``j`` <-> logical
        ``s + j*S``), so each process hashes only the rows its devices
        own -- bit-identical to permuting a global pack, at 1/P of the
        host work.  Per-row bit counts ride along as a device form
        (``_bits_dev``) because no host holds all of them.
        """
        S = self.corpus.n_shards
        Jf = self.shard_stride
        n = self.corpus.n_rows
        blocks: dict = {}

        def pack(s):
            blk = blocks.get(s)
            if blk is None:
                words = np.zeros((self.sig_words, Jf), np.uint32)
                counts = np.zeros((Jf, 1), np.int32)
                frag_s = self.corpus._frags[s::S]
                live_s = max(0, (n - s + S - 1) // S)
                for b0 in range(0, live_s, _BUILD_CHUNK_ROWS):
                    b1 = min(b0 + _BUILD_CHUNK_ROWS, live_s)
                    w, c = row_signatures(frag_s[b0:b1], self.q,
                                          self.n_bits)
                    words[:, b0:b1] = w.T
                    counts[b0:b1, 0] = c
                blocks[s] = blk = (words, counts)
            return blk
        self._sigs = jax.make_array_from_callback(
            (self.sig_words, S * Jf), self.corpus._row_sharding(1),
            lambda idx: pack((idx[1].start or 0) // Jf)[0])
        self._bits_dev = jax.make_array_from_callback(
            (S * Jf, 1), self.corpus._row_sharding(),
            lambda idx: pack((idx[0].start or 0) // Jf)[1])
        self._dcache = None

    # -- corpus observer hooks -------------------------------------------------
    def _on_rows_written(self, start: int, rows: np.ndarray) -> None:
        """Touched-rows-only splice, mirroring ``PackedCorpus._splice_device``."""
        n = rows.shape[0]
        if self._sigs is not None:
            words, counts = row_signatures(rows, self.q, self.n_bits)
            s = self.corpus.n_shards
            if s == 1:
                self._sigs = self._sigs.at[:, start:start + n].set(
                    jnp.asarray(words.T))
            elif self.corpus._multiprocess:
                phys = _sharding.cyclic_physical_rows(
                    np.arange(start, start + n), s, self.shard_stride)
                self._sigs = _merge.scatter_rows(self._sigs, phys, words.T,
                                                 axis=1)
                if self._bits_dev is not None:
                    self._bits_dev = _merge.scatter_rows(
                        self._bits_dev, phys,
                        counts[:, None].astype(np.int32))
                self._dcache = None
            else:
                phys = jnp.asarray(_sharding.cyclic_physical_rows(
                    np.arange(start, start + n), s, self.shard_stride))
                self._sigs = self._sigs.at[:, phys].set(
                    jnp.asarray(words.T))
            self._row_bits[start:start + n] = counts
            self.row_update_count += n

    def _on_capacity(self) -> None:
        """Capacity growth: zero-extend on device, extend host counters."""
        cap = self.corpus.capacity
        if cap > self._row_bits.shape[0]:
            self._row_bits = np.concatenate(
                [self._row_bits,
                 np.zeros(cap - self._row_bits.shape[0], np.int32)])
        if self._sigs is not None:
            pad = self._rows_padded
            if self._sigs.shape[1] < pad:
                # Per-shard zero-extension through the corpus's layout
                # helper: rows keep their shard and slot, placement is
                # re-applied.
                self._sigs = self.corpus._grow_form_rows(self._sigs, pad,
                                                         axis=1)
                if self._bits_dev is not None:
                    self._bits_dev = self.corpus._grow_form_rows(
                        self._bits_dev, pad)
                    self._dcache = None

    def _on_invalidate(self) -> None:
        self._sigs = None
        self._bits_dev = None
        self._dsum_fn = None
        self._dcache = None

    # -- selectivity model -----------------------------------------------------
    def density(self) -> float:
        """Mean fraction of signature bits set per live row.

        Measured once the index is built; before that, the analytic prior
        for hashed q-gram occupancy (F - q + 1 throws into B bins) -- so
        the planner can price the filter before paying the first pack.
        """
        n = self.corpus.n_rows
        if self._sigs is not None and n:
            if self._bits_dev is not None:
                return self._density_device(n)
            return float(self._row_bits[:n].mean()) / self.n_bits
        return expected_density(self.corpus.fragment_chars, self.q,
                                self.n_bits)

    def _density_device(self, n: int) -> float:
        """Live-row mean bit count from the device counts, replicated.

        The masked integer sum reduces on device (XLA inserts the
        cross-shard psum) and every process receives the same scalar, so
        planner decisions stay in lock step; ``float(total) / n``
        reproduces ``np.mean`` (exact integer sum, one float64 divide)
        bit for bit.  Cached per (generation, n): density is read on
        every plan, the corpus mutates far less often.
        """
        key = (self.corpus.generation, n)
        if self._dcache is not None and self._dcache[0] == key:
            return self._dcache[1]
        if self._dsum_fn is None:
            Jf, S = self.shard_stride, self.corpus.n_shards
            ns = NamedSharding(self.corpus._mesh, PartitionSpec())

            def total(c, n_):
                p = jnp.arange(c.shape[0])
                logical = (p % Jf) * S + p // Jf
                return jnp.sum(jnp.where(logical < n_, c[:, 0], 0))
            self._dsum_fn = jax.jit(total, out_shardings=ns)
        tot = int(np.asarray(self._dsum_fn(self._bits_dev, np.int32(n))))
        val = float(tot) / n / self.n_bits
        self._dcache = (key, val)
        return val

    def estimate_survivor_frac(self, n_query_bits: Sequence[int],
                               slacks: Sequence[int], *,
                               calibrated: bool = True) -> float:
        """Estimated fraction of rows surviving the (union) filter.

        Per query: P(#absent required bits <= slack) with bits modeled as
        independently present at the measured density; union-bounded over
        queries.  ``calibrated=True`` (the planner's spelling) scales by
        the measured-selectivity EWMA; ``calibrated=False`` is the raw
        model prediction -- the quantity measurements are recorded
        against, so the calibration converges to measured/model instead
        of chasing its own output.
        """
        d = self.density()
        total = 0.0
        for bq, slack in zip(n_query_bits, slacks):
            if slack < 0:
                continue                 # unsatisfiable: prunes every row
            total += pass_probability(bq, slack, d)
        if calibrated and self._calibration is not None:
            total *= self._calibration
        return float(min(1.0, total))

    @property
    def _calibration(self) -> Optional[float]:
        """Measured-selectivity EWMA value (None until the first run)."""
        return self._selectivity.value

    def record_selectivity(self, predicted: float, measured: float) -> None:
        """Fold one filtered run's outcome into the calibration EWMA.

        ``predicted`` must be the **uncalibrated** model estimate
        (``estimate_survivor_frac(..., calibrated=False)``): folding in
        ratios against already-calibrated predictions would converge the
        calibrated estimate only to the geometric mean of model and
        truth, never to the truth itself.

        The per-update ratio clamp is deliberately tight (one decade):
        only filtered runs ever record, so a single wild outlier that
        saturated the estimate could flip every future eligible query to
        "scan" and never be contradicted -- an absorbing state.  Walking
        the calibration a long way therefore requires *consistent*
        evidence across runs, each of which still took the filter path.
        """
        self._selectivity.update(measured / max(predicted, 1e-9))
        self.n_filter_runs += 1
        self.last_survivor_frac = measured

    def stats(self) -> dict:
        return {
            "q": self.q,
            "n_bits": self.n_bits,
            "sig_pack_count": self.sig_pack_count,
            "row_update_count": self.row_update_count,
            "density": round(self.density(), 4),
            "n_filter_runs": self.n_filter_runs,
            "last_survivor_frac": self.last_survivor_frac,
            "calibration": (None if self._calibration is None
                            else round(self._calibration, 4)),
        }
