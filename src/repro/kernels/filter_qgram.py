"""Q-gram signature filter -- Pallas TPU kernel (DESIGN.md Sec. 3g).

Stage one of the filter-then-verify pipeline: the corpus index
(``repro.match.index``) keeps one B-bit q-gram occurrence signature per
corpus row, packed as uint32 words; a query lowers to a signature of the
q-grams it *requires* (q-grams spanning wildcard/ambiguity positions are
dropped, so the requirement is conservative).  This kernel scans the row
signatures and emits a candidate-row bitmap for a group of patterns:

    absent_p(r)  = popcount(qsig_p & ~row_sig(r))
    candidate(r) = OR over p of  absent_p(r) <= slack_p

``slack`` encodes the q-gram lemma: an alignment with at most ``e``
mismatches destroys at most ``e * q`` of the pattern's fully-determined
q-grams, and every absent signature bit witnesses >= 1 destroyed q-gram --
so a row whose absent count exceeds ``e * q`` cannot contain a qualifying
alignment.  Zero false negatives by construction; collisions of the
signature hash only ever *add* candidates.

This is the in-storage sparse-filter discipline (Jun et al.: prune with a
cheap bulk filter where the data live, verify the survivors exactly): the
kernel touches ``W_b`` words per row instead of the ``L x Wp`` words per
row the exact scan reads, which is what makes selective queries cheap at
scale.

Data layout (rows ride the 128 lanes, as in the match kernels):
  row_sigs (Wb, R)         uint32 -- the resident signature form: column r
                                     is row r's signature, rows padded by
                                     ``padded_rows`` (pad rows are all-zero
                                     and dropped by the caller).
  qsigs    (Q_pad, Wb, 1)  uint32 -- one column of required bits per
                                     pattern; ``Q_pad = pattern_pad(Q)``, a
                                     power of two >= ``PATTERN_TILE``.
  slacks   (Q_pad, 1, 1)   int32  -- per-pattern budgets ``e * q``; pad
                                     patterns (and unsatisfiable ones)
                                     carry -1 and never pass.
  out      (1, R / 8)      int8   -- the survivor union, one bit per row:
                                     in a tile of ``T = flag_tile(R, Wb)``
                                     rows starting at row ``t*T``, bit k
                                     of byte ``t*T/8 + l`` is row
                                     ``t*T + k*T/8 + l``.

``pattern_operands`` builds ``qsigs`` and ``slacks`` on the host, and
``survivor_rows`` turns pulled flags back into row ids.  One dispatch
serves a whole coalesced group: each ``(Wb, T)`` row tile is read from HBM
once and every pattern is tested against it in VMEM, so the signatures are
read once per group, not once per pattern.  Patterns stream through VMEM
in blocks of ``pattern_block(Wb)`` along the grid's inner axis, OR-ing
into the row tile's resident flags, so VMEM stays bounded at any group
size.  A row's absent count is a sum over the ``Wb`` sublanes of its
column.  The tile's eight lane blocks of ``T/8`` rows fold into the bits
of one lane-dense byte row, so the host pulls one bit per row.  Slacks
are operands, not constants, so the number of programs follows ``Q_pad``
alone.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.popcount import popcount_words
from repro.kernels.tiling import (VMEM_BLOCK_BUDGET, coarse_row_tile,
                                  lane_bytes)

FILTER_ROW_TILE = 128      # one lane width (the bank prefilter's unit)
_FLAG_BITS = 8             # rows per flag byte
SIG_ROW_TILE = _FLAG_BITS * FILTER_ROW_TILE   # signature-form row unit
FILTER_BLOCK_ROWS = 16384  # the largest kernel tile; large forms pad to it
PATTERN_TILE = 8           # patterns per unrolled step; least Q_pad
PATTERN_BLOCK = 64         # most patterns resident in VMEM at once
# VMEM share of one pattern block; the row tile gets the rest of the
# budget.
_PATTERN_BUDGET = VMEM_BLOCK_BUDGET // 4


def padded_rows(n: int) -> int:
    """Row extent of a signature form holding ``n`` rows.

    A multiple of ``SIG_ROW_TILE``, and of ``FILTER_BLOCK_ROWS`` once
    ``n`` reaches it, so the kernel's grid stays coarse whatever the row
    count factors into; the pad costs under one block of zero columns.
    """
    unit = min(FILTER_BLOCK_ROWS,
               max(SIG_ROW_TILE, 1 << max(0, int(n) - 1).bit_length()))
    return -(-int(n) // unit) * unit


def _sublane_rows(n: int) -> int:
    return -(-n // 8) * 8


def pattern_pad(Q: int) -> int:
    """Patterns the kernel runs for a group of ``Q``: the least power of
    two of at least ``PATTERN_TILE`` that holds them."""
    return max(PATTERN_TILE, 1 << max(0, int(Q) - 1).bit_length())


def pattern_block(Wb: int) -> int:
    """Patterns per VMEM block: ``PATTERN_BLOCK``, halved while a block's
    ``qsigs`` and ``slacks`` slabs (each padded to whole (8, 128) tiles)
    overrun ``_PATTERN_BUDGET``; never below ``PATTERN_TILE``."""
    per_pattern = lane_bytes(1) * (_sublane_rows(Wb) + 8)
    pb = PATTERN_BLOCK
    while pb > PATTERN_TILE and pb * per_pattern > _PATTERN_BUDGET:
        pb //= 2
    return pb


def flag_tile(R: int, Wb: int) -> int:
    """Rows per kernel tile: the largest power-of-two multiple of
    ``SIG_ROW_TILE`` up to ``FILTER_BLOCK_ROWS`` that divides ``R`` and
    fits the VMEM the pattern block leaves.

    A function of ``R`` and ``Wb`` alone, because the flag layout depends
    on it.  Per row: the signature column, one flag bit of the int8
    output and one of the int32 accumulator (each a (1, T/8) row padded
    to whole sublane tiles: 4 bytes a row apiece).
    """
    row_bytes = _sublane_rows(Wb) * 4 + 2 * 4
    return coarse_row_tile(R, SIG_ROW_TILE, row_bytes,
                           budget_bytes=VMEM_BLOCK_BUDGET - _PATTERN_BUDGET,
                           max_rows=FILTER_BLOCK_ROWS)


def pattern_operands(qsig_words: np.ndarray, slacks: Sequence[int]
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """(Q, Wb) signatures + Q slacks -> the kernel's padded pattern operands.

    Q pads to ``pattern_pad(Q)`` with all-zero signatures at slack -1,
    which no row passes.
    """
    qsig_words = np.asarray(qsig_words, np.uint32)
    Q, Wb = qsig_words.shape
    q_pad = pattern_pad(Q)
    qs = np.zeros((q_pad, Wb, 1), np.uint32)
    qs[:Q, :, 0] = qsig_words
    sl = np.full((q_pad, 1, 1), -1, np.int32)
    sl[:Q, 0, 0] = np.asarray(slacks, np.int64)
    return qs, sl


def _filter_kernel(sig_ref, qsig_ref, slack_ref, out_ref, acc_ref):
    span = out_ref.shape[1]                  # rows per lane block, T / 8
    n_steps = qsig_ref.shape[0] // PATTERN_TILE
    packed = jnp.zeros((1, span), jnp.int32)
    for k in range(_FLAG_BITS):
        missing = ~sig_ref[:, k * span:(k + 1) * span]      # (Wb, span)

        def patterns(i, hit):
            # PATTERN_TILE independent patterns per step keep the VPU
            # busy; each absent count is a sum over the Wb sublanes.
            for j in range(PATTERN_TILE):
                p = i * PATTERN_TILE + j
                absent = jax.lax.population_count(
                    qsig_ref[p] & missing).astype(jnp.int32).sum(
                        axis=0, keepdims=True)                  # (1, span)
                hit = hit | (absent <= slack_ref[p]).astype(jnp.int32)
            return hit

        hit = jax.lax.fori_loop(0, n_steps, patterns,
                                jnp.zeros((1, span), jnp.int32))
        packed = packed | (hit << k)

    # The pattern axis is the inner grid axis: the row tile's flags stay
    # resident and every pattern block ORs into them.
    block = pl.program_id(1)

    @pl.when(block == 0)
    def _():
        acc_ref[...] = packed

    @pl.when(block > 0)
    def _():
        acc_ref[...] = acc_ref[...] | packed

    @pl.when(block == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def filter_qgram(row_sigs: jnp.ndarray, qsigs: jnp.ndarray,
                 slacks: jnp.ndarray, *,
                 interpret: bool = False) -> jnp.ndarray:
    """Bit-packed survivor union of one pattern group: see module
    docstring for layouts.

    A negative slack is legal and passes no row (the pattern's threshold
    is unsatisfiable).
    """
    Wb, R = row_sigs.shape
    Q = qsigs.shape[0]
    if R % SIG_ROW_TILE:
        raise ValueError(
            f"rows must be padded to a multiple of {SIG_ROW_TILE}")
    if Q != pattern_pad(Q) or qsigs.shape != (Q, Wb, 1):
        raise ValueError(
            f"qsigs must be (Q_pad, {Wb}, 1) with Q_pad a power of two "
            f">= {PATTERN_TILE}; got {qsigs.shape}")
    if slacks.shape != (Q, 1, 1):
        raise ValueError(f"slacks must be ({Q}, 1, 1); got {slacks.shape}")
    tile = flag_tile(R, Wb)
    span = tile // _FLAG_BITS
    pb = min(Q, pattern_block(Wb))
    return pl.pallas_call(
        _filter_kernel,
        grid=(R // tile, Q // pb),
        in_specs=[
            pl.BlockSpec((Wb, tile), lambda i, j: (0, i)),
            pl.BlockSpec((pb, Wb, 1), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((pb, 1, 1), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, span), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, R // _FLAG_BITS), jnp.int8),
        scratch_shapes=[pltpu.VMEM((1, span), jnp.int32)],
        interpret=interpret,
        name="filter_qgram",
    )(row_sigs, qsigs, slacks)


def survivor_rows(flags: np.ndarray, sig_words: int,
                  n_shards: int = 1) -> np.ndarray:
    """Pulled kernel flags -> ascending logical ids of the flagged rows.

    ``flags`` is ``filter_qgram``'s output, for a form of ``sig_words``
    words a row, for ``n_shards`` equal blocks of a form in the cyclic
    row layout, laid end to end (one block when unsharded): slot ``j`` of
    block ``s`` is logical row ``j * S + s``.  Only the nonzero bytes are
    expanded.
    """
    b = np.asarray(flags).reshape(-1).view(np.uint8)
    jf = b.size * _FLAG_BITS // n_shards     # rows per block
    tile = flag_tile(jf, sig_words)
    span = tile // _FLAG_BITS
    nz = np.flatnonzero(b)
    bits = np.unpackbits(b[nz, None], axis=1, bitorder="little").view(bool)
    shard, pos = np.divmod(nz, jf // _FLAG_BITS)
    t, lane = np.divmod(pos, span)
    slot = (t * tile + lane)[:, None] + span * np.arange(_FLAG_BITS)
    return np.sort((slot * n_shards + shard[:, None])[bits])


def filter_qgram_ref(row_sigs: np.ndarray, qsig: np.ndarray,
                     slack: int) -> np.ndarray:
    """NumPy oracle for one pattern ((R,) int32 candidate flags).

    ``row_sigs`` is row-major ``(R, Wb)``; a group's flags are the OR of
    this over its patterns.
    """
    absent = np.asarray(qsig, np.uint32) & ~np.asarray(row_sigs, np.uint32)
    bytes_ = absent.view(np.uint8).reshape(absent.shape[0], -1)
    counts = np.unpackbits(bytes_, axis=1).sum(1).astype(np.int64)
    return (counts <= slack).astype(np.int32)


# -- pattern-bank prefilter (standing queries, DESIGN.md Sec. 3j) -------------
#
# The inverted regime swaps the roles: the *patterns* are the resident
# axis (thousands of standing queries in a PatternBank) and the arriving
# document batch is the transient side.  One dispatch answers, for every
# pattern at once, "can this pattern possibly fire on any document of the
# batch?" -- the corpus filter's q-gram lemma read backwards: a document
# that contains a qualifying alignment of pattern p contains all of that
# window's q-grams, so every *required* signature bit of p absent from
# the document's occurrence signature witnesses a destroyed q-gram, and
# ``popcount(psig & ~docsig) > slack_p`` proves p cannot fire on it.
# Per-pattern slacks ride as a dynamic operand, as in the corpus filter:
# the bank mixes thresholds freely and must not recompile per distinct
# value.

def _bank_kernel(psig_ref, dsig_ref, slack_ref, out_ref):
    # Patterns ride the lanes, documents the sublanes: every vector op is
    # a lane-dense (D, TILE) block, and the per-word loop keeps the
    # (TILE, D, Wb) broadcast (8 of 128 lanes used) out of VMEM.
    psigs = psig_ref[...].T                  # (Wb, TILE) required bits
    dsigs = dsig_ref[...]                    # (D, Wb) doc occurrence sigs
    slacks = slack_ref[...]                  # (1, TILE) per-pattern budget
    shape = (dsigs.shape[0], psigs.shape[1])
    absent = jnp.zeros(shape, jnp.int32)
    for w in range(psigs.shape[0]):
        absent += popcount_words(jnp.broadcast_to(psigs[w:w + 1], shape)
                                 & ~jnp.broadcast_to(dsigs[:, w:w + 1],
                                                     shape))
    fires = (absent <= jnp.broadcast_to(slacks, shape)).astype(jnp.int32)
    out_ref[...] = jnp.max(fires, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bank_prefilter(pat_sigs: jnp.ndarray, doc_sigs: jnp.ndarray,
                   slacks: jnp.ndarray, *,
                   interpret: bool = False) -> jnp.ndarray:
    """Surviving-pattern bitmap for one document batch.

    pat_sigs (Q, Wb) uint32 -- per-pattern required-bit signatures, rows
                               padded to ``FILTER_ROW_TILE`` (pad rows
                               carry slack -1 and never survive).
    doc_sigs (D, Wb) uint32 -- per-document occurrence signatures (all-
                               zero pad docs admit only unprunable
                               patterns, which survive regardless).
    slacks   (Q, 1)  int32  -- per-pattern mismatch budgets e*q
                               (negative: unsatisfiable, never fires).
    out      (Q, 1)  int32  -- 1 iff some document admits the pattern.
    """
    Q, Wb = pat_sigs.shape
    D = doc_sigs.shape[0]
    if Q % FILTER_ROW_TILE:
        raise ValueError(
            f"patterns must be padded to a multiple of {FILTER_ROW_TILE}")
    if doc_sigs.shape[1] != Wb:
        raise ValueError(f"doc_sigs must be (D, {Wb}); got "
                         f"{doc_sigs.shape}")
    if slacks.shape != (Q, 1):
        raise ValueError(f"slacks must be ({Q}, 1); got {slacks.shape}")
    # Per-pattern footprint: the lane-padded signature row plus a column
    # of each (D, TILE) temporary (absent counts and popcount stages).
    tile = coarse_row_tile(Q, FILTER_ROW_TILE,
                           lane_bytes(Wb) + 8 * 4 * (D + 2))
    out = pl.pallas_call(
        _bank_kernel,
        grid=(Q // tile,),
        in_specs=[
            pl.BlockSpec((tile, Wb), lambda i: (i, 0)),
            pl.BlockSpec((D, Wb), lambda i: (0, 0)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Q), jnp.int32),
        interpret=interpret,
        name="bank_prefilter",
    )(pat_sigs, doc_sigs, slacks.reshape(1, Q))
    return out.reshape(Q, 1)


def bank_prefilter_ref(pat_sigs: np.ndarray, doc_sigs: np.ndarray,
                       slacks: np.ndarray) -> np.ndarray:
    """NumPy oracle for ``bank_prefilter`` ((Q,) int32 survivor flags)."""
    ps = np.asarray(pat_sigs, np.uint32)[:, None, :]
    ds = np.asarray(doc_sigs, np.uint32)[None, :, :]
    absent = ps & ~ds                                # (Q, D, Wb)
    bytes_ = absent.view(np.uint8).reshape(
        absent.shape[0], absent.shape[1], -1)
    counts = np.unpackbits(bytes_, axis=2).sum(2).astype(np.int64)
    return (counts <= np.asarray(slacks).reshape(-1, 1)).any(1).astype(
        np.int32)
