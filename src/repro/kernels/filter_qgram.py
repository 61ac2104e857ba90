"""Q-gram signature filter -- Pallas TPU kernel (DESIGN.md Sec. 3g).

Stage one of the filter-then-verify pipeline: the corpus index
(``repro.match.index``) keeps one B-bit q-gram occurrence signature per
corpus row, packed as uint32 words; a query lowers to a signature of the
q-grams it *requires* (q-grams spanning wildcard/ambiguity positions are
dropped, so the requirement is conservative).  This kernel scans the row
signatures and emits a candidate-row bitmap:

    absent(r)    = popcount(query_sig & ~row_sig(r))
    candidate(r) = absent(r) <= slack

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

Data layout:
  row_sigs (R, Wb) uint32 -- per-row q-gram signatures, rows padded to
                             ``FILTER_ROW_TILE`` (padding rows are all-zero
                             and sliced off by the caller).
  qsig     (1, Wb) uint32 -- the query's required-bit signature.
  out      (R, 1)  int32  -- 1 iff the row is a candidate.

The row tile is much larger than the match kernels' (128 vs 8): the
per-row work is a handful of word ops, so the grid must be coarse for the
launch not to dominate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.popcount import popcount_words
from repro.kernels.tiling import coarse_row_tile, lane_bytes

FILTER_ROW_TILE = 128


def _filter_kernel(sig_ref, qsig_ref, out_ref, *, slack: int):
    sigs = sig_ref[...]                      # (TILE, Wb)
    qsig = qsig_ref[...]                     # (1, Wb)
    # Full SWAR popcount per word (absent bits are arbitrary, unlike the
    # match kernels' <=1-bit-per-lane fast path).
    counts = popcount_words(qsig & ~sigs).sum(axis=-1, keepdims=True)
    out_ref[...] = (counts <= slack).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("slack", "interpret"))
def filter_qgram(row_sigs: jnp.ndarray, qsig: jnp.ndarray, *, slack: int,
                 interpret: bool = False) -> jnp.ndarray:
    """Candidate-row bitmap: see module docstring for layouts.

    ``slack`` is static: it is query geometry (``e * q``), one compile per
    distinct value, like ``pattern_chars`` in the match kernels.  A
    negative slack is legal and marks no row (the query's threshold is
    unsatisfiable).
    """
    R, Wb = row_sigs.shape
    if R % FILTER_ROW_TILE:
        raise ValueError(
            f"rows must be padded to a multiple of {FILTER_ROW_TILE}")
    if qsig.shape != (1, Wb):
        raise ValueError(f"qsig must be (1, {Wb}); got {qsig.shape}")
    # Row-elementwise body: coarsen the dispatch tile (kernels.tiling) so
    # launch overhead amortizes at scale; output is bit-identical.
    tile = coarse_row_tile(R, FILTER_ROW_TILE, lane_bytes(Wb, 1))
    grid = (R // tile,)
    kernel = functools.partial(_filter_kernel, slack=int(slack))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, Wb), lambda i: (i, 0)),
            pl.BlockSpec((1, Wb), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        interpret=interpret,
        name="filter_qgram",
    )(row_sigs, qsig)


def filter_qgram_ref(row_sigs: np.ndarray, qsig: np.ndarray,
                     slack: int) -> np.ndarray:
    """NumPy oracle for the filter kernel ((R,) int32 candidate flags)."""
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
# Per-pattern slacks ride as a dynamic operand (unlike the corpus
# filter's static slack: the bank mixes thresholds freely and must not
# recompile per distinct value).

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
