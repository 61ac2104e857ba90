"""SWAR bit-parallel sliding string match -- Pallas TPU kernel.

TPU adaptation of CRAM-PM Phase 1+2 (DESIGN.md Sec. 2b): 16 two-bit
characters per uint32 lane; one VPU op compares 8x128x16 characters -- the
analogue of a row-wide gang of XOR/NOR gates -- and the popcount reduction
tree becomes branch-free SWAR arithmetic.  The match string never leaves
VMEM (the CRAM analogy: the match string never leaves the row).

Data layout (HBM, the public contract):
  ref_words  (R, W)  uint32 -- folded reference fragments, 16 chars/word,
                               padded with >= 1 zero word at the end.
  pat_words  (R, Wp) uint32 -- per-row pattern (broadcast for shared).
  valid_mask (1, Wp) uint32 -- low-bit-of-lane mask of valid pattern chars.
  out        (R, L)  int32  -- similarity scores per alignment.

Kernel layout (VMEM): each grid step loads a ``(tile, W)`` row block once
and transposes it to ``(W, tile)``, so corpus rows ride the 128 lanes and
words ride sublanes.  Alignments ``16b .. 16b+15`` share base word ``b``
and differ only in the in-word shift, so one ``(16, tile)`` block of
window words is two sublane slices (words ``b+k`` and ``b+k+1``) shifted
by a per-sublane shift vector: every vector op is lane-dense.  A loop step
scores 128 alignments from one sublane-aligned word window and stores its
``(128, tile)`` score block aligned; the finished block is transposed back
and stored once per tile.  No index is an unaligned dynamic offset, which
Mosaic cannot lower.  Rows are padded to ``LANE_TILE`` inside the wrapper
(the public padding contract stays ``ROW_TILE``).

Grid: one program per row tile; the whole alignment sweep runs inside the
kernel, so the reference tile is read from HBM exactly once per pattern
block (the paper's data-movement-minimization objective, HBM->VMEM).

``match_swar_masks`` is the accept-set variant (the reconfigurable-logic
story of the paper, Sec. 1/3: same resident data, reprogrammed match
logic): instead of one packed pattern word per 16 positions it takes four
*bit-planes* -- plane c has the low bit of lane i set iff DNA code c is
accepted at pattern position i -- and a window lane scores a match iff its
character's plane accepts it.  IUPAC ambiguity codes, N wildcards and
arbitrary character classes all lower to these planes; exact matching is
the one-hot special case (but rides the cheaper XOR kernel above).

  pat_planes (R, 4*Wp) uint32 -- planes concatenated along words:
                                 plane c occupies columns [c*Wp, (c+1)*Wp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import coarse_row_tile, lane_bytes

M1 = np.uint32(0x55555555)
M2 = np.uint32(0x33333333)
M4 = np.uint32(0x0F0F0F0F)
MUL = np.uint32(0x01010101)
# Code c replicated into every 2-bit lane (lane equality test operand).
CODE_LANES = tuple(np.uint32(c * 0x55555555) for c in range(4))

ROW_TILE = 8      # public row-padding contract (sublane-aligned)
LANE_TILE = 128   # kernel row tile: rows ride the lanes inside the kernel
SHIFTS = 16       # alignments per base word (characters per uint32)
GROUP_BLOCKS = 8  # base words per loop step: 8 x 16 = 128 alignments


def _mismatch_count(mism):
    """Per-word popcount of a <=1-bit-per-2-bit-lane word (SWAR stage 2+)."""
    v = (mism & M2) + ((mism >> jnp.uint32(2)) & M2)
    v = (v + (v >> jnp.uint32(4))) & M4
    return ((v * MUL) >> jnp.uint32(24)).astype(jnp.int32)


def _group_geometry(n_locs: int, wp: int, W: int) -> tuple[int, int, int]:
    """(groups, loaded word rows per group, word-scratch rows).

    A group is ``GROUP_BLOCKS`` base words = 128 alignments; it reads word
    rows ``[8g, 8g + 8 + wp)``, loaded as one sublane-aligned window.
    """
    n_groups = -(-n_locs // (GROUP_BLOCKS * SHIFTS))
    rows = -(-(GROUP_BLOCKS + wp) // 8) * 8
    return n_groups, rows, max(-(-W // 8) * 8,
                               (n_groups - 1) * GROUP_BLOCKS + rows)


def _sweep(ref_ref, out_ref, words_scr, scores_scr, *, n_locs: int, wp: int,
           pattern_chars: int, word_mismatch):
    """Score every alignment of one row tile into ``out_ref``.

    The ``(tile, W)`` block is transposed once into ``words_scr`` (rows on
    lanes); each loop step scores one group of 128 alignments from an
    aligned word window and stores its ``(128, tile)`` block aligned; the
    finished ``(L_pad, tile)`` block is transposed back in one store.
    ``word_mismatch(window, k)`` returns the mismatch bits (<= 1 per 2-bit
    lane) of window word ``k`` against pattern word ``k``.
    """
    W, tile = ref_ref.shape[1], ref_ref.shape[0]
    n_groups, rows, _ = _group_geometry(n_locs, wp, W)
    words_scr[...] = jnp.zeros_like(words_scr)
    words_scr[0:W, :] = ref_ref[...].T
    sh = jax.lax.broadcasted_iota(jnp.uint32, (SHIFTS, tile), 0) * 2
    hi_sh = (jnp.uint32(32) - sh) & jnp.uint32(31)
    aligned = sh == 0

    def group(g, carry):
        xt = words_scr[pl.ds(pl.multiple_of(g * GROUP_BLOCKS, 8), rows), :]
        blocks = []
        for b in range(GROUP_BLOCKS):
            mismatches = jnp.zeros((SHIFTS, tile), jnp.int32)
            for k in range(wp):
                lo = jnp.broadcast_to(xt[b + k:b + k + 1], sh.shape)
                hi = jnp.broadcast_to(xt[b + k + 1:b + k + 2], sh.shape)
                window = (lo >> sh) | jnp.where(aligned, jnp.uint32(0),
                                                hi << hi_sh)
                mismatches += _mismatch_count(word_mismatch(window, k))
            blocks.append(pattern_chars - mismatches)
        span = GROUP_BLOCKS * SHIFTS
        scores_scr[pl.ds(pl.multiple_of(g * span, span), span), :] = \
            jnp.concatenate(blocks, axis=0)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    out_ref[...] = scores_scr[...].T[:, :n_locs]


def _swar_kernel(ref_ref, pat_ref, mask_ref, out_ref, words_scr, scores_scr,
                 *, n_locs: int, pattern_chars: int, wp: int):
    pt = pat_ref[...].T                      # (Wp, tile)
    mask = mask_ref[...]                     # (1, Wp)

    def word_mismatch(window, k):
        diff = window ^ pt[k:k + 1]
        return (diff | (diff >> jnp.uint32(1))) & M1 & mask[:, k:k + 1]

    _sweep(ref_ref, out_ref, words_scr, scores_scr, n_locs=n_locs, wp=wp,
           pattern_chars=pattern_chars, word_mismatch=word_mismatch)


def _swar_masks_kernel(ref_ref, plane_ref, mask_ref, out_ref, words_scr,
                       scores_scr, *, n_locs: int, pattern_chars: int,
                       wp: int):
    planes = plane_ref[...].T                # (4*Wp, tile)
    valid = mask_ref[...]                    # (1, Wp)

    def word_mismatch(window, k):
        # Accept bit per lane: lane equals code c (both bits of the XOR
        # clear) AND plane c accepts position i.  Four equality tests
        # replace the single XOR of the exact kernel -- still branch-free
        # VPU work, no decode of the 2-bit characters.
        accept = jnp.zeros_like(window)
        for c in range(4):
            diff = window ^ CODE_LANES[c]
            eq = ~(diff | (diff >> jnp.uint32(1))) & M1
            accept |= eq & planes[c * wp + k:c * wp + k + 1]
        return valid[:, k:k + 1] & ~accept

    _sweep(ref_ref, out_ref, words_scr, scores_scr, n_locs=n_locs, wp=wp,
           pattern_chars=pattern_chars, word_mismatch=word_mismatch)


def _launch(kernel, ref_words, pat_cols, valid_mask, *, n_locs: int,
            wp: int, interpret: bool, masks: bool):
    """Shared pallas_call for both SWAR kernels (lane-tiled row grid)."""
    R, W = ref_words.shape
    if R % ROW_TILE:
        raise ValueError(f"rows must be padded to a multiple of {ROW_TILE}")
    r_pad = -(-R // LANE_TILE) * LANE_TILE
    if r_pad != R:
        ref_words = jnp.pad(ref_words, ((0, r_pad - R), (0, 0)))
        pat_cols = jnp.pad(pat_cols, ((0, r_pad - R), (0, 0)))
    wc = pat_cols.shape[1]
    n_groups, _, word_rows = _group_geometry(n_locs, wp, W)
    l_pad = n_groups * GROUP_BLOCKS * SHIFTS
    # Row-elementwise body: coarsen the dispatch tile (kernels.tiling) so
    # launch overhead amortizes at scale; output is bit-identical.  Per-row
    # bytes: the lane-padded blocks, both scratch buffers and the
    # transposed score block.
    row_bytes = lane_bytes(W, wc, n_locs) + (word_rows + 2 * l_pad) * 4
    tile = coarse_row_tile(r_pad, LANE_TILE, row_bytes)
    out = pl.pallas_call(
        kernel,
        grid=(r_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile, W), lambda i: (i, 0)),
            pl.BlockSpec((tile, wc), lambda i: (i, 0)),
            pl.BlockSpec(valid_mask.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, n_locs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, n_locs), jnp.int32),
        scratch_shapes=[pltpu.VMEM((word_rows, tile), jnp.uint32),
                        pltpu.VMEM((l_pad, tile), jnp.int32)],
        interpret=interpret,
        name="match_swar_masks" if masks else "match_swar",
    )(ref_words, pat_cols, valid_mask)
    return out if r_pad == R else out[:R]


@functools.partial(jax.jit, static_argnames=("n_locs", "pattern_chars",
                                             "interpret"))
def match_swar(ref_words: jnp.ndarray, pat_words: jnp.ndarray,
               valid_mask: jnp.ndarray, *, n_locs: int, pattern_chars: int,
               interpret: bool = False) -> jnp.ndarray:
    """Packed sliding match: see module docstring for layouts."""
    wp = pat_words.shape[1]
    kernel = functools.partial(_swar_kernel, n_locs=n_locs,
                               pattern_chars=pattern_chars, wp=wp)
    return _launch(kernel, ref_words, pat_words, valid_mask, n_locs=n_locs,
                   wp=wp, interpret=interpret, masks=False)


@functools.partial(jax.jit, static_argnames=("n_locs", "pattern_chars",
                                             "interpret"))
def match_swar_masks(ref_words: jnp.ndarray, pat_planes: jnp.ndarray,
                     valid_mask: jnp.ndarray, *, n_locs: int,
                     pattern_chars: int,
                     interpret: bool = False) -> jnp.ndarray:
    """Accept-set sliding match: see module docstring for layouts."""
    W4 = pat_planes.shape[1]
    if W4 % 4:
        raise ValueError("pat_planes must hold 4 concatenated plane blocks")
    wp = W4 // 4
    kernel = functools.partial(_swar_masks_kernel, n_locs=n_locs,
                               pattern_chars=pattern_chars, wp=wp)
    return _launch(kernel, ref_words, pat_planes, valid_mask,
                   n_locs=n_locs, wp=wp, interpret=interpret, masks=True)
