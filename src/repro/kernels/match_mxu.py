"""One-hot correlation string match on the MXU -- Pallas TPU kernel.

Hardware-codesign variant (DESIGN.md Sec. 2b): score(r, o, q) =
sum_i sum_c ref1h[r, o+i, c] * pat1h[q, i, c] is a sliding contraction.
Where CRAM-PM spends 7 gate steps per character, the systolic array
contracts 128 character-channels of 128+ alignments against Q patterns per
pass.  The trick that makes it MXU-shaped: the im2col window matrix of one
32-character chunk is four stacks of lane-shifted copies of the row's
channel planes,

    A^T[c*32 + j, l] = plane_c[o0 + i0 + 31 - j + l],   j in [0, 32)

and one strided lane rotation (``pltpu.roll`` with a per-sublane stride)
builds a whole 32-row stack, so the alignment tile reduces to
ceil(4P/128) MXU matmuls A @ B^T of (L_TILE, 128) x (128, Q), contracting
the stack axis of both (A^T and B are what the kernel holds).  Every slice is
static and lane-aligned: Mosaic lowers no unaligned dynamic lane window.

Inputs (the public contract):
  ref_flat (R, F4)      bf16 -- one-hot reference rows, char-major flattened
                                (F4 = 4*F_padded), zero padded.
  pat_mat  (P4, Q)      bf16 -- one-hot patterns, (i*4+c, q), zero padded to
                                a multiple of 128 rows.
  out      (R, L_pad, Q) f32 -- scores (caller trims to L).

The wrapper re-lays both operands for the kernel: the reference chunk as
(R, 4, F_lanes) channel planes, the pattern matrix as (Q, P4) in the
stack order above.  Both are small next to the (R, L_pad, Q) output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

L_TILE = 256
K_CHUNK = 128            # = 32 characters * 4 channels
CHARS_PER_CHUNK = K_CHUNK // 4


def _mxu_kernel(ref_ref, pat_ref, out_ref, *, n_chunks: int, n_tiles: int):
    x = ref_ref[0].astype(jnp.float32)              # (4, F_lanes)
    f_lanes = x.shape[1]
    planes = [jnp.broadcast_to(x[c:c + 1], (CHARS_PER_CHUNK, f_lanes))
              for c in range(4)]
    for t in range(n_tiles):
        acc = jnp.zeros((L_TILE, pat_ref.shape[0]), jnp.float32)
        for chunk in range(n_chunks):
            p0 = t * L_TILE + chunk * CHARS_PER_CHUNK
            # Row j of each stack is the plane shifted left by
            # p0 + 31 - j: roll by -(p0 + 31), plus j per sublane.
            shift = -(p0 + CHARS_PER_CHUNK - 1) % f_lanes
            a_t = jnp.concatenate(
                [pltpu.roll(pc, shift, 1, stride=1, stride_axis=0)[:, :L_TILE]
                 for pc in planes], axis=0)
            b = pat_ref[:, chunk * K_CHUNK:(chunk + 1) * K_CHUNK]
            a_t = a_t.astype(b.dtype)
            # (L_TILE, Q) directly, contracting the stack axis of both.
            acc += jax.lax.dot_general(a_t, b, (((0,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        out_ref[0, t * L_TILE:(t + 1) * L_TILE, :] = acc


@functools.partial(jax.jit, static_argnames=("l_pad", "interpret"))
def match_mxu(ref_flat: jnp.ndarray, pat_mat: jnp.ndarray, *, l_pad: int,
              interpret: bool = False) -> jnp.ndarray:
    """ref_flat (R, F4) bf16, pat_mat (P4, Q) bf16 -> (R, l_pad, Q) f32.

    ``l_pad`` (multiple of L_TILE) alignment rows are produced; the caller
    must pad ref_flat so every window read stays in bounds
    (F4 >= (l_pad + P4/4) * 4) -- use ``ops.match_scores`` which handles all
    padding and trimming.
    """
    R, F4 = ref_flat.shape
    P4, Q = pat_mat.shape
    if P4 % K_CHUNK or Q % 128:
        raise ValueError("pattern rows must be padded to 128, Q to 128")
    if l_pad % L_TILE:
        raise ValueError("l_pad must be a multiple of L_TILE")
    n_chunks = P4 // K_CHUNK
    deepest = (l_pad - L_TILE + (n_chunks - 1) * CHARS_PER_CHUNK
               + L_TILE + CHARS_PER_CHUNK) * 4
    if deepest > F4:
        raise ValueError(f"ref_flat too short: need {deepest}, have {F4}")
    F = F4 // 4
    f_lanes = -(-F // 128) * 128
    planes = jnp.pad(ref_flat.reshape(R, F, 4).transpose(0, 2, 1),
                     ((0, 0), (0, 0), (0, f_lanes - F)))
    # (i*4 + c, q) -> (q, chunk*128 + c*32 + (31 - i % 32)): the stack order
    # the kernel's rolled window rows follow.
    pat_t = (pat_mat.reshape(n_chunks, CHARS_PER_CHUNK, 4, Q)[:, ::-1]
             .transpose(3, 0, 2, 1).reshape(Q, P4))
    kernel = functools.partial(_mxu_kernel, n_chunks=n_chunks,
                               n_tiles=l_pad // L_TILE)
    return pl.pallas_call(
        kernel,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, 4, f_lanes), lambda r: (r, 0, 0)),
            pl.BlockSpec((Q, P4), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, l_pad, Q), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, l_pad, Q), jnp.float32),
        interpret=interpret,
        name="match_mxu",
    )(planes, pat_t)
