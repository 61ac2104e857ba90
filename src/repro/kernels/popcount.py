"""Bulk popcount -- Pallas TPU kernel (BC benchmark / Phase-2 analogue).

The CRAM-PM adder reduction tree (Fig. 4b) becomes branch-free SWAR
arithmetic over uint32 lanes; one VPU op pops 8x128 words.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

M1 = np.uint32(0x55555555)
M2 = np.uint32(0x33333333)
M4 = np.uint32(0x0F0F0F0F)
MUL = np.uint32(0x01010101)

N_TILE = 256


def popcount_words(v: jnp.ndarray) -> jnp.ndarray:
    """Branch-free SWAR popcount per uint32 word (int32 out).

    Pure jnp, so it inlines into Pallas kernel bodies (this module's bulk
    kernel, ``filter_qgram``) as well as ordinary jitted code.
    """
    v = v - ((v >> jnp.uint32(1)) & M1)
    v = (v & M2) + ((v >> jnp.uint32(2)) & M2)
    v = (v + (v >> jnp.uint32(4))) & M4
    return ((v * MUL) >> jnp.uint32(24)).astype(jnp.int32)


def _popcount_kernel(x_ref, out_ref):
    out_ref[...] = popcount_words(x_ref[...]).sum(axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def popcount(words: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """(N, W) uint32 -> (N, 1) int32 per-row popcount. N % N_TILE == 0."""
    N, W = words.shape
    if N % N_TILE:
        raise ValueError(f"rows must be padded to a multiple of {N_TILE}")
    return pl.pallas_call(
        _popcount_kernel,
        grid=(N // N_TILE,),
        in_specs=[pl.BlockSpec((N_TILE, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((N_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.int32),
        interpret=interpret,
        name="popcount",
    )(words)
