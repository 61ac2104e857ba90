"""Bulk bitwise ops -- Pallas TPU kernel (Fig. 11 gate-level analogue + RC4).

One kernel, op selected statically; operands stream HBM->VMEM tile-wise and
the result is produced in-place in VMEM -- the TPU rendition of "computation
happens where the data sits" (no intermediate ever returns to HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_TILE = 256
OPS = ("NOT", "OR", "NAND", "XOR", "AND", "NOR")


def _bitwise_kernel(a_ref, b_ref, out_ref, *, op: str):
    a = a_ref[...]
    b = b_ref[...]
    if op == "NOT":
        r = ~a
    elif op == "OR":
        r = a | b
    elif op == "AND":
        r = a & b
    elif op == "NAND":
        r = ~(a & b)
    elif op == "NOR":
        r = ~(a | b)
    elif op == "XOR":
        r = a ^ b
    else:
        raise ValueError(op)
    out_ref[...] = r


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def bitwise(op: str, a: jnp.ndarray, b: jnp.ndarray | None = None,
            *, interpret: bool = False) -> jnp.ndarray:
    """(N, W) uint32 elementwise bulk op; N % N_TILE == 0."""
    if op not in OPS:
        raise ValueError(op)
    if b is None:
        b = a  # unary NOT ignores b
    N, W = a.shape
    if N % N_TILE:
        raise ValueError(f"rows must be padded to a multiple of {N_TILE}")
    kernel = functools.partial(_bitwise_kernel, op=op)
    return pl.pallas_call(
        kernel,
        grid=(N // N_TILE,),
        in_specs=[pl.BlockSpec((N_TILE, W), lambda i: (i, 0)),
                  pl.BlockSpec((N_TILE, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((N_TILE, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, W), jnp.uint32),
        interpret=interpret,
        name="bitwise",
    )(a, b)
