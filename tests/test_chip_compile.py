"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel at deployment widths (F = 256
characters, P = 32 and 128, row counts past the coarse dispatch tile) and
compiles it with the TPU compiler for a ``v5e:2x2`` topology that is
described, not attached.  That catches what interpret mode cannot --
unaligned slices, block shapes the (8, 128) rule refuses, VMEM overruns --
at no chip time.  A compile that passes is not a chip run.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this
file.  All such tests stay in this one file for the same reason.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import filter_qgram as fq
from repro.kernels import match_mxu as mxu
from repro.kernels import match_swar as swar
from repro.match.index import DEFAULT_BITS
from repro.match.planner import _mxu_geometry, _swar_geometry

F = 256
WB = DEFAULT_BITS // 32           # q-gram signature words per row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 -- any refusal skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-chip compile cannot be read back from the persistent
    cache without the chip; keep it out of any cache the run has on."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("P", [32, 128])
@pytest.mark.parametrize("R", [8192, 1 << 17])
@pytest.mark.parametrize("kernel", ["match_swar", "match_swar_masks"])
def test_swar_compiles(one_chip, kernel, R, P):
    L = F - P + 1
    wp, need = _swar_geometry(P, L)
    cols = wp if kernel == "match_swar" else 4 * wp
    fn = getattr(swar, kernel)
    compile_for(one_chip,
                lambda w, p, m: fn(w, p, m, n_locs=L, pattern_chars=P),
                ((R, need), jnp.uint32), ((R, cols), jnp.uint32),
                ((1, wp), jnp.uint32))


def test_swar_unaligned_rows_compile(one_chip):
    """A filter-survivor or rows= gather chunk: rows padded to 8, not 128."""
    P = 32
    L = F - P + 1
    wp, need = _swar_geometry(P, L)
    compile_for(one_chip,
                lambda w, p, m: swar.match_swar(w, p, m, n_locs=L,
                                                pattern_chars=P),
                ((1000, need), jnp.uint32), ((1000, wp), jnp.uint32),
                ((1, wp), jnp.uint32))


@pytest.mark.parametrize("P,Q", [(32, 64), (128, 128)])
def test_mxu_compiles(one_chip, P, Q):
    L = F - P + 1
    l_pad, p_chars, q_pad, f_chars = _mxu_geometry(P, L, Q)
    f4 = max(f_chars, F) * 4
    compile_for(one_chip,
                lambda r, p: mxu.match_mxu(r, p, l_pad=l_pad),
                ((1 << 14, f4), jnp.bfloat16),
                ((p_chars * 4, q_pad), jnp.bfloat16))


@pytest.mark.parametrize("R", [1 << 14, 1 << 20, fq.padded_rows(1585712)])
def test_filter_qgram_compiles(one_chip, R):
    Q = 16
    compile_for(one_chip, fq.filter_qgram,
                ((WB, R), jnp.uint32), ((Q, WB, 1), jnp.uint32),
                ((Q, 1, 1), jnp.int32))


@pytest.mark.parametrize("Q", [4096, 65536])
def test_filter_qgram_compiles_large_groups(one_chip, Q):
    # Patterns stream through VMEM in fixed blocks, so any group size
    # compiles at the cell's row count (a group resident whole would need
    # 4 KiB of VMEM per pattern and operand: 256 MiB at Q = 65536).
    R = fq.padded_rows(1585712)
    compile_for(one_chip, fq.filter_qgram,
                ((WB, R), jnp.uint32), ((Q, WB, 1), jnp.uint32),
                ((Q, 1, 1), jnp.int32))


@pytest.mark.parametrize("Q,D", [(1024, 64), (4096, 8)])
def test_bank_prefilter_compiles(one_chip, Q, D):
    compile_for(one_chip, fq.bank_prefilter,
                ((Q, WB), jnp.uint32), ((D, WB), jnp.uint32),
                ((Q, 1), jnp.int32))
