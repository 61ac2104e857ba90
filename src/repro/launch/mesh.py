"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

A function, not a module-level constant, so importing this module never
touches jax device state.

Every mesh here has ``Auto`` axes: the sharded match stack places arrays
with explicit ``NamedSharding``s and ``shard_map``, and lets XLA propagate
the rest.  ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
sharding-typed ops (e.g. a jitted ``jnp.take`` over a row-sharded array)
demand an ``out_sharding`` at every call site.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) for two
    pods.  Uses the first prod(shape) available devices so a 512-way
    host-platform dry-run can build both meshes."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} -- "
            "run under launch/dryrun.py which forces 512 host devices")
    return _auto_mesh(shape, axes, devices[:n])


def make_debug_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (requires forced host devices)."""
    shape = ((2, n_data, n_model) if multi_pod else (n_data, n_model))
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    return _auto_mesh(shape, axes, jax.devices()[:n])


def make_row_mesh(n_shards: int):
    """1-D ``("data",)`` mesh for row-sharded match engines.

    The match stack shards corpus rows over the mesh's row axes (logical
    axis ``rows`` -> ``data`` under the default rules, DESIGN.md
    Sec. 3h); a pure data mesh gives it exactly ``n_shards`` row shards
    with no idle model axis.
    """
    devices = jax.devices()
    if len(devices) < n_shards:
        raise RuntimeError(
            f"need {n_shards} devices for a {n_shards}-shard row mesh, "
            f"have {len(devices)} -- force host devices via XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N")
    return _auto_mesh((n_shards,), ("data",), devices[:n_shards])
