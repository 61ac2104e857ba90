"""Sharding rules + HLO analysis unit tests (no big meshes needed: a tiny
forced-host-device mesh exercises the full pjit path)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec

from repro.distributed import hlo_analysis, sharding
from repro.launch.mesh import make_debug_mesh


def tiny_mesh():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs >=4 devices (run under forced host device count)")
    return make_debug_mesh(2, 2)


class TestRules:
    def test_divisible_dims_shard(self):
        mesh = tiny_mesh()
        spec = sharding.spec_for(("vocab", "embed"), (64, 32), mesh)
        assert spec == PartitionSpec("model", "data")

    def test_indivisible_falls_back_to_replication(self):
        mesh = tiny_mesh()
        spec = sharding.spec_for(("heads", None), (3, 7), mesh)
        assert spec == PartitionSpec(None, None)

    def test_axis_used_once(self):
        mesh = tiny_mesh()
        # both dims map to model -> second one must replicate
        spec = sharding.spec_for(("vocab", "ff"), (64, 64), mesh)
        assert spec == PartitionSpec("model", None)

    def test_batch_composite_axis(self):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 devices")
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             devices=devs[:8])
        spec = sharding.spec_for(("batch", None), (8, 4), mesh)
        assert spec == PartitionSpec(("pod", "data"), None)

    def test_partial_fallback_drops_leading_axis(self):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 devices")
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             devices=devs[:8])
        # batch=2 cannot shard over pod*data (4) but can over data (2)
        spec = sharding.spec_for(("batch",), (2,), mesh)
        assert spec == PartitionSpec("data")


class TestHloShapes:
    def test_shape_bytes(self):
        assert hlo_analysis._shape_bytes(
            hlo_analysis._parse_shapes("bf16[4,8]{1,0}")) == 64
        assert hlo_analysis._shape_bytes(
            hlo_analysis._parse_shapes("(f32[2,2]{1,0}, s32[3]{0})")) == 28
        assert hlo_analysis._shape_bytes(
            hlo_analysis._parse_shapes("f32[]")) == 4

    def test_split_rhs(self):
        t = hlo_analysis._split_rhs(
            "bf16[16,128]{1,0} dot(%a, %b), lhs_contracting_dims={1}")
        assert t[0] == "bf16[16,128]{1,0}"
        assert t[1] == "dot"
        assert "lhs_contracting_dims" in t[3]

    def test_split_rhs_tuple_type(self):
        t = hlo_analysis._split_rhs(
            "(f32[2]{0}, s32[]) while(%init), condition=%c, body=%b")
        assert t[1] == "while"


class TestWalker:
    def test_while_trip_multiplication(self):
        """A jitted scan's flops must be multiplied by the trip count."""
        def f(x):
            def body(c, _):
                return c @ c, None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out

        x = jax.ShapeDtypeStruct((64, 64), np.float32)
        text = jax.jit(f).lower(x).compile().as_text()
        cost = hlo_analysis.analyze_hlo(text)
        want = 7 * 2 * 64 * 64 * 64   # 7 iterations of a 64^3 matmul
        assert cost.flops == pytest.approx(want, rel=0.3)

    def test_collectives_detected_under_pjit(self):
        mesh = tiny_mesh()
        from jax.sharding import NamedSharding

        def f(a, b):
            return a @ b

        a = jax.ShapeDtypeStruct((32, 64), np.float32)
        b = jax.ShapeDtypeStruct((64, 16), np.float32)
        sa = NamedSharding(mesh, PartitionSpec("data", "model"))
        sb = NamedSharding(mesh, PartitionSpec("model", None))
        out_s = NamedSharding(mesh, PartitionSpec("data", None))
        comp = jax.jit(f, in_shardings=(sa, sb), out_shardings=out_s) \
            .lower(a, b).compile()
        cost = hlo_analysis.analyze_hlo(comp.as_text())
        # contraction over the model axis must reduce across shards
        assert cost.total_coll_bytes > 0

    def test_dot_flops_partitioned(self):
        mesh = tiny_mesh()
        from jax.sharding import NamedSharding
        a = jax.ShapeDtypeStruct((32, 64), np.float32)
        b = jax.ShapeDtypeStruct((64, 16), np.float32)
        rep = NamedSharding(mesh, PartitionSpec())
        comp = jax.jit(lambda x, y: x @ y, in_shardings=(rep, rep),
                       out_shardings=rep).lower(a, b).compile()
        cost = hlo_analysis.analyze_hlo(comp.as_text())
        assert cost.flops == pytest.approx(2 * 32 * 64 * 16, rel=0.2)


class TestEndToEndTinyMesh:
    def test_elastic_checkpoint_restore_onto_mesh(self, tmp_path):
        """A checkpoint written without any mesh restores sharded onto a
        2x2 mesh (elastic reshard-on-load)."""
        mesh = tiny_mesh()
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(tmp_path, async_write=False)
        tree = {"w": jnp.arange(64.0).reshape(8, 8),
                "b": jnp.ones((4,))}
        mgr.save(3, tree, blocking=True)
        shardings = {
            "w": NamedSharding(mesh, PartitionSpec("data", "model")),
            "b": NamedSharding(mesh, PartitionSpec()),
        }
        restored, step = mgr.restore(tree, shardings=shardings)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding.spec == PartitionSpec("data", "model")
        assert len(restored["w"].sharding.device_set) == 4

    def test_smoke_model_shards_and_runs(self):
        """A reduced arch trains one jitted step on a real 2x2 mesh."""
        mesh = tiny_mesh()
        from repro.configs import get_config
        from repro.distributed import context as dc
        from repro.models import model
        from repro.models.spec import tree_axes
        from repro.optim import adamw
        from repro.runtime import steps as rsteps

        cfg = get_config("llama3.2-1b", smoke=True)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        pspecs = model.param_specs(cfg)
        shard = sharding.shardings_for(tree_axes(pspecs), params, mesh)
        params = jax.tree.map(jax.device_put, params, shard)
        opt_state = adamw.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, cfg.vocab, (4, 16)),
                 "labels": rng.integers(0, cfg.vocab, (4, 16))}
        batch = {k: jax.device_put(v, sharding.batch_sharding(mesh))
                 for k, v in batch.items()}
        step = jax.jit(rsteps.make_train_step(cfg, adamw.OptConfig()))
        with dc.activation_sharding(mesh):
            new_params, _, metrics = step(params, opt_state, batch)
        assert np.isfinite(float(metrics["loss"]))
