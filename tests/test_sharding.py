"""Sharding rules: divisibility fallbacks, mesh-axis conflicts, cache
heuristics, collective parser on a 1-device mesh + synthetic HLO, and the
trainer on two virtual devices (subprocess)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_dev_mesh
from repro.launch.sharding import cache_shardings, spec_for
from repro.roofline.hlo_parse import collective_bytes, _shape_bytes


class FakeMesh:
    """Duck-typed mesh for spec_for (only shape/axis_names are read)."""

    def __init__(self, shape):
        self.shape = shape

    @property
    def axis_names(self):
        return tuple(self.shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_tp_and_fsdp_assignment():
    assert spec_for(("embed", "mlp"), (1024, 4096), MESH) == P("data", "model")
    assert spec_for(("embed", "heads", None), (1024, 32, 128), MESH) == \
        P("data", "model")
    assert spec_for(("vocab", "embed"), (49152, 576), MESH) == \
        P("model", "data")


def test_divisibility_fallback():
    # 9 heads don't divide 16 -> replicated head dim
    assert spec_for(("embed", "heads", None), (576, 9, 64), MESH) == P("data")
    # odd vocab falls back
    assert spec_for(("vocab", "embed"), (50281, 1024), MESH) == P(None, "data")


def test_mesh_axis_used_once():
    # expert takes "model"; mlp must NOT also get it
    s = spec_for(("expert", "embed", "mlp"), (64, 1024, 2048), MESH)
    assert s == P("model", "data")


def test_multipod_fsdp_expansion():
    s = spec_for(("embed", "mlp"), (1024, 4096), MESH3)
    assert s == P(("pod", "data"), "model")
    # dim divisible by data but not pod*data -> prefix fallback
    s2 = spec_for(("embed", "mlp"), (16, 4096), MESH3)
    assert s2 == P("data", "model")


def test_one_dim_params_replicated():
    assert spec_for(("embed",), (1024,), MESH) == P()


def test_cache_heuristics_batch_vs_sequence():
    mesh = make_dev_mesh()  # 1x1, real mesh for NamedSharding
    kv = {"k": jax.ShapeDtypeStruct((128, 1024, 8, 128), jnp.bfloat16)}
    sh = cache_shardings(kv, mesh)["k"]
    assert sh.spec[0] is not None  # batch sharded
    kv1 = {"k": jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16)}
    sh1 = cache_shardings(kv1, mesh)["k"]
    # batch=1: sequence dim takes the dp axes
    assert sh1.spec[0] is None and sh1.spec[1] is not None


def test_hlo_collective_parser_trip_counts():
    hlo = """
HloModule test

%body_inner (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %ar = f32[64]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
}

%cond_inner (p: (s32[], f32[64])) -> pred[] {
  %c = s32[] constant(5)
}

ENTRY %main (a: f32[64]) -> f32[64] {
  %w = (s32[], f32[64]) while(%t), condition=%cond_inner, body=%body_inner, backend_config={"known_trip_count":{"n":"5"}}
  %ag = f32[128]{0} all-gather(%a), replica_groups=[2,8]<=[16], dimensions={0}
}
"""
    out = collective_bytes(hlo)
    # all-reduce: 2 * 256B * 3/4 = 384B per trip, 5 trips
    np.testing.assert_allclose(out["all-reduce"], 5 * 2 * 256 * 3 / 4)
    # all-gather: 512B * 7/8
    np.testing.assert_allclose(out["all-gather"], 512 * 7 / 8)


def test_shape_bytes_tuple():
    assert _shape_bytes("(f32[2,2], bf16[4])") == 16 + 8


def test_dev_mesh_uses_every_device_given():
    devs = jax.devices()
    mesh = make_dev_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == len(devs) and mesh.shape["model"] == 1
    assert make_dev_mesh(devs[:1]).devices.size == 1
    with pytest.raises(ValueError, match="device"):
        make_dev_mesh([])


def test_trainer_on_two_devices_matches_one():
    """Trainer on a (2, 1) mesh — batches placed over "data", flash kernels
    per shard under shard_map, slabs row-sharded — tracks the one-device
    trainer on the same global batch. Subprocess: the device count must
    be forced before jax initializes."""
    import os
    import re
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import dataclasses
        import jax, numpy as np
        assert jax.device_count() == 2
        from repro.configs import smollm_135m
        from repro.core.precision import TriAccelConfig
        from repro.launch.mesh import make_dev_mesh
        from repro.train.task import LMTask
        from repro.train.trainer import Trainer, TrainerConfig
        cfg = smollm_135m.reduced_config()
        st = cfg.stack
        cfg = dataclasses.replace(cfg, stack=dataclasses.replace(
            st, attn=dataclasses.replace(st.attn, impl="flash")))
        losses = []
        for devs, mb in ((jax.devices(), 1), (jax.devices()[:1], 2)):
            tac = TriAccelConfig(ladder="tpu", t_ctrl=2,
                                 enable_curvature=False, enable_batch=False)
            tcfg = TrainerConfig(seq_len=256, rungs=(mb,), log_every=1,
                                 warmup_steps=1)
            tr = Trainer(LMTask(cfg), tac, tcfg, mesh=make_dev_mesh(devs))
            assert tr.slab_shards == len(devs)
            tr.warm_rungs()
            tr.run(3)
            assert tr.compile_count == 1
            losses.append([m["loss"] for m in tr.metrics_log])
        np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)
        print("TWO_DEVICE_TRAINER_OK")
    """)
    inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
    env = dict(os.environ,
               XLA_FLAGS=inherited
               + " --xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TWO_DEVICE_TRAINER_OK" in out.stdout
