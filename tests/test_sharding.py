"""Logical->mesh sharding rules incl. divisibility fallback + a real
8-device lower/compile round (subprocess with forced device count)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.common.sharding import MeshRules

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_rules():
    r = MeshRules.create(MESH)
    assert r.pspec(("vocab", "embed"), (32000, 2048)) == P("model")
    assert r.pspec(("embed", "mlp"), (2048, 5632)) == P(None, "model")
    assert r.pspec(("batch", None), (256, 4096)) == P("data")


def test_multipod_batch_axes():
    r = MeshRules.create(MESH3)
    assert r.pspec(("batch", None), (256, 4096)) == P(("pod", "data"))


def test_divisibility_fallback_kv_heads():
    r = MeshRules.create(MESH)
    # kv=4 not divisible by model=16 -> replicate
    assert r.pspec(("embed", "kv_heads", None), (2048, 4, 64)) == P()
    # q heads 32 divisible -> shard
    assert r.pspec(("embed", "heads", None), (2048, 32, 64)) == P(None, "model")


def test_divisibility_fallback_odd_vocab():
    r = MeshRules.create(MESH)
    assert r.pspec(("vocab", "embed"), (51865, 512)) == P()  # whisper vocab


def test_batch_fallback_for_batch_1():
    r = MeshRules.create(MESH3)
    assert r.pspec(("batch", None), (1, 1)) == P()


def test_no_axis_reuse_within_spec():
    r = MeshRules.create(MESH, overrides={"seq": ("model",)})
    s = r.pspec(("heads", "seq"), (32, 4096))
    # model used by heads; seq falls back to replication, never reused
    assert s == P("model")


def test_overrides_ep_mode():
    r = MeshRules.create(MESH, overrides={"expert": ("data",)})
    assert r.pspec(("expert", "embed", "mlp"), (256, 64, 2048)) == \
        P("data", None, "model")


@pytest.mark.slow
def test_mini_dryrun_8_devices(tmp_path):
    """Real lower+compile of the smoke model on 8 forced host devices:
    proves the sharding config is coherent, end to end, in miniature."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_model
        from repro.configs.base import TrainConfig, ShapeConfig
        from repro.common.pytree import abstract
        from repro.train.train_step import make_train_step
        from repro.train.optimizer import init_opt_state, opt_state_specs

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        m = smoke_model("gemma2-9b")
        m.mesh = mesh
        defs = m.param_defs()
        p_abs = abstract(defs)
        specs = m.param_specs()
        def shard(t):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
        o_abs = jax.eval_shape(lambda p: init_opt_state(p, keep_master=False), p_abs)
        o_specs = opt_state_specs(specs, defs, mesh, keep_master=False)
        tcfg = TrainConfig(microbatch=4)
        step = make_train_step(m, tcfg)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        b_specs = {"tokens": P("data", None)}
        with mesh:
            fn = jax.jit(step, in_shardings=(shard(specs), shard(o_specs), shard(b_specs)),
                         out_shardings=(shard(specs), shard(o_specs), None))
            compiled = fn.lower(p_abs, o_abs, batch).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):   # jax<=0.4.x: one dict per device
            ca = ca[0]
        print(json.dumps({"flops": ca.get("flops", 0.0),
                          "n_devices": mesh.devices.size}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert out["flops"] > 0
