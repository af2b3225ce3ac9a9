"""Sharding-rule tests.  These run in a SUBPROCESS with 8 fake devices so
the main pytest process keeps seeing 1 device (the dry-run owns the
512-device configuration; see the system contract in launch/dryrun.py)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the child runs on fake CPU devices, never on a chip its parent
    # may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_param_rules_on_mesh():
    code = textwrap.dedent("""
        import json, jax
        from jax.sharding import PartitionSpec as P
        def _mk(shape, axes):
            return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        mesh = _mk((2, 4), ("data", "model"))
        from repro.parallel.sharding import param_pspec
        out = {}
        # column-parallel default: in->data, out->model
        out["ffn_up"] = str(param_pspec(mesh, "layers/ffn/up/w", (24, 896, 4864)))
        # row-parallel exception: contraction on model
        out["ffn_down"] = str(param_pspec(mesh, "layers/ffn/down/w", (24, 4864, 896)))
        out["attn_wo"] = str(param_pspec(mesh, "layers/attn/wo/w", (24, 1024, 896)))
        # embedding: vocab->model
        out["embed"] = str(param_pspec(mesh, "embed/table", (151936, 896)))
        # norm scale replicated
        out["norm"] = str(param_pspec(mesh, "layers/ln1/norm_scale", (24, 896)))
        # experts: EP on model
        out["experts"] = str(param_pspec(mesh, "layers/moe/experts/up", (61, 384, 7168, 2048)))
        # indivisible dims are dropped, not errors
        out["odd"] = str(param_pspec(mesh, "layers/attn/wq/w", (24, 897, 898)))
        print(json.dumps(out))
    """)
    out = _run_subprocess(code)
    assert "model" in out["ffn_up"] and "data" in out["ffn_up"]
    assert out["ffn_down"].startswith("PartitionSpec(None, 'model'")
    assert out["attn_wo"].startswith("PartitionSpec(None, 'model'")
    assert "'model'" in out["embed"].split(",")[0]
    assert out["norm"] == "PartitionSpec(None, None)" or \
        out["norm"] == "PartitionSpec()"
    assert "'model'" in out["experts"].split(",")[1]
    assert out["odd"] in ("PartitionSpec(None, None, None)",)


def test_train_step_compiles_sharded_and_math_matches():
    """Same train step on 1 device vs an (2,4) mesh: metrics agree."""
    code = textwrap.dedent("""
        import json, jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import RunConfig
        from repro.data import TokenStream
        from repro.train.loop import build_train_step, init_state
        from repro.parallel import sharding as sh
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_config("qwen2-0.5b").reduced()
        run = RunConfig(arch="t", steps=1, lr=1e-3, warmup_steps=0,
                        checkpoint_every=0)
        data = TokenStream(vocab=cfg.vocab, seq_len=32, global_batch=8)
        batch = data.next_batch()
        state = init_state(jax.random.PRNGKey(0), cfg, run)

        # single-device reference
        s1, m1 = build_train_step(cfg, run)(state, batch)

        # sharded: 2-way data, 4-way model
        def _mk(shape, axes):
            return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        mesh = _mk((2, 4), ("data", "model"))
        step_fn, shard_state = build_train_step(cfg, run, mesh=mesh)
        state2 = init_state(jax.random.PRNGKey(0), cfg, run)
        st_sh = shard_state(state2)
        bt_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, P("data", *([None]*(x.ndim-1)))),
            batch)
        with sh.use_mesh(mesh):
            f = jax.jit(step_fn, in_shardings=(st_sh, bt_sh),
                        out_shardings=(st_sh, None))
            s2, m2 = f(jax.device_put(state2, st_sh),
                       jax.device_put(batch, bt_sh))
        print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"])}))
    """)
    out = _run_subprocess(code)
    assert abs(out["l1"] - out["l2"]) < 5e-2, out


def test_cache_sharding_rules():
    code = textwrap.dedent("""
        import json, jax
        def _mk(shape, axes):
            return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        mesh = _mk((2, 4), ("data", "model"))
        from repro.parallel.sharding import cache_pspec
        out = {}
        # kv cache: batch on data, head_dim on model
        out["kv"] = str(cache_pspec(mesh, "k", (24, 8, 512, 2, 64), batch=8))
        # B=1 long-context: seq takes the data axes (SP)
        out["kv_sp"] = str(cache_pspec(mesh, "k", (4, 1, 1024, 8, 128), batch=1))
        print(json.dumps(out))
    """)
    out = _run_subprocess(code)
    assert "'data'" in out["kv"] and "'model'" in out["kv"]
    kv_sp = out["kv_sp"]
    assert kv_sp.index("data") > 0  # seq axis got the data shard
