"""Pipeline parallelism (GPipe over a mesh axis) == sequential semantics.
Runs in a subprocess with 8 fake devices (same pattern as test_sharding)."""

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_pipeline_matches_sequential():
    code = textwrap.dedent("""
        import json, jax, numpy as np
        import jax.numpy as jnp
        def _mk(shape, axes):
            return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        mesh = _mk((4, 2), ("stage", "data"))
        from repro.parallel.pipeline import pipeline_apply

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"]) + p["b"]

        rng = np.random.default_rng(0)
        S, D = 4, 16
        params = {
            "w": jnp.asarray(rng.normal(size=(S, D, D)).astype(np.float32) * 0.3),
            "b": jnp.asarray(rng.normal(size=(S, 1, D)).astype(np.float32) * 0.1),
        }
        x = jnp.asarray(rng.normal(size=(8, D)).astype(np.float32))

        # sequential reference
        y_ref = x
        for s in range(S):
            y_ref = stage_fn(jax.tree.map(lambda t: t[s], params), y_ref)

        y_pipe = pipeline_apply(mesh, "stage", stage_fn, params, x,
                                n_microbatches=4)
        err = float(jnp.max(jnp.abs(y_pipe - y_ref)))
        print(json.dumps({"err": err}))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the child runs on fake CPU devices, never on a chip its parent
    # may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["err"] < 1e-5, res
