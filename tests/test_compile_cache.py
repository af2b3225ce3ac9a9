"""Placement of JAX's persistent compilation cache by the entry points
(``repro.launch.cache``).  Each case runs in a fresh CPU-only child, since
JAX reads ``JAX_COMPILATION_CACHE_DIR`` when it is imported."""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = textwrap.dedent("""
    import json, os, jax
    from repro.launch.cache import setup_compile_cache
    path = setup_compile_cache()
    if os.environ.get("COMPILE_ONE"):
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
    print(json.dumps({"path": path,
                      "config": jax.config.jax_compilation_cache_dir}))
""")


def _run(**env_extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_cache_goes_where_the_environment_says(tmp_path):
    where = str(tmp_path / "cache")
    got = _run(JAX_COMPILATION_CACHE_DIR=where, COMPILE_ONE="1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert got == {"path": where, "config": where}
    assert os.listdir(where)                 # the compile landed there


def test_cache_defaults_to_a_fixed_ignored_directory():
    want = os.path.join(REPO, ".jax_cache")
    assert _run() == {"path": want, "config": want}
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
