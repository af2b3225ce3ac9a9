"""Compile the served kernels and the paged decode step for a TPU v5e at
qwen2-0.5b's published widths, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology: what Mosaic refuses (block shapes off the (8, 128)
tiling, more VMEM than a kernel may hold) fails here, while interpret
mode on the CPU accepts it.  Nothing runs; a pass is not a chip run.
The topology is described inside a fixture, never at import time (only
one process may load the TPU library, and every test worker imports
this file)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import formats as fmt
from repro.core.packing import lanes_per_word
from repro.core.policy import PrecisionPolicy
from repro.kernels.codec import dequant_pallas
from repro.kernels.flash_decode import (flash_decode_pallas,
                                        paged_flash_decode_pallas,
                                        paged_flash_prefill_pallas)
from repro.kernels.quire_dot import quire_dot_pallas
from repro.kernels.rmmec_matmul import default_blocks, rmmec_matmul_pallas

CFG = get_config("qwen2-0.5b")                   # published widths
KH, DH = CFG.n_kv_heads, CFG.resolved_head_dim
G = CFG.n_heads // KH
B, PAGE, MAX_LEN = 8, 128, 1024
NP = MAX_LEN // PAGE
POOL = B * NP + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a persistent-cache entry compiled for a described chip cannot be
    # read back without one: keep these compiles out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    """Lower + compile ``fn`` for the described chip; -> HLO text."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("spec", [fmt.FP4, fmt.POSIT8, fmt.POSIT16],
                         ids=lambda s: s.name)
def test_rmmec_matmul_compiles(one_chip, spec):
    """The FFN up projection (d_model x d_ff), group-32 scales."""
    bm, bk, bn = default_blocks(spec)
    per = lanes_per_word(spec.bits)
    kp = -(-CFG.d_model // bk) * bk
    np_ = -(-CFG.d_ff // bn) * bn
    text = _compile(
        lambda x, w, s, m: rmmec_matmul_pallas(
            x, w, s, m, spec=spec, bm=bm, bk=bk, bn=bn, group=32,
            interpret=False),
        one_chip, _sds((bm, kp), jnp.bfloat16),
        _sds((kp, np_ // per), jnp.uint32), _sds((kp // 32, np_), jnp.float32),
        _sds((kp // bk, np_ // bn), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec", [fmt.FP4, fmt.POSIT8, fmt.POSIT16],
                         ids=lambda s: s.name)
def test_dequant_compiles(one_chip, spec):
    per = lanes_per_word(spec.bits)
    bk, bn = 256, 128 * per
    k, n = 1024, -(-CFG.d_ff // bn) * bn
    text = _compile(
        lambda w, s: dequant_pallas(w, s, spec=spec, bk=bk, bn=bn, group=32,
                                    interpret=False),
        one_chip, _sds((k, n // per), jnp.uint32),
        _sds((k // 32, n), jnp.float32))
    assert "tpu_custom_call" in text


def test_quire_dot_compiles(one_chip):
    text = _compile(lambda a, b: quire_dot_pallas(a, b, interpret=False),
                    one_chip, _sds((8, 1024), jnp.int32),
                    _sds((8, 1024), jnp.int32))
    assert "tpu_custom_call" in text


def _kv(lead):
    return (_sds(lead + (KH, DH), jnp.uint8), _sds(lead + (KH, 1), jnp.bfloat16),
            _sds(lead + (KH, DH), jnp.uint8), _sds(lead + (KH, 1), jnp.bfloat16))


def test_flash_decode_compiles(one_chip):
    text = _compile(
        lambda q, kc, ks, vc, vs, pos: flash_decode_pallas(
            q, kc, ks, vc, vs, pos, blk=PAGE, interpret=False),
        one_chip, _sds((B, KH, G, DH), jnp.bfloat16), *_kv((B, MAX_LEN)),
        _sds((), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_flash_decode_compiles(one_chip):
    text = _compile(
        lambda q, kc, ks, vc, vs, pt, pos: paged_flash_decode_pallas(
            q, kc, ks, vc, vs, pt, pos, interpret=False),
        one_chip, _sds((B, KH, G, DH), jnp.bfloat16), *_kv((POOL, PAGE)),
        _sds((B, NP), jnp.int32), _sds((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_flash_prefill_compiles(one_chip):
    text = _compile(
        lambda q, kc, ks, vc, vs, pt, st: paged_flash_prefill_pallas(
            q, kc, ks, vc, vs, pt, st, interpret=False),
        one_chip, _sds((1, PAGE, KH, G, DH), jnp.bfloat16),
        *_kv((POOL, PAGE)), _sds((1, NP), jnp.int32), _sds((1,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_decode_step_compiles(one_chip):
    """The served decode step at full width: paper_mixed packed weights,
    posit8 paged KV, the default blocked (pure-XLA) attention."""
    from repro.launch.specs import paged_cache_specs
    from repro.models import zoo
    from repro.serve.engine import build_serve_step
    policy = PrecisionPolicy.paper_mixed()
    params = jax.eval_shape(lambda: zoo.pack_params(
        zoo.init_model(jax.random.PRNGKey(0), CFG), policy))
    cache = paged_cache_specs(CFG, B, MAX_LEN, page_size=PAGE)
    _compile(build_serve_step(CFG), one_chip, params,
             _sds((B, 1), jnp.int32), cache, _sds((), jnp.int32))
