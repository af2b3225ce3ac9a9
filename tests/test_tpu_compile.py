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
import re

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


def _while_bodies(text):
    """-> {body computation name: its instruction lines} of every while
    loop in compiled HLO text."""
    comps = dict(re.findall(r"\n(%[\w.\-]+) \([^\n]*\{\n(.*?)\n\}", text,
                            re.S))
    return {b: comps[b] for b in re.findall(r"while\(.*?body=(%[\w.\-]+)",
                                            text)}


def test_paged_decode_block_loop_cycles(one_chip):
    """One layer of the served K-step decode loop at the chat cell's
    engine settings (16 rows, 128-token pages, a 512-page pool, 32 pages
    a row, K = 4): the compiler's estimated cycles of one iteration of
    ``paged_decode_blocked``'s block loop, summed over its body.

    With the posit decode by ``ldexp`` the body read 152,516 cycles, of
    which 134,510 were the two dequantization fusions (the 24-layer loop
    reads the same body); with the float32 words assembled from the
    posit fields the dequantization fuses into the two attention dots
    and the body reads 25,746."""
    import dataclasses
    from repro.models import zoo
    from repro.serve.engine import _build_decode_loop
    from repro.serve.paged_kv import PagedKVPool
    b, n_pages, npp, k_steps = 16, 512, 32, 4
    cfg = dataclasses.replace(CFG, n_layers=1)
    params = jax.eval_shape(lambda: zoo.pack_params(
        zoo.init_model(jax.random.PRNGKey(0), cfg),
        PrecisionPolicy.paper_mixed()))
    pool = PagedKVPool.device_specs(cfg, n_pages, PAGE)
    row = _sds((b,), jnp.int32)
    text = _compile(
        _build_decode_loop(cfg, 0.0, k_steps), one_chip, params,
        _sds((b, 1), jnp.int32), row, pool, _sds((b, npp), jnp.int32), row,
        _sds((b,), jnp.bool_), row, row, row, row,
        jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    block = f"u8[{b},{PAGE},{KH},{DH}]"
    bodies = [body for body in _while_bodies(text).values()
              if block in body and "while(" not in body]
    assert len(bodies) == 1
    cycles = sum(int(c) for c in
                 re.findall(r'"estimated_cycles":"(\d+)"', bodies[0]))
    assert 0 < cycles < 152_516 / 3
