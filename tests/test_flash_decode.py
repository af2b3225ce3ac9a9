"""KV decode plane: fused flash-decode kernel vs oracle, the XLA blocked
fallback, quantized-vs-bf16 decode parity, and the quantized_kv=True
serving path end-to-end."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import PrecisionPolicy
from repro.kernels import ref
from repro.kernels.flash_decode import default_kv_block, flash_decode_pallas
from repro.models import attention as A
from repro.models import transformer as T
from repro.models import zoo
from repro.serve.engine import (TOKEN_GAP_TOL, ServeEngine,
                                build_token_gap_step)

CFG = get_config("qwen2-0.5b").reduced()
RNG = np.random.default_rng(0)


def _quantized_cache(b=2, t=64, kh=2, dh=32, group=None):
    k = jnp.asarray(RNG.normal(size=(b, t, kh, dh)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, t, kh, dh)).astype(np.float32))
    kc, ks = A.quantize_kv(k, group)
    vc, vs = A.quantize_kv(v, group)
    return {"k_codes": kc, "k_scale": ks, "v_codes": vc, "v_scale": vs}


# ---------------------------------------------------------------------------
# kernel / fallback vs the naive oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pos", [0, 5, 31, 63])
@pytest.mark.parametrize("softcap", [0.0, 20.0])
@pytest.mark.parametrize("group", [None, 8])
def test_flash_kernel_vs_oracle(pos, softcap, group):
    cache = _quantized_cache(group=group)
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 32)).astype(np.float32))
    got = flash_decode_pallas(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], jnp.int32(pos), blk=16, softcap=softcap,
        interpret=True)
    want = ref.flash_decode_ref(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], pos, softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [0, 7, 40, 63])
@pytest.mark.parametrize("group", [None, 16])
def test_blocked_xla_vs_oracle(pos, group):
    cache = _quantized_cache(group=group)
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 32)).astype(np.float32))
    got = jax.jit(A.decode_quantized_blocks)(q, cache, jnp.int32(pos))
    want = ref.flash_decode_ref(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_matches_blocked_with_softcap():
    """Both live paths agree with each other (same math, different
    schedule) including the softcap nonlinearity."""
    cache = _quantized_cache()
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 32)).astype(np.float32))
    a = flash_decode_pallas(q, cache["k_codes"], cache["k_scale"],
                            cache["v_codes"], cache["v_scale"],
                            jnp.int32(41), softcap=30.0, interpret=True)
    b = A.decode_quantized_blocks(q, cache, jnp.int32(41), softcap=30.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ragged batches: the per-row pad operand
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("softcap", [0.0, 20.0])
@pytest.mark.parametrize("group", [None, 8])
def test_flash_kernel_pad_vs_oracle(softcap, group):
    """Per-row left-pad widths mask cache slots below pad[b] inside the
    kernel's online softmax -- ragged static batches need no fallback."""
    cache = _quantized_cache(group=group)
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 32)).astype(np.float32))
    pad = jnp.asarray([3, 17], jnp.int32)
    got = flash_decode_pallas(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], jnp.int32(41), pad=pad, blk=16,
        softcap=softcap, interpret=True)
    want = ref.flash_decode_ref(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], 41, softcap, pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [5, 40, 63])
def test_flash_kernel_pad_matches_blocked(pos):
    """Kernel and XLA fallback agree on ragged batches, including a row
    whose pad covers whole KV blocks (blocks fully below pad mask to
    exact zeros) and a row with no padding at all."""
    cache = _quantized_cache()
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 32)).astype(np.float32))
    pad = jnp.asarray([0, min(pos, 33)], jnp.int32)
    a = flash_decode_pallas(q, cache["k_codes"], cache["k_scale"],
                            cache["v_codes"], cache["v_scale"],
                            jnp.int32(pos), pad=pad, blk=16,
                            interpret=True)
    b = A.decode_quantized_blocks(q, cache, jnp.int32(pos), blk=16,
                                  pad=pad)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("softcap", [0.0, 20.0])
@pytest.mark.parametrize("group", [None, 8])
def test_flash_kernel_pad_skip_whole_blocks(softcap, group):
    """Pads covering WHOLE KV blocks: the index map now clamps those
    blocks onto the first live one (they are never fetched) and the
    compute gate skips them -- the output must still match the oracle's
    mask-everything path, including a pad-free row and a row whose pad
    is a multiple of the block size."""
    cache = _quantized_cache(b=3)
    q = jnp.asarray(RNG.normal(size=(3, 2, 2, 32)).astype(np.float32))
    pad = jnp.asarray([0, 16, 48], jnp.int32)    # 0, 1 and 3 whole blocks
    got = flash_decode_pallas(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], jnp.int32(55), pad=pad, blk=16,
        softcap=softcap, interpret=True)
    want = ref.flash_decode_ref(
        q, cache["k_codes"], cache["k_scale"], cache["v_codes"],
        cache["v_scale"], 55, softcap, pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [33, 47, 63])
def test_flash_kernel_pad_skip_matches_blocked(pos):
    """Kernel (skip-below-pad index map) vs the XLA blocked fallback
    (which still masks below-pad slots): same result on rows whose pad
    skips whole blocks, lands mid-block, or equals pos (a single live
    slot -- the smallest legal window)."""
    cache = _quantized_cache(b=3)
    q = jnp.asarray(RNG.normal(size=(3, 2, 2, 32)).astype(np.float32))
    pad = jnp.asarray([32, 19, pos], jnp.int32)
    a = flash_decode_pallas(q, cache["k_codes"], cache["k_scale"],
                            cache["v_codes"], cache["v_scale"],
                            jnp.int32(pos), pad=pad, blk=16,
                            interpret=True)
    b = A.decode_quantized_blocks(q, cache, jnp.int32(pos), blk=16,
                                  pad=pad)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_engine_ragged_generate_flash_matches_blocked():
    """lengths= (ragged static batch) no longer forces the blocked
    fallback under decode_impl='flash': both paths emit the same
    tokens."""
    cfg = dataclasses.replace(CFG, decode_impl="flash")
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    toks = np.zeros((3, 9), np.int32)
    lens = np.asarray([4, 9, 6])
    rng = np.random.default_rng(5)
    for i, ln in enumerate(lens):
        toks[i, 9 - ln:] = rng.integers(0, cfg.vocab, (ln,))
    toks = jnp.asarray(toks)
    out_fl = ServeEngine(cfg, params, max_len=32, quantized_kv=True) \
        .generate(toks, steps=5, lengths=lens)
    out_bl = ServeEngine(CFG, params, max_len=32, quantized_kv=True) \
        .generate(toks, steps=5, lengths=lens)
    np.testing.assert_array_equal(out_fl, out_bl)


# ---------------------------------------------------------------------------
# unified scale layout (quant.group_scales along Dh)
# ---------------------------------------------------------------------------

def test_quantize_kv_group_layout():
    k = jnp.asarray(RNG.normal(size=(2, 8, 2, 32)).astype(np.float32))
    codes, s = A.quantize_kv(k)                     # per-(token, head)
    assert codes.shape == (2, 8, 2, 32) and s.shape == (2, 8, 2, 1)
    codes_g, s_g = A.quantize_kv(k, group_size=8)   # Dh-grouped
    assert s_g.shape == (2, 8, 2, 4)
    # group >= Dh (and non-divisors) degenerate to per-(token, head)
    assert A.quantize_kv(k, group_size=32)[1].shape == (2, 8, 2, 1)
    assert A.quantize_kv(k, group_size=7)[1].shape == (2, 8, 2, 1)
    # both layouts round-trip at posit8-level error
    err = jnp.mean(jnp.abs(A.dequantize_kv(codes, s, jnp.float32) - k))
    err_g = jnp.mean(jnp.abs(A.dequantize_kv(codes_g, s_g, jnp.float32) - k))
    assert float(err) < 0.1 and float(err_g) < 0.1


def test_kv_block_divides():
    for ml in (32, 64, 96, 128, 256, 2048):
        blk = default_kv_block(ml)
        assert ml % blk == 0 and blk <= 128


# ---------------------------------------------------------------------------
# quantized vs bf16 decode parity over many steps
# ---------------------------------------------------------------------------

def test_quantized_kv_parity_32_steps():
    """Greedy decode with a posit8 cache stays within posit8 tolerance of
    the bf16 cache for >= 32 consecutive steps (same token stream)."""
    params = T.lm_init(jax.random.PRNGKey(0), CFG)
    B, steps = 2, 33
    cache_f = T.init_cache(CFG, B, steps + 1, quantized_kv=False)
    cache_q = T.init_cache(CFG, B, steps + 1, quantized_kv=True)
    tok = jnp.asarray(RNG.integers(0, CFG.vocab, (B, 1)), jnp.int32)
    step = jax.jit(lambda p, t, c, i: zoo.decode_model(p, t, CFG, c, i))
    worst = 0.0
    for i in range(steps):
        lf, cache_f = step(params, tok, cache_f, jnp.int32(i))
        lq, cache_q = step(params, tok, cache_q, jnp.int32(i))
        pf = jax.nn.softmax(lf.astype(jnp.float32), -1)
        pq = jax.nn.softmax(lq.astype(jnp.float32), -1)
        worst = max(worst, float(jnp.max(jnp.abs(pf - pq))))
        tok = jnp.argmax(lf[:, -1], -1)[:, None].astype(jnp.int32)
    assert worst < 0.05, worst


# ---------------------------------------------------------------------------
# quantized_kv=True end-to-end serving
# ---------------------------------------------------------------------------

def test_engine_generate_quantized_kv():
    params = T.lm_init(jax.random.PRNGKey(0), CFG)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, CFG.vocab, (2, 8)), jnp.int32)
    eng_q = ServeEngine(CFG, params, max_len=64, quantized_kv=True)
    # prefill really returns codes, not a bf16 cache
    _, cache = eng_q._prefill(eng_q.params, {"tokens": toks})
    flat = jax.tree_util.tree_leaves_with_path(cache)
    assert any(x.dtype == jnp.uint8 for _, x in flat)
    assert not any(p[-1].key in ("k", "v") for p, _ in flat
                   if hasattr(p[-1], "key"))
    out_q = eng_q.generate(toks, steps=6)
    assert out_q.shape == (2, 14) and np.isfinite(out_q).all()
    # posit8 KV is near-lossless: teacher-forced through a bf16-KV
    # prefill, every generated token's logit is within TOKEN_GAP_TOL row
    # deviations of its row's max (ties between near-equal logits may
    # flip the argmax, which a token-agreement check would count)
    gap, spread = jax.jit(build_token_gap_step(CFG))(
        eng_q.params, jnp.asarray(out_q))
    gen = slice(toks.shape[1] - 1, out_q.shape[1] - 1)
    rel = np.asarray(gap[:, gen] / spread[:, gen])
    assert rel.max() <= TOKEN_GAP_TOL, rel


def test_engine_generate_quantized_kv_grouped_policy():
    """PrecisionPolicy.group_size grids the KV plane like the weights."""
    params = T.lm_init(jax.random.PRNGKey(0), CFG)
    pol = PrecisionPolicy(rules=[], default="posit8_0", group_size=16)
    eng = ServeEngine(CFG, params, max_len=48, quantized_kv=True, policy=pol)
    _, cache = eng._prefill(eng.params, {"tokens": jnp.zeros((1, 4),
                                                             jnp.int32)})
    scales = [x for p, x in jax.tree_util.tree_leaves_with_path(cache)
              if hasattr(p[-1], "key") and p[-1].key == "k_scale"]
    assert scales and all(s.shape[-1] == 2 for s in scales)  # Dh=32 / 16
    out = eng.generate(jnp.zeros((1, 4), jnp.int32), steps=4)
    assert out.shape == (1, 8) and np.isfinite(out).all()


def test_engine_generate_flash_impl():
    """cfg.decode_impl='flash' serves through the fused Pallas kernel."""
    cfg = dataclasses.replace(CFG, decode_impl="flash")
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, (2, 6)), jnp.int32)
    eng_fl = ServeEngine(cfg, params, max_len=32, quantized_kv=True)
    eng_bl = ServeEngine(CFG, params, max_len=32, quantized_kv=True)
    out_fl = eng_fl.generate(toks, steps=4)
    out_bl = eng_bl.generate(toks, steps=4)
    np.testing.assert_array_equal(out_fl, out_bl)


def test_engine_generate_quantized_kv_hybrid():
    """Hybrid (attn + mamba) caches quantize their attention sub-blocks
    only; mamba states pass through and decode still works."""
    cfg = get_config("jamba-v0.1-52b").reduced()
    params = T.lm_init(jax.random.PRNGKey(1), cfg)
    toks = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab, (2, 4)), jnp.int32)
    out = ServeEngine(cfg, params, max_len=32,
                      quantized_kv=True).generate(toks, steps=3)
    assert out.shape == (2, 7) and np.isfinite(out).all()


# ---------------------------------------------------------------------------
# structure-aware cache padding
# ---------------------------------------------------------------------------

def test_pad_cache_structure_aware():
    params = T.lm_init(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, max_len=40, quantized_kv=True)
    _, cache = eng._prefill(eng.params, {"tokens": jnp.zeros((2, 8),
                                                             jnp.int32)})
    padded = eng._pad_cache(cache, 2)
    for path, x in jax.tree_util.tree_leaves_with_path(padded):
        key = path[-1].key
        assert x.shape[2] == 40, (key, x.shape)      # seq axis is axis 2
        if key.endswith("_scale"):
            assert x.shape[-1] == 1                   # scale cols intact
    # state tensors (no seq axis) must pass through untouched
    ssm_cfg = get_config("rwkv6-1.6b").reduced()
    ssm_params = T.lm_init(jax.random.PRNGKey(2), ssm_cfg)
    ssm_eng = ServeEngine(ssm_cfg, ssm_params, max_len=40)
    _, state = ssm_eng._prefill(ssm_params, {"tokens": jnp.zeros((2, 8),
                                                                 jnp.int32)})
    repadded = ssm_eng._pad_cache(state, 2)
    for (p1, a), (p2, b) in zip(
            jax.tree_util.tree_leaves_with_path(state),
            jax.tree_util.tree_leaves_with_path(repadded)):
        assert a.shape == b.shape, (p1, a.shape, b.shape)
