"""The yardstick's operation and byte counts against hand-computed
values, and against the program's own packed layout."""

import json
import os

import pytest

from bench.tests.tiny import ROOT, run_module
from bench.lib import costs

QWEN = os.path.join(ROOT, "bench", "configs", "qwen2-0.5b.json")


@pytest.fixture(scope="module")
def qwen():
    with open(QWEN) as f:
        return costs.Dense.from_config(json.load(f))


def test_kv_page_is_811008_bytes(qwen):
    # 2 (K, V) x 24 layers x 128 tokens x 2 KV heads x (64 posit8 codes
    # + one bf16 scale)
    assert qwen.kv_token_bytes == 6336
    assert qwen.kv_page_bytes(128) == 811_008


def test_packed_params_are_747_mb(qwen):
    # embedding fp32 151936 x 896 x 4, final norm, and per layer: two
    # norms, QKV biases, posit8 q/k/v/o (one byte a code, fp32 scale
    # per output channel), fp4 gate/up/down (half a byte a code)
    per_layer = (2 * 896 * 4 + (896 + 128 + 128) * 4
                 + 896 * 896 + 896 * 4 + 2 * (896 * 128 + 128 * 4)
                 + 896 * 896 + 896 * 4
                 + 2 * (896 * 4864 // 2 + 4864 * 4)
                 + 4864 * 896 // 2 + 896 * 4)
    want = 24 * per_layer + 151936 * 896 * 4 + 896 * 4
    assert want == 746_974_720
    assert qwen.weight_bytes == want
    assert round(want / 1e6) == 747


def test_weight_bytes_match_the_program_layout(qwen):
    """The closed form equals the program's packed tree (words, scales,
    fp32 leaves), less the power-gating masks it also keeps."""
    import jax
    from bench.lib.registry import cell as find_cell
    from repro.core.policy import flatten_with_paths
    run = run_module()
    c = find_cell("qwen2-0.5b.chat")
    packed = jax.eval_shape(run.weight_maker(c),
                            jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    total = mask = 0
    for path, leaf in flatten_with_paths(packed):
        n = leaf.size * leaf.dtype.itemsize
        total += n
        mask += n if path.endswith("/mask") else 0
    assert total - mask == qwen.weight_bytes


def test_decode_and_prefill_flops_by_hand(qwen):
    layer = 896 * (2 * 896 + 2 * 128) + 3 * 896 * 4864
    assert qwen.layer_matmul_params == layer == 14_909_440
    logits = 2 * 896 * 151936
    # one token at position 9 attends 10 keys: QK^T and PV, 14 heads
    attn = 4 * 24 * 14 * 64 * 10
    assert qwen.decode_token_flops(9) == 2 * 24 * layer + attn + logits
    # 3 prompt tokens at 5..7 attend 6, 7 and 8 keys; logits once
    attn3 = 4 * 24 * 14 * 64 * (6 + 7 + 8)
    assert qwen.prefill_flops(5, 3, True) == \
        2 * 24 * layer * 3 + attn3 + logits
    assert qwen.prefill_flops(5, 3, False) == 2 * 24 * layer * 3 + attn3


def test_decode_dispatch_bytes_by_hand(qwen):
    # rows at 127 (1 page) for 2 steps and at 0 for 1 step: weights read
    # twice; pages 1 + 2 (128 then 129 live tokens) + 1; three writes
    got = qwen.decode_dispatch_bytes([127, 0], [2, 1], 128)
    assert got == 2 * qwen.weight_bytes + 4 * 811_008 + 3 * 6336


def test_peaks_are_keyed_by_device_kind():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "cloud.google.com" in p["source"]
    with pytest.raises(KeyError):
        costs.peaks("cpu")
