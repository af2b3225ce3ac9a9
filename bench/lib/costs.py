"""Operations and bytes the algorithm needs, from shapes alone, and the
chip's published peaks.

These are the yardstick's numbers, kept apart from the program's own
byte models (``serve/paged_kv.py``, ``roofline/analysis.py``) so that a
change to the program cannot move them.  Bytes are what the stored
formats hold (packed weight codes and their scales, fp32 leaves as
fp32, posit8 KV codes with bf16 scales), not what one implementation
happens to move; operations count two per multiply-accumulate and
only the work the algorithm needs: causal attention over the live
context, logits only where a token is sampled.

Each configuration brings its own cost model: the module beside its
JSON defines ``cost_model(config)`` (``registry.cell`` refuses one that
does not), and the harness hands what it returns to the per-layer
readers as ``layers.Context.model``.  The shared readers
(``bench/lib/layers.py``) call four methods on it, each returning a
float:

  decode_dispatch_flops(positions, steps)
  decode_dispatch_bytes(positions, steps, page)
      one K-step decode dispatch: row r starts at ``positions[r]`` and
      decodes ``steps[r]`` tokens; ``page`` is the KV page's tokens
  prefill_flops(start, real, sampled)
  prefill_bytes(start, real)
      one prefill chunk of ``real`` prompt tokens at ``start ..``;
      ``sampled`` where it completes the prompt (its logits count)

A configuration's own readers may call more methods on its own model.
``Dense`` is the model of a dense GQA transformer.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Iterable

__all__ = ["Dense", "peaks", "PEAKS_PATH", "WORD_BITS"]

PEAKS_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")

WORD_BITS = 32          # codes are stored packed in 32-bit words
_BITS = {"fp4": 4, "posit8_0": 8, "posit16_1": 16, "float32": 32}


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; a chip that is not in
    the table is an error, never a default."""
    with open(PEAKS_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_PATH} (have {sorted(table)})")
    return table[device_kind]


def _packed_bytes(k: int, n: int, fmt: str) -> int:
    """A (K, N) matrix: codes packed along N into 32-bit words, plus one
    fp32 scale per output channel; fp32 matrices are stored as is."""
    bits = _BITS[fmt]
    if bits == 32:
        return 4 * k * n
    per = WORD_BITS // bits
    return 4 * k * (-(-n // per)) + 4 * n


@dataclasses.dataclass(frozen=True)
class Dense:
    """A dense GQA transformer with a SwiGLU FFN, stated by its
    configuration file."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    attn_fmt: str
    ffn_fmt: str
    tied: bool
    qkv_bias: bool

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dense":
        w = c["precision"]["weights"]
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ff=c["intermediate_size"],
                   vocab=c["vocab_size"], attn_fmt=w["attention"],
                   ffn_fmt=w["ffn"], tied=c["tie_word_embeddings"],
                   qkv_bias=c["attention_bias"])

    # -- operations ---------------------------------------------------------

    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv) + 3 * self.d * self.ff

    def attn_flops(self, ctx: int) -> float:
        """One query against ``ctx`` keys, all layers: QK^T and PV."""
        return 4.0 * self.layers * self.heads * self.head_dim * ctx

    @property
    def logits_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def decode_token_flops(self, pos: int) -> float:
        """One decoded token at position ``pos`` (attends pos + 1)."""
        return (2.0 * self.layers * self.layer_matmul_params
                + self.attn_flops(pos + 1) + self.logits_flops)

    def prefill_flops(self, start: int, real: int,
                      sampled: bool) -> float:
        """``real`` prompt tokens at ``start ..``, causal; logits only for
        the last one when the chunk completes the prompt."""
        ctx = real * start + real * (real + 1) // 2
        return (2.0 * self.layers * self.layer_matmul_params * real
                + self.attn_flops(1) * ctx
                + (self.logits_flops if sampled else 0.0))

    # -- bytes ----------------------------------------------------------------

    @property
    def weight_bytes(self) -> int:
        """All parameters as stored: packed matrices with their scales,
        fp32 embedding (read back for the tied logits), norms, biases."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        per = (_packed_bytes(self.d, q, self.attn_fmt)
               + 2 * _packed_bytes(self.d, kv, self.attn_fmt)
               + _packed_bytes(q, self.d, self.attn_fmt)
               + 2 * _packed_bytes(self.d, self.ff, self.ffn_fmt)
               + _packed_bytes(self.ff, self.d, self.ffn_fmt)
               + 4 * 2 * self.d)
        if self.qkv_bias:
            per += 4 * (q + 2 * kv)
        total = self.layers * per + 4 * self.vocab * self.d + 4 * self.d
        if not self.tied:
            total += _packed_bytes(self.d, self.vocab, "float32")
        return total

    @property
    def kv_token_bytes(self) -> int:
        """posit8 K and V codes plus one bf16 scale per head, all layers."""
        return 2 * self.layers * self.kv_heads * (self.head_dim + 2)

    def kv_page_bytes(self, page: int) -> int:
        return page * self.kv_token_bytes

    def decode_dispatch_bytes(self, positions: Iterable[int],
                              steps: Iterable[int], page: int) -> float:
        """One K-step decode dispatch: weights once per iteration that
        any row needs, and per row and iteration its live KV pages and
        the token it writes.  ``steps[r]`` is how many iterations row
        ``r`` needed (its tokens delivered)."""
        positions, steps = list(positions), list(steps)
        iters = max(steps, default=0)
        kv = 0
        for p, n in zip(positions, steps):
            for i in range(n):
                kv += -(-(p + i + 1) // page) * self.kv_page_bytes(page)
                kv += self.kv_token_bytes
        return float(iters * self.weight_bytes + kv)

    def decode_dispatch_flops(self, positions: Iterable[int],
                              steps: Iterable[int]) -> float:
        return float(sum(self.decode_token_flops(p + i)
                         for p, n in zip(positions, steps)
                         for i in range(n)))

    def prefill_bytes(self, start: int, real: int) -> float:
        """One chunk: the weights, the bf16 K/V of the ``start`` tokens
        before it, and the posit8 K/V it writes."""
        carry = 2 * self.layers * self.kv_heads * self.head_dim * 2 * start
        return float(self.weight_bytes + carry
                     + real * self.kv_token_bytes)
