"""qwen2-0.5b: weights from the seed, the plain float32 reference, and
its cost model (``costs.Dense``).

Nothing here imports the program.  The weights are made here, in the
parameter layout the program's ``ContinuousEngine`` takes; the harness
packs them with the program and serves them, and this module rebuilds
the stored values (``stored_weights``) from the same seed with its own
number formats (``bench/lib/numerics.py``).

The reference is one teacher-forced forward pass over prompt + served
tokens, in float32 (the caller sets "highest" matmul precision):
RMSNorm, GQA attention with rotate-half RoPE and QKV biases, a SwiGLU
FFN, tied logits.  It follows what the configuration states about the
cache: query positions inside the prompt attend to the prompt's exact
K/V (the prompt is prefilled against its own carry), positions after
it attend to every position's posit8 K/V (decode reads the pages).
``act`` rounds every activation to a lower type: the control.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.lib import costs, numerics as nx

QUERY_BLOCK = 256


def _dims(c: Dict[str, Any]):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"])


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree, in the program's layout (layers stacked)."""
    L, d, H, KH, hd, ff, V = _dims(c)
    lin = lambda k, n, bias: dict({"w": (L, k, n)},
                                  **({"bias": (L, n)} if bias else {}))
    b = c["attention_bias"]
    return {
        "embed": {"table": (V, d)},
        "final_norm": {"norm_scale": (d,)},
        "layers": {
            "ln1": {"norm_scale": (L, d)},
            "ln2": {"norm_scale": (L, d)},
            "attn": {"wq": lin(d, H * hd, b), "wk": lin(d, KH * hd, b),
                     "wv": lin(d, KH * hd, b), "wo": lin(H * hd, d, False)},
            "ffn": {"gate": lin(d, ff, False), "up": lin(d, ff, False),
                    "down": lin(ff, d, False)},
        },
    }


def _paths(tree, prefix=""):
    for k in sorted(tree):
        v, p = tree[k], f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _paths(v, p)
        else:
            yield p, v


def _set(tree, path, value):
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def init_weights(key: jax.Array, c: Dict[str, Any]) -> Dict[str, Any]:
    """Master float32 weights: one fold of ``key`` per leaf, in path
    order (jit this: it is one program)."""
    out: Dict[str, Any] = {}
    for i, (path, shape) in enumerate(_paths(param_shapes(c))):
        k = jax.random.fold_in(key, i)
        if path == "embed/table":
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif path.endswith("/w"):
            a = 1.0 / math.sqrt(shape[-2])
            v = jax.random.uniform(k, shape, jnp.float32, -a, a)
        elif path.endswith("/bias"):
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:                                          # norm scales
            v = jax.random.uniform(k, shape, jnp.float32, 0.8, 1.2)
        _set(out, path, v)
    return out


def cost_model(c: Dict[str, Any]) -> costs.Dense:
    """The yardstick's operations and bytes: a dense GQA transformer."""
    return costs.Dense.from_config(c)


def program_config(c: Dict[str, Any], base):
    """The program's ``ModelConfig`` with this file's sizes put in."""
    return dataclasses.replace(
        base, family=c["family"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], qkv_bias=c["attention_bias"],
        tie_embeddings=c["tie_word_embeddings"], ffn_kind="swiglu")


def stored_weights(master: Dict[str, Any], c: Dict[str, Any]):
    """The values the configuration stores: attention matrices and FFN
    matrices in their formats, everything else float32."""
    fmt = c["precision"]["weights"]
    out = jax.tree.map(lambda x: x, master)
    for name in ("wq", "wk", "wv", "wo"):
        out["layers"]["attn"][name]["w"] = nx.weight_values(
            master["layers"]["attn"][name]["w"], fmt["attention"])
    for name in ("gate", "up", "down"):
        out["layers"]["ffn"][name]["w"] = nx.weight_values(
            master["layers"]["ffn"][name]["w"], fmt["ffn"])
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, kq, vq, prompt_len):
    """Causal GQA, in query blocks.  Rows before ``prompt_len`` read the
    exact K/V, rows from it on the posit8 K/V."""
    T, H, hd = q.shape
    KH = k.shape[1]
    blk = min(QUERY_BLOCK, T)
    qb = q.reshape(T // blk, blk, KH, H // KH, hd) / math.sqrt(hd)
    kpos = jnp.arange(T)

    def one(args):
        i, qi = args
        qpos = i * blk + jnp.arange(blk)
        paged = (qpos >= prompt_len)[:, None, None, None]
        s = jnp.where(paged, jnp.einsum("bkgd,tkd->bkgt", qi, kq),
                      jnp.einsum("bkgd,tkd->bkgt", qi, k))
        live = (kpos[None, :] <= qpos[:, None])[:, None, None, :]
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.where(paged, jnp.einsum("bkgt,tkd->bkgd", p, vq),
                         jnp.einsum("bkgt,tkd->bkgd", p, v))

    out = jax.lax.map(one, (jnp.arange(T // blk), qb))
    return out.reshape(T, H * hd)


def logits_at(w: Dict[str, Any], tokens: jax.Array, prompt_len: jax.Array,
              rows: jax.Array, c: Dict[str, Any], act=None) -> jax.Array:
    """Logits (len(rows), vocab) at positions ``rows`` of one sequence
    ``tokens`` (T,), whose first ``prompt_len`` tokens are the prompt."""
    L, d, H, KH, hd, ff, V = _dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    T = tokens.shape[0]
    r = lambda x: nx.act_round(x, act)
    pos = jnp.arange(T, dtype=jnp.float32)
    x = r(w["embed"]["table"][tokens])

    def layer(x, p):
        a = p["attn"]
        h = r(_rms(x, p["ln1"]["norm_scale"], eps))
        q = r(h @ a["wq"]["w"] + a["wq"].get("bias", 0.0))
        k = r(h @ a["wk"]["w"] + a["wk"].get("bias", 0.0))
        v = r(h @ a["wv"]["w"] + a["wv"].get("bias", 0.0))
        q = r(_rope(q.reshape(T, H, hd), pos, theta))
        k = r(_rope(k.reshape(T, KH, hd), pos, theta))
        v = v.reshape(T, KH, hd)
        o = r(_attention(q, k, v, nx.kv_values(k), nx.kv_values(v),
                         prompt_len))
        x = r(x + r(o @ a["wo"]["w"]))
        h = r(_rms(x, p["ln2"]["norm_scale"], eps))
        f = p["ffn"]
        g = r(jax.nn.silu(r(h @ f["gate"]["w"])) * r(h @ f["up"]["w"]))
        return r(x + r(g @ f["down"]["w"])), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    h = r(_rms(x[rows], w["final_norm"]["norm_scale"], eps))
    return r(h @ w["embed"]["table"].T)
