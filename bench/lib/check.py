"""The comparison that decides ``correct`` for a served model.

After the window has closed, a sample of the finished requests, drawn
from the seed and always holding the longest, is run once through the
configuration's plain reference: prompt + served tokens, teacher
forced.  At each served position the number read is how far the served
token's reference logit lies below the reference's best (0 where it is
the reference's argmax).  Two numbers are compared with the
configuration's limits: the widest such gap over the sample, which
catches one badly wrong token, and the mean gap over the sample's
served tokens, which is steady from seed to seed where the widest gap
is a single near-tie.

The control puts the reference itself, computed one precision below
the configuration's, in the program's place: at each of the same
positions it reads the gap of the token the lower precision puts first,
and those numbers go through the same ``verdict`` and limits as the
program's.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import numerics as nx

__all__ = ["seed_key", "pick_sample", "token_gaps", "verdict",
           "CONTROL_DTYPE"]

# the step below the stated bf16 activations: fp8 (e4m3)
CONTROL_DTYPE = nx.FP8


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (drivers pass > 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, (seed >> 31) % (2 ** 31))


def pick_sample(finished: Sequence[Tuple[np.ndarray, np.ndarray]],
                seed: int, min_tokens: int,
                max_requests: int) -> List[int]:
    """Indices into ``finished`` ((prompt, served) pairs): the one with
    the most served tokens, then others in a seeded order until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i][1]), len(finished[i][0])))
    rest = [i for i in np.random.default_rng([int(seed), 17]).permutation(
        len(finished)) if i != longest]
    pick, total = [longest], len(finished[longest][1])
    for i in rest:
        if total >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(int(i))
        total += len(finished[i][1])
    return pick


@functools.lru_cache(maxsize=None)
def _gap_fn(reference, config_json: str, control: bool):
    """jit: (weights, tokens (T,), prompt_len, rows (R,), served (R,))
    -> (served gap (R,), control gap (R,) or zeros)."""
    config = json.loads(config_json)

    def fn(w, tokens, prompt_len, rows, served):
        ref = reference.logits_at(w, tokens, prompt_len, rows, config)
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        low = reference.logits_at(w, tokens, prompt_len, rows, config,
                                  act=CONTROL_DTYPE)
        pick = jnp.argmax(low, -1)
        ctl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return gap, ctl

    return jax.jit(fn)


def token_gaps(reference, config: Dict[str, Any], weights,
               sample: Sequence[Tuple[np.ndarray, np.ndarray]],
               seq_len: int, max_out: int,
               control: bool = False) -> Dict[str, Any]:
    """Run the reference over each (prompt, served) pair, padded to
    ``seq_len`` tokens and ``max_out`` rows (one compiled program).
    Returns the widest and the mean gap, the share of served tokens that
    are the reference's argmax, the share that are distinct within
    their request, and the tokens compared; with ``control``, also the
    control's widest and mean gap under ``"control"``, keyed as the
    program's are."""
    fn = _gap_fn(reference, json.dumps(config, sort_keys=True), control)
    worst, worst_ctl, exact, total, distinct = 0.0, 0.0, 0, 0, 0
    gap_sum = ctl_sum = 0.0
    with jax.default_matmul_precision("highest"):
        for prompt, served in sample:
            n, p = len(served), len(prompt)
            seq = np.zeros(seq_len, np.int32)
            body = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            seq[:body.size] = body
            rows = np.full(max_out, p - 1, np.int32)
            rows[:n] = p - 1 + np.arange(n)
            tgt = np.zeros(max_out, np.int32)
            tgt[:n] = served
            gap, ctl = fn(weights, jnp.asarray(seq), jnp.int32(p),
                          jnp.asarray(rows), jnp.asarray(tgt))
            gap, ctl = np.asarray(gap)[:n], np.asarray(ctl)[:n]
            worst = max(worst, float(gap.max()))
            worst_ctl = max(worst_ctl, float(ctl.max()))
            gap_sum += float(gap.sum())
            ctl_sum += float(ctl.sum())
            exact += int(np.sum(gap == 0.0))
            distinct += len(np.unique(served))
            total += n
    total = max(total, 1)
    out = {"token_gap_max": worst, "token_gap_mean": gap_sum / total,
           "argmax_share": exact / total, "distinct_share": distinct / total,
           "tokens": total}
    if control:
        out["control"] = {"token_gap_max": worst_ctl,
                          "token_gap_mean": ctl_sum / total}
    return out


def verdict(numbers: Dict[str, Optional[float]],
            limits: Dict[str, float]) -> Tuple[Dict[str, Any], bool]:
    """Each number compared beside its limit, and whether every one is
    read and within it."""
    checks = {name: {"value": numbers.get(name), "limit": float(limit)}
              for name, limit in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return checks, ok
