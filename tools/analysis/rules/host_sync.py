"""Rule ``host-sync``: the decode/step critical path must not block on
implicit device->host transfers.

The serving plane's latency model budgets exactly ONE host sync per
engine step -- the explicit ``jax.device_get`` of the (B, K) sampled
tokens.  Anything else that forces a transfer inside the step path
(``.item()``, ``np.asarray`` on a device value, ``int()/float()`` on a
jnp result, printing a device array) serializes host and device and
shows up as an unattributable p99 shift, not a test failure.

Two checks:

  1. inside the serving layer's HOT functions (``step``, ``dispatch``,
     ``sync``, the prefill/dispatch helpers), flag ``.item()``,
     ``np.asarray`` / ``np.array``, ``print``, and ``int/float/bool``
     applied to a value produced by a ``jnp.``/``jax.``/``lax.`` call
     (``jax.device_get`` is the sanctioned explicit escape and is
     never flagged);
  2. anywhere in the scanned tree, flag ``int/float/bool`` wrapping a
     ``jnp.``/``jax.``-rooted call lexically inside a for/while loop:
     a device sync per iteration.  Accumulate on device and convert
     once after the loop.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set

from ..core import (Finding, FileContext, Rule, dotted_name, register,
                    root_name, walk_functions)

NAME = "host-sync"

#: step/dispatch-path functions of the serving layer (engine.py /
#: disagg.py).  ``_sample`` is deliberately absent: it is the sanctioned
#: HOST twin of the fused sampler, called once per request at prefill
#: completion, and its int(...) syncs are its contract.
HOT_FUNCTIONS = frozenset({
    "step", "dispatch", "sync", "admit_handoffs",
    "_prefill_chunk", "_forward_chunk", "_prefill_phase",
    "_dispatch_decode_loop",
    "_apply_decode_tokens", "_drain_ready",
})

_DEVICE_ROOTS = frozenset({"jnp", "jax", "lax"})
#: calls that RETURN host values (or metadata) despite a device root
_HOST_RETURNING = ("jax.device_get", "jnp.finfo", "jnp.iinfo",
                   "jax.eval_shape")
_CASTS = frozenset({"int", "float", "bool"})
_NP_SYNCS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                       "numpy.array", "onp.asarray", "onp.array"})


def _is_device_call(node: ast.AST) -> bool:
    """True for a Call rooted at jnp/jax/lax that returns a device
    value (``jax.device_get`` etc. excluded)."""
    if not isinstance(node, ast.Call):
        return False
    dn = dotted_name(node.func)
    if dn is None or root_name(node.func) not in _DEVICE_ROOTS:
        return False
    return not any(dn == h or dn.startswith(h + ".")
                   for h in _HOST_RETURNING)


def _device_bound_names(fn: ast.AST) -> Set[str]:
    """Names assigned (anywhere in ``fn``) from a device-returning
    jnp/jax call -- the conservative alias set the int/float check
    consults."""
    bound: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and _is_device_call(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    bound.add(tgt.id)
    return bound


def _check_hot_function(ctx: FileContext, fn) -> Iterable[Finding]:
    device_names = _device_bound_names(fn)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        dn = dotted_name(func)
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not node.args:
            yield Finding(NAME, ctx.path, node.lineno,
                          f"`.item()` in step-path `{fn.name}` blocks on a "
                          f"device->host transfer; keep the value on device "
                          f"or use the step's one sanctioned "
                          f"jax.device_get sync")
        elif dn in _NP_SYNCS:
            yield Finding(NAME, ctx.path, node.lineno,
                          f"`{dn}(...)` in step-path `{fn.name}` implicitly "
                          f"syncs if handed a device value; use "
                          f"jax.device_get for the sanctioned sync (host "
                          f"arrays: build them outside the hot path)")
        elif isinstance(func, ast.Name) and func.id == "print":
            yield Finding(NAME, ctx.path, node.lineno,
                          f"`print(...)` in step-path `{fn.name}`: printing "
                          f"a device value forces a blocking transfer (and "
                          f"host I/O) on the decode critical path; use the "
                          f"obs trace/metrics plane instead")
        elif isinstance(func, ast.Name) and func.id in _CASTS and node.args:
            arg = node.args[0]
            is_device = _is_device_call(arg) or (
                isinstance(arg, ast.Name) and arg.id in device_names) or (
                isinstance(arg, ast.Subscript)
                and isinstance(arg.value, ast.Name)
                and arg.value.id in device_names)
            if is_device:
                yield Finding(
                    NAME, ctx.path, node.lineno,
                    f"`{func.id}(...)` on a device value in step-path "
                    f"`{fn.name}` blocks on the transfer; sync once via "
                    f"jax.device_get and convert the host copy")


def _check_casts_in_loops(ctx: FileContext) -> Iterable[Finding]:
    loops = [n for n in ast.walk(ctx.tree)
             if isinstance(n, (ast.For, ast.While, ast.AsyncFor))]
    seen: Set[int] = set()
    for loop in loops:
        for stmt in loop.body + loop.orelse:
            for node in ast.walk(stmt):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in _CASTS and node.args):
                    continue
                if node.lineno in seen or not _is_device_call(node.args[0]):
                    continue
                seen.add(node.lineno)
                yield Finding(
                    NAME, ctx.path, node.lineno,
                    f"`{node.func.id}(jnp...)` inside a loop syncs the "
                    f"device every iteration; accumulate on device (or "
                    f"collect device scalars) and convert once after the "
                    f"loop")


def check_file(ctx: FileContext) -> List[Finding]:
    out: List[Finding] = []
    if ctx.path.startswith("src/repro/serve/"):
        for fn in walk_functions(ctx.tree):
            if fn.name in HOT_FUNCTIONS:
                out.extend(_check_hot_function(ctx, fn))
    out.extend(_check_casts_in_loops(ctx))
    return out


register(Rule(
    name=NAME,
    summary=("no implicit device->host sync (.item(), np.asarray, "
             "int()/float() on device values, print) in serve step paths "
             "or per-iteration in loops"),
    check_file=check_file,
))
