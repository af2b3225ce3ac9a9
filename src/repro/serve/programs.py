"""The jit name of every program the serving engines run.

A jitted function ``f`` runs on the device as module ``jit_f``, and the
device trace names each run by it; the benchmark finds the decode loop,
the chunk step and the prefill writes there by these names.  Each
program's function goes through ``program`` before ``jax.jit``, so
renaming a Python function changes nothing on the device trace.
"""

from __future__ import annotations

__all__ = ["PROGRAM_NAMES", "program"]

#: role -> jit name (the trace shows ``jit_<name>``)
PROGRAM_NAMES = {
    "decode_loop": "loop",
    "chunk_step": "chunk_step",
    "carry_write": "_ctx_write",
    "page_scatter": "_scatter_pages",
}


def program(fn, role: str):
    """``fn``, named for ``role`` (before it goes to ``jax.jit``)."""
    fn.__name__ = fn.__qualname__ = PROGRAM_NAMES[role]
    return fn
