"""engine.exposed_host_ms_per_step -- layer: engine (serve/engine.py
step); unit ms; source: device_trace; moves tpot_p95_ms.

Mean over the traced window's complete ``step`` annotations (the
engine's span recorder writes them) of the device's idle time inside
the step that no ``decode_sync`` or ``prefill_sync`` annotation covers:
the host work of a step that the chip really waits on.  Silent where
the trace holds no ``step`` annotation."""

from bench.lib.hostspans import exposed_host_s, of

LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "device_trace", "tpot_p95_ms"


def value(trace):
    per_step = exposed_host_s(trace)
    if not per_step:
        return None
    return 1e3 * sum(per_step) / len(per_step)


def read(ctx):
    trace = of(ctx)
    return None if trace is None else value(trace)
