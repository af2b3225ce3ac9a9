"""device.compiles_in_window -- layer: device; unit count; source:
program_counter; moves ttft_p95_ms.

How many times the engine's jitted programs were traced inside the
window (growth of ``ContinuousEngine.trace_counts``); 0 when set-up
warmed every shape the traffic uses."""

LAYER, UNIT, SOURCE, MOVES = ("device", "count", "program_counter",
                              "ttft_p95_ms")


def read(ctx):
    return float(ctx.compiles_in_window)
