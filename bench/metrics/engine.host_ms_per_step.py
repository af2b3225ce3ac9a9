"""engine.host_ms_per_step -- layer: engine (serve/engine.py step);
unit ms; source: program_span; moves tpot_p95_ms.

Median over the window's engine steps of the host time of a step: the
``step`` span minus the ``decode_sync`` span inside it (the wait for
the device's tokens)."""

import statistics

LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "program_span", "tpot_p95_ms"


def read(ctx):
    steps = ctx.spans.get("step", [])
    syncs = sorted(ctx.spans.get("decode_sync", []))
    if not steps:
        return None
    host, j = [], 0
    for t, dur in sorted(steps):
        waited = 0.0
        while j < len(syncs) and syncs[j][0] < t + dur:
            if syncs[j][0] >= t:
                waited += syncs[j][1]
            j += 1
        host.append(dur - waited)
    return 1e3 * statistics.median(host)
