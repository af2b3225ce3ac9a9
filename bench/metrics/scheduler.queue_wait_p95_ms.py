"""scheduler.queue_wait_p95_ms -- layer: scheduler (serve/scheduler.py);
unit ms; source: program_span; moves ttft_p95_ms.

95th percentile over the window's requests of the time from when a
request was due to the scheduler's ``ADMIT`` event for it."""

import numpy as np

LAYER, UNIT, SOURCE, MOVES = ("scheduler", "ms", "program_span",
                              "ttft_p95_ms")


def read(ctx):
    due = {r.req.rid: r.due for r in ctx.requests if r.req is not None}
    waits = [t - due[rid] for t, rid, _ in ctx.events.get("ADMIT", [])
             if rid in due]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95.0))
