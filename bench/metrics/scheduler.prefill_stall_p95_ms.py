"""scheduler.prefill_stall_p95_ms -- layer: scheduler
(serve/scheduler.py); unit ms; source: program_span; moves ttft_p95_ms.

95th percentile over the window's requests of the time from the
scheduler's first ``ADMIT`` event for a request whose prefill
completed (``PREFILL_COMPLETE``) to the end of the ``prefill_chunk``
span that holds the request's last ``PREFILL_CHUNK`` event.  With ``scheduler.queue_wait_p95_ms`` it splits the time to
first token.  Silent where the recorder wrote no ``prefill_chunk``
span."""

import bisect

import numpy as np

LAYER, UNIT, SOURCE, MOVES = ("scheduler", "ms", "program_span",
                              "ttft_p95_ms")


def read(ctx):
    chunks = sorted(ctx.spans.get("prefill_chunk", []))
    if not chunks:
        return None
    starts = [t for t, _ in chunks]
    due = {r.req.rid for r in ctx.requests if r.req is not None}
    complete = {rid for _, rid, _ in ctx.events.get("PREFILL_COMPLETE", [])}
    admit, done = {}, {}
    for t, rid, _ in ctx.events.get("ADMIT", []):
        admit.setdefault(rid, t)
    for t, rid, _ in ctx.events.get("PREFILL_CHUNK", []):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= chunks[i][0] + chunks[i][1]:
            done[rid] = chunks[i][0] + chunks[i][1]
    stalls = [done[rid] - admit[rid] for rid in done
              if rid in admit and rid in due and rid in complete]
    if not stalls:
        return None
    return 1e3 * float(np.percentile(stalls, 95.0))
