"""model.prefill_ms_per_chunk -- layer: model step; unit ms; source:
device_trace; moves ttft_p95_ms.

Device seconds of the complete runs of the prefill programs in the
traced window -- the chunk step, the carry write and the page scatter,
jitted as ``chunk_step``, ``_ctx_write`` and ``_scatter_pages`` -- over
the ``prefill_chunk`` annotations complete in it.  Silent where there
is no such annotation, or where the chunk step's runs and the chunks
disagree in number."""

from bench.lib.hostspans import of

LAYER, UNIT, SOURCE, MOVES = ("model step", "ms", "device_trace",
                              "ttft_p95_ms")
CHUNK_STEP = "chunk_step"
PROGRAMS = (CHUNK_STEP, "_ctx_write", "_scatter_pages")


def value(trace):
    chunks = len(trace.named("prefill_chunk"))
    if chunks == 0 or len(trace.program_runs(CHUNK_STEP)) != chunks:
        return None
    device_s = sum(b - a for name in PROGRAMS
                   for a, b in trace.program_runs(name))
    return 1e3 * device_s / chunks


def read(ctx):
    trace = of(ctx)
    return None if trace is None else value(trace)
