"""chunk_step_roofline -- layer: kernels; unit %; source: device_trace;
moves ttft_p95_ms.

The least time the chip could take for the traced prefill chunks,
max(FLOPs / bf16 peak, bytes / HBM bandwidth) each, counting only the
real prompt tokens a chunk forwards and logits only where the prompt
completes (the configuration's cost model), over the device time of
the chunk-step program's complete runs in the trace (jitted as
``chunk_step``)."""

from bench.lib.layers import roofline_share, traced_prefill_cost

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "ttft_p95_ms")
PROGRAM = "chunk_step"


def read(ctx):
    if ctx.device is None:
        return None
    _, least, n = traced_prefill_cost(ctx)
    return roofline_share(least, ctx.device.program_seconds(PROGRAM), n)
