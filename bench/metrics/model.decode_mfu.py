"""model.decode_mfu -- layer: model step; unit %; source: device_trace;
moves tpot_p95_ms.

The decode FLOPs of the traced decode dispatches, as the
configuration's cost model counts them (for a dense model: matmuls,
causal attention over the live context, logits), over the device time
of the decode-loop program's complete runs in the trace (the engine
jits it as ``loop``) times the chip's bf16 peak: the decode step's
share of the chip's peak while it runs."""

from bench.lib.layers import roofline_share, traced_decode_cost

LAYER, UNIT, SOURCE, MOVES = ("model step", "%", "device_trace",
                              "tpot_p95_ms")
PROGRAM = "loop"


def read(ctx):
    if ctx.device is None:
        return None
    flops, _, n = traced_decode_cost(ctx)
    return roofline_share(flops / ctx.peak["bf16_flops_per_s"],
                          ctx.device.program_seconds(PROGRAM), n)
