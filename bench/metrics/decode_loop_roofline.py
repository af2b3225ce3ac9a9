"""decode_loop_roofline -- layer: kernels; unit %; source: device_trace;
moves tpot_p95_ms.

The least time the chip could take for the traced decode dispatches,
max(FLOPs / bf16 peak, bytes / HBM bandwidth) each, as the
configuration's cost model counts them (for a dense model: packed
weights as stored once per needed iteration, live posit8 KV pages),
over the device time of the decode-loop program's complete runs in the
trace (the engine jits it as ``loop``)."""

from bench.lib.layers import roofline_share, traced_decode_cost

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "tpot_p95_ms")
PROGRAM = "loop"


def read(ctx):
    if ctx.device is None:
        return None
    _, least, n = traced_decode_cost(ctx)
    return roofline_share(least, ctx.device.program_seconds(PROGRAM), n)
