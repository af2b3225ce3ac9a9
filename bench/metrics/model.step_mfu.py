"""model.step_mfu -- layer: model step; unit %; source: device_trace;
moves ttft_p95_ms.

All model FLOPs of the traced steps (prefill chunks and decode
dispatches, counted by the configuration's cost model) over the traced
window's seconds times the chip's bf16 peak: the whole step's share of
the chip, which bounds what any one program's roofline can add."""

from bench.lib.layers import traced_decode_cost, window_prefill_flops

LAYER, UNIT, SOURCE, MOVES = ("model step", "%", "device_trace",
                              "ttft_p95_ms")


def read(ctx):
    if ctx.device is None or ctx.traced is None:
        return None
    flops = traced_decode_cost(ctx)[0] + window_prefill_flops(
        ctx, *ctx.traced)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.device.window_s
                            * ctx.peak["bf16_flops_per_s"])
