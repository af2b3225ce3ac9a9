"""device.idle_frac -- layer: device; unit %; source: device_trace;
moves output_tok_s.

100 x (1 - the union of the device-operation intervals over the traced
window's length), averaged over the chips used."""

LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "output_tok_s"


def read(ctx):
    if ctx.device is None or ctx.device.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.device.busy_s / ctx.device.window_s)
