"""Share of the traced window in which the device ran no operation."""

from chipbench import devtrace


def read(ctx):
    if ctx.device is None or ctx.device_window is None:
        return None
    w0, w1 = ctx.device_window
    if w1 <= w0 or not (ctx.device.ops or ctx.device.modules):
        return None
    return 100.0 * (1.0 - devtrace.busy_seconds(ctx.device, w0, w1)
                    / ((w1 - w0) / 1e9))
