"""device_idle_share (%): 1 - (union of the device's operation intervals
in the traced window / the window).  Layer: device."""

from chipbench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    busy = trace.busy_s(ctx.trace)
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
