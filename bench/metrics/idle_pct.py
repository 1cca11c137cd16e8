"""idle_pct: share of the traced window in which no op ran on the device, in %.

1 - (union of the device's op intervals) / (traced window). Layer: the
device.
"""

from bench import xplane


def read(ctx):
    cap = ctx.capture
    if cap is None or not cap.ops.get(ctx.plane):
        return None
    lo, hi = xplane.window_ns(cap)
    return 100.0 * (1.0 - xplane.busy_ns(cap.ops[ctx.plane], lo, hi) / (hi - lo))
