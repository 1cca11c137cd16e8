"""step_idle_ms: device-idle time inside each traced engine step.

The capture's `engine.step` host spans (`Ctx.step_spans`), each the whole
of one `InferenceEngine.step()`, the steps that `Ctx.programs` counts
device runs in: the time inside them in which no op ran on the device,
summed and divided by their number. That is the host's share of a step:
admission, dispatch and the bookkeeping after each sync. Layer: engine
host path (`serve/engine.py`).
"""

from bench import opscope


def read(ctx):
    steps = ctx.step_spans()
    if not ctx.plane or not steps:
        return None
    return opscope.idle_ns(ctx.capture.ops[ctx.plane], steps) / 1e6 \
        / len(steps)
