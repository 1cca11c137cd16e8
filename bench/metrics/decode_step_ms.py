"""decode_step_ms: device time of the fused decode programs per micro-step.

Summed duration of the `decode` program runs in the trace, divided by the
runs times the engine's `decode_chunk` micro-steps each. Layer: model
step, fused decode (`distributed/steps.py`).
"""

from bench.context import DECODE_PROGRAM


def read(ctx):
    runs = ctx.programs(DECODE_PROGRAM)
    if not runs:
        return None
    k = ctx.engine["decode_chunk"]
    return sum(e - s for _, s, e in runs) / 1e6 / (len(runs) * k)
