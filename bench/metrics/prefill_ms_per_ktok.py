"""prefill_ms_per_ktok: device time of the prefill programs per 1000 prompt tokens.

Device time is the summed duration of the `prefill` program runs in the
trace; tokens are the real (unpadded) prompt tokens of the requests
admitted while the profiler recorded. Layer: model step, prefill programs
(`distributed/steps.py`).
"""

from bench.context import PREFILL_PROGRAM


def read(ctx):
    runs = ctx.programs(PREFILL_PROGRAM)
    tokens = sum(s.prefill_tokens for s in ctx.traced_steps())
    if not runs or not tokens:
        return None
    return sum(e - s for _, s, e in runs) / 1e6 / (tokens / 1e3)
