"""decode_attn_ms: device time of decode attention per micro-step.

The device ops of the decode programs that ran within traced engine steps
(`Ctx.programs`), counted as `xplane.breakdown` counts them, whose
innermost contract scope (`bench/opscope.py`) is `attn_core`: scores,
mask, softmax, P.V and the layout of K/V for them, such as the broadcast
of K/V to the query heads. Divided by runs x `decode_chunk`. On a program
whose ops carry no contract scope, one older than the scopes, every op
reads as unscoped and this reads 0: a 0 that measures no attention, read
as a number and not as None only because `bench/cell.py` fails a traced
run on a None. Layer: decode attention (`models/attention.py`).
"""

from bench import opscope


def read(ctx):
    split = opscope.decode_split_ms(ctx)
    return None if split is None else split["attn_core"]
