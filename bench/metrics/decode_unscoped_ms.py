"""decode_unscoped_ms: device time per micro-step of decode ops under no scope.

As `decode_attn_ms`, for the ops whose name stack holds no contract scope
(`bench/opscope.py`): what XLA adds without metadata, such as copies it
inserts, and any op of the program that no scope names. On a program whose
ops carry no contract scope this reads ABSENT, as `decode_attn_ms` does:
there all the decode ops' time would read unscoped, not the time of ops
XLA added.
Layer: decode ops under no scope.
"""

from bench import opscope


def read(ctx):
    split = opscope.decode_split_ms(ctx)
    return split[""] if isinstance(split, dict) else split
