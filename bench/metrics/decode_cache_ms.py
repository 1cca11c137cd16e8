"""decode_cache_ms: device time of KV cache movement per micro-step.

As `decode_attn_ms`, for the ops whose innermost contract scope is
`kv_write` (new K/V written into the cache), `layer_scan` (the layer
scan's own per-layer slices of the stacked cache and weights, and their
write-back) or `decode_loop` (the micro-step loop's carry copies of the
slab). The weights' slices are a small part of `layer_scan` (PERF.md,
section 3), but a change to the stacked weights' layout moves this too.
On a program whose ops carry no contract scope this reads ABSENT, as
`decode_attn_ms` does.
Layer: KV cache movement (`models/attention.py`, `models/transformer.py`,
`distributed/steps.py`).
"""

from bench import opscope


def read(ctx):
    split = opscope.decode_split_ms(ctx)
    if not isinstance(split, dict):
        return split
    return split["kv_write"] + split["layer_scan"] + split["decode_loop"]
