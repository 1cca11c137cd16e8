"""decode_mfu: the decode programs' model FLOPs over the chip's bf16 peak, in %.

Model FLOPs of the tokens the traced decode dispatches delivered: the
operations of the packed projections and the output head per token
(`bench/workcount.py`) plus attention over each token's actual context,
not over the slab, each as the architecture's adapter lists them. Divided
by the device time of the decode programs times the bf16 peak. Layer: the
whole decode step.
"""

from bench import workcount
from bench.context import DECODE_PROGRAM


def read(ctx):
    runs = ctx.programs(DECODE_PROGRAM)
    steps = ctx.traced_steps()
    tokens = sum(s.tokens for s in steps)
    if not runs or not tokens:
        return None
    adapter = ctx.adapter
    flops = tokens * workcount.model_flops_per_token(adapter, ctx.model,
                                                     ctx.kratos) \
        + sum(adapter.attention_flops(ctx.model, s.ctx_sum) for s in steps)
    device_s = sum(e - s for _, s, e in runs) / 1e9
    return 100.0 * flops / (device_s * ctx.peaks["bf16_flops"])
