"""gemm_roofline: the packed decode GEMMs' share of their roofline, in %.

Numerator: the least time the chip needs for the work every packed
projection of each decode micro-step requires at the configuration's
Kratos point (`bench/workcount.py`, the projections from the
architecture's adapter, peaks from `bench/peaks.py`), over `n_slots` rows,
times the micro-steps traced. Denominator: the device time of the GEMM
kernel runs inside those decode programs. The trace
names a Pallas kernel's op by its HLO text only, so a configuration
states how its GEMM kernel's op reads there (`gemm_ops`, a regular
expression over that text: the Mosaic call and its operands). Layer:
packed GEMMs (`core/kratos.py`, `kernels/quant_matmul.py`,
`kernels/dense_matmul.py`).
"""

import re

from bench import workcount
from bench.context import DECODE_PROGRAM


def read(ctx):
    runs = ctx.programs(DECODE_PROGRAM)
    pattern = re.compile(ctx.config["gemm_ops"])
    ops = [op for op in ctx.ops_in(runs) if pattern.search(op[0])]
    if not ops:
        return None
    k = ctx.engine["decode_chunk"]
    least = len(runs) * k * workcount.decode_gemm_least_s(
        ctx.adapter, ctx.model, ctx.kratos, ctx.engine["n_slots"], ctx.peaks)
    return 100.0 * least / (sum(e - s for _, s, e in ops) / 1e9)
