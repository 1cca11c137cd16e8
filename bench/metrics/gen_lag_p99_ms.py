"""gen_lag_p99_ms: how late the load generator sent requests (host clock).

99th percentile of submit time minus due time, over the requests due
in the window before the profiler started (`Ctx.host_recs`). Layer: the
load generator (`bench/loop.py`).
"""

from bench.context import percentile


def read(ctx):
    return percentile(((r.submit - r.due) * 1e3 for r in ctx.host_recs()), 99)
