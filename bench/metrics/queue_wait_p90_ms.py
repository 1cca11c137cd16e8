"""queue_wait_p90_ms: time from due to the start of prefill (host clock).

90th percentile over the requests due in the window before the profiler
started (`Ctx.host_recs`); one never admitted counts as +inf. Stamped by
the harness where the engine admits a request (`InferenceEngine._start`,
which runs its prefill). Layer: admission (`serve/engine.py`,
`serve/scheduler.py`).
"""

from bench.context import percentile


def read(ctx):
    return percentile(((r.admit - r.due) * 1e3 if r.admit else float("inf")
                       for r in ctx.host_recs()), 90)
