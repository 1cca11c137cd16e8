"""The measured window: one thread drives the engine on the host clock.

Each pass of the loop submits every request that is due, makes one
`engine.step()`, and sleeps only when the engine has nothing to do and
nothing is due. Tokens are stamped with `perf_counter` as the engine
delivers them (`on_token`). Requests are timed from when they were due,
not from when `submit()` ran, so a late generator shows as latency.

After the window closes nothing new is sent; requests due inside it are
drained, up to `drain_s` past the close. One that has not finished by
then has failed.

A traced run also starts the profiler for the last `trace_s` seconds of
the window and puts host spans (`jax.profiler.TraceAnnotation`) around
submit, each engine step and the generator's sleep.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, List, Optional

import jax

now = time.perf_counter


@dataclasses.dataclass
class Rec:
    """What the harness saw of one request."""

    due: float                       # host clock, when it was due
    prompt: object                   # np.ndarray of token ids
    n_out: int
    submit: float = 0.0
    admit: float = 0.0               # host clock, when its prefill began
    stamps: List[float] = dataclasses.field(default_factory=list)
    handle: object = None            # the engine's Request, while it runs
    served: List[int] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.n_out


@dataclasses.dataclass
class StepRec:
    """One engine step: its host interval, what it decoded and prefilled."""

    t0: float
    t1: float
    tokens: int          # tokens its decode dispatch delivered
    ctx_sum: int         # over those tokens, the cache positions each read
    prefill_tokens: int  # real prompt tokens it prefilled


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    recs: List[Rec]
    steps: List[StepRec]
    trace_t0: float = 0.0
    trace_t1: float = 0.0
    waiting_at_close: int = 0    # requests still queued when it closed
    t_drained: float = 0.0       # when the drain ended


class Driver:
    """Load generator and engine loop for one window."""

    def __init__(self, engine, seconds: float, drain_s: float,
                 trace_dir: Optional[str] = None, trace_s: float = 0.0):
        self.engine = engine
        self.seconds = seconds
        self.drain_s = drain_s
        self.trace_dir = trace_dir
        self.trace_s = min(trace_s, seconds)
        self.recs: List[Rec] = []
        self.steps: List[StepRec] = []
        self._by_id = {}
        self._finished: List[Rec] = []
        self._decoded = self._ctx = self._prefilled = 0
        self._tracing = False
        self.trace_t0 = self.trace_t1 = 0.0
        self._waiting_at_close: Optional[int] = None
        inner = engine._start

        def start(r):
            rec = self._by_id.get(r.id)
            if rec is not None:
                rec.admit = now()
                self._prefilled += len(rec.prompt)
            return inner(r)
        engine._start = start

    def _span(self, name: str):
        if self.trace_dir:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _on_token(self, rec: Rec):
        def cb(r, tok):
            rec.stamps.append(now())
            if len(rec.stamps) > 1:
                # a decoded token attends over the prompt and the tokens
                # before it
                self._decoded += 1
                self._ctx += len(rec.prompt) + len(rec.stamps) - 1
            if len(rec.stamps) == rec.n_out:
                self._finished.append(rec)
        return cb

    def submit(self, due: float, req) -> None:
        rec = Rec(due=due, prompt=req.prompt, n_out=req.n_out)
        with self._span("submit"):
            rec.submit = now()
            rec.handle = self.engine.submit(req.prompt, req.n_out,
                                            on_token=self._on_token(rec))
        self._by_id[rec.handle.id] = rec
        self.recs.append(rec)

    def _step(self) -> None:
        self._decoded = self._ctx = self._prefilled = 0
        with self._span("engine.step"):
            t0 = now()
            self.engine.step()
            t1 = now()
        self.steps.append(StepRec(t0, t1, self._decoded, self._ctx,
                                  self._prefilled))

    def _sleep(self, until: float) -> None:
        with self._span("generator.sleep"):
            left = until - now()
            while left > 0:
                time.sleep(min(left, 0.002))
                left = until - now()

    def _busy(self) -> bool:
        return bool(self.engine.n_active or self.engine.n_waiting)

    def _tick(self, t_close: float) -> float:
        """Note the queue at the window's close; start or stop the profiler
        at its boundaries. Returns the next boundary (inf when none is
        left)."""
        t = now()
        if self._waiting_at_close is None and t >= t_close:
            self._waiting_at_close = self.engine.n_waiting
        if not self.trace_dir:
            return float("inf")
        start = t_close - self.trace_s
        if not self._tracing and start <= t < t_close and not self.trace_t0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # host spans, not every call
            self.trace_t0 = now()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
        elif self._tracing and t >= t_close:
            self._stop_trace()
        if not self.trace_t0:
            return start
        return t_close if self._tracing else float("inf")

    def _stop_trace(self) -> None:
        if self._tracing:
            self.trace_t1 = now()
            jax.profiler.stop_trace()
            self._tracing = False

    def _window(self, t_open: float, t_close: float) -> Window:
        self._stop_trace()
        if self._waiting_at_close is None:
            self._waiting_at_close = self.engine.n_waiting
        for rec in self.recs:
            # keep the served tokens; the engine's request (whose callback
            # holds this driver and so the engine) is let go
            rec.served = list(rec.handle.generated)
            rec.handle = None
        return Window(t_open, t_close, self.recs, self.steps,
                      self.trace_t0, self.trace_t1, self._waiting_at_close,
                      now())

    def run_open(self, reqs: List) -> Window:
        """Open loop: each request is sent when it is due."""
        t_open = now()
        t_close = t_open + self.seconds
        i = 0
        while now() < t_close + self.drain_s:
            boundary = self._tick(t_close)
            t = now()
            while i < len(reqs) and t_open + reqs[i].due_s <= t:
                self.submit(t_open + reqs[i].due_s, reqs[i])
                i += 1
            if self._busy():
                self._step()
            elif i < len(reqs):
                self._sleep(min(t_open + reqs[i].due_s, boundary))
            elif now() >= t_close:
                break
            else:
                self._sleep(min(t_close, boundary))
        return self._window(t_open, t_close)

    def run_closed(self, stream: Iterator, n_clients: int) -> Window:
        """Closed loop: each client sends its next request as soon as its
        previous one completes, until the window closes."""
        t_open = now()
        t_close = t_open + self.seconds
        for _ in range(n_clients):
            self.submit(t_open, next(stream))
        while now() < t_close + self.drain_s:
            self._tick(t_close)
            done, self._finished = self._finished, []
            for rec in done:
                if rec.stamps[-1] < t_close:
                    self.submit(rec.stamps[-1], next(stream))
            if not self._busy():
                break
            self._step()
        return self._window(t_open, t_close)
