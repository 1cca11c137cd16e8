"""What a per-layer metric's reader is given: one run's records and capture.

A reader (`metrics/<name>.py`) defines `read(ctx)` and returns a number,
None when the run holds nothing for it to read, or ABSENT when the program
under test predates what the metric reads; the harness then leaves the
metric out of the line (and fails a traced run on the chip for a None).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench import system, xplane
from bench.loop import StepRec, Window

PREFILL_PROGRAM = "jit_prefill"
DECODE_PROGRAM = "jit_decode"


class _Absent:
    """A reader's answer for a program older than what its metric reads,
    such as a program that names no contract scope: the metric is left
    out of the line."""

    def __repr__(self) -> str:
        return "ABSENT"


ABSENT = _Absent()


@dataclasses.dataclass
class Ctx:
    config: Dict
    mix: Dict
    window: Window
    peaks: Dict
    capture: Optional[xplane.Capture] = None
    plane: str = ""

    @property
    def model(self) -> Dict:
        return self.config["model"]

    @property
    def kratos(self) -> Dict:
        return self.config["kratos"]

    @property
    def engine(self) -> Dict:
        return self.config["engine"]

    @property
    def adapter(self):
        """The configuration's architecture adapter (`system.adapter`)."""
        return system.adapter(self.config)

    def step_spans(self) -> List[xplane.Event]:
        """The `engine.step` host spans of the capture: one per traced
        step (`traced_steps`), on the capture's clock."""
        if self.capture is None:
            return []
        return [h for h in self.capture.host if h[0] == "engine.step"]

    def programs(self, name: str) -> List[xplane.Event]:
        """Runs of program `name` that ran within a traced engine step, so
        that their device time and the steps' token counts cover the same
        work (the engine waits for each program's result within its step)."""
        if self.capture is None:
            return []
        return xplane.centred_in(
            xplane.programs(self.capture, self.plane, name), self.step_spans())

    def traced_steps(self) -> List[StepRec]:
        """Engine steps that ran wholly while the profiler recorded; the
        profiler starts and stops between steps, so these are the steps
        whose spans the capture holds."""
        w = self.window
        if not w.trace_t1:
            return []
        return [s for s in w.steps if s.t0 >= w.trace_t0 and s.t1 <= w.trace_t1]

    def host_recs(self) -> List:
        """Requests due before the profiler was started: host-clock
        readers leave out the stall that starting it makes."""
        w = self.window
        if not w.trace_t0:
            return list(w.recs)
        return [r for r in w.recs if r.due < w.trace_t0]

    def ops_in(self, runs: List[xplane.Event]) -> List[xplane.Event]:
        if self.capture is None:
            return []
        return xplane.inside(self.capture.ops.get(self.plane, []), runs)



def percentile(values, q: float) -> Optional[float]:
    """numpy's linear percentile; None for no values; inf stays inf."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))
