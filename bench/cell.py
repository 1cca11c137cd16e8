"""One run of one cell: set-up, the window, the drain, the check, the metrics.

`run` is the whole of a run after the chip check; `bench/run.py` calls it
with what `BENCHMARK.json` names, the tests call it with small
configurations on the CPU.
"""

from __future__ import annotations

import gc
import importlib
import shutil
import time
from pathlib import Path
from typing import Dict, List

from bench import check, loop, system, traffic, xplane
from bench.context import ABSENT, DECODE_PROGRAM, Ctx, percentile
from bench.peaks import peaks_for

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
DRAIN_S = 120.0         # past the window's close, for requests due in it
TRACE_S = 20.0          # the traced run profiles the window's last seconds


def load_metric(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def read_per_layer(ctx: Ctx, per_layer: List[Dict]) -> Dict[str, Dict]:
    """{name: {'value', 'unit'}} of the per-layer metrics a traced run
    reads. A metric whose reader finds nothing (None) is left out, except
    in a device capture, where the run fails: a program or kernel was not
    matched. One whose reader answers ABSENT, a program older than what
    it reads, is left out."""
    metrics = {}
    for spec in per_layer:
        value = load_metric(spec["name"]).read(ctx)
        if value is ABSENT:
            continue
        if value is None and ctx.plane:
            raise RuntimeError(f"{spec['name']}: nothing to read in the "
                               f"capture of {ctx.plane}")
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return metrics


def end_to_end(window: loop.Window, seconds: float) -> Dict[str, float]:
    """ttft_p90_ms, tpot_p90_ms and out_tok_s of one window."""
    recs = window.recs
    # a request with no token by the drain's end counts as waiting until
    # then: a lower bound of its time to first token
    ttft = [((r.stamps[0] if r.stamps else window.t_drained) - r.due) * 1e3
            for r in recs]
    tpot = [(r.stamps[-1] - r.stamps[0]) * 1e3 / (r.n_out - 1)
            for r in recs if r.done and r.n_out > 1]
    in_window = sum(1 for r in recs for t in r.stamps
                    if window.t_open <= t <= window.t_close)
    return {"ttft_p90_ms": percentile(ttft, 90),
            "tpot_p90_ms": percentile(tpot, 90),
            "out_tok_s": in_window / seconds}


def _configure_cache() -> None:
    import jax
    from repro.launch import compile_cache
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts XLA compilations while `armed` (none belong in the window)."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def run(config: Dict, mix: Dict, seed: int, seconds: float, trace: bool,
        t_start: float, e2e: List[Dict], per_layer: List[Dict],
        control: bool = False) -> Dict:
    """One run; returns the result's line as a dict. control=True (the
    control readings of `bench/control.py`) also reads the bfloat16
    reference's widest gap on the compared requests."""
    import jax
    _configure_cache()
    compiles = CompileCounter()
    model_cfg, eng = config["model"], config["engine"]
    if traffic.length_bound(mix) > eng["max_len"]:
        raise ValueError("the mix's longest request does not fit max_len")
    ref = check.reference(config)
    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if trace and dev.platform == "tpu" \
        else {}

    model = system.load_model(
        config, ref.make_weights(seed, model_cfg, config["kratos"]))
    engine = system.make_engine(config, model)
    lengths = traffic.prompt_lengths(mix, seconds)
    system.warm_up(engine, lengths, model_cfg["vocab"])
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
        system.annotate(engine)
    driver = loop.Driver(engine, seconds, DRAIN_S, trace_dir, TRACE_S)
    if mix["loop"] == "open":
        reqs = traffic.open_requests(mix, seconds, model_cfg["vocab"], seed)
    else:
        stream = traffic.closed_requests(mix, model_cfg["vocab"], seed)
    setup_s = time.perf_counter() - t_start
    compiles.armed = True
    if mix["loop"] == "open":
        window = driver.run_open(reqs)
    else:
        window = driver.run_closed(stream, traffic.n_clients(mix, eng["n_slots"]))
    compiles.armed = False

    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    del engine, model, driver
    gc.collect()

    verdict = check.judge(config, seed, window.recs, control)
    metrics: Dict[str, Dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"], "attempted": len(window.recs),
              "failed": sum(not r.done for r in window.recs)}
    notes: Dict = {}
    if trace:
        cap = xplane.load(xplane.find_xplane(str(TRACE_DIR)))
        plane = sorted(cap.ops)[0] if cap.ops else ""
        ctx = Ctx(config, mix, window, peaks, cap, plane)
        metrics.update(read_per_layer(ctx, per_layer))
        if plane:
            notes.update(
                traced_steps=len(ctx.traced_steps()),
                step_spans=len(ctx.step_spans()),
                decode_runs=[len(ctx.programs(DECODE_PROGRAM)), len(
                    xplane.programs(cap, plane, DECODE_PROGRAM))])
            lo, hi = xplane.window_ns(cap)
            device["busy_s"] = xplane.busy_ns(cap.ops[plane], lo, hi) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = xplane.breakdown(cap, plane, lo, hi)
    else:
        values = {**end_to_end(window, seconds), "setup_s": setup_s}
        for spec in e2e:
            metrics[spec["name"]] = {"value": values[spec["name"]],
                                     "unit": spec["unit"]}
    result.update(metrics=metrics, device=device)
    notes.update(compiles_in_window=compiles.count,
                 compared=verdict["compared"],
                 readings=verdict["readings"]["program"],
                 waiting_at_close=window.waiting_at_close)
    result["notes"] = notes
    if control:
        result["control"] = verdict.get("control")
        result["readings"] = verdict["readings"]
    result["checks"] = verdict["checks"]
    return result
