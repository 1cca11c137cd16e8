"""serve.trace + serve.telemetry: zero-cost disabled path, span/metrics
reconciliation, Chrome + JSONL exports, ring buffer, page events, live
telemetry registry + Prometheus endpoint."""

import gc
import json
import sys
import urllib.request

import numpy as np
import pytest

from repro.serve import (EngineConfig, InferenceEngine, ModelRegistry,
                         NULL_TRACER, ReplicaRouter, TelemetryConfig,
                         TelemetryExporter, TelemetryRegistry, TraceConfig,
                         Tracer, engine_sample, export_chrome, export_jsonl,
                         router_sample)
from repro.serve.trace import SPANS, NullTracer, chrome_events

ARCH = "h2o-danube-1.8b"
_REGISTRY = ModelRegistry()


def _model():
    return _REGISTRY.load(ARCH)


def _engine(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    return InferenceEngine(_model(), EngineConfig(**kw))


# ---------------------------------------------------------------------------
# disabled path: zero cost
# ---------------------------------------------------------------------------

def test_engine_defaults_to_null_tracer():
    eng = _engine()
    assert eng.trace is NULL_TRACER
    assert not eng.trace.enabled
    assert eng.pool.tracer is NULL_TRACER


def test_null_tracer_zero_alloc():
    """The disabled hot path allocates NOTHING per dispatch: fixed-arity
    no-op methods, no *args packing, call sites pass pre-existing values.
    The first measured pass may warm CPython's adaptive specialization, so
    the assertion is on the steady-state (last) measurement."""
    t = NULL_TRACER

    def hot_path():
        # one dispatch's worth of disabled-tracer traffic
        t.step = 7
        t.dispatch_begin()
        t.decode_dispatch(4, 2, 2)
        t.host_sync("decode", 32)
        t.first_token(1, 0, 3)
        t.finish(1, 0, 9, 6)
        t.submit(1, 2, 3)
        t.admit_begin()
        t.admit(1, 0, 0, 4)
        t.prefill(1, 0, 4, 8, False)
        t.pool_wait()
        t.page_alloc(0, 1, 2)
        t.page_free(0, 3)
        t.page_evict(1)
        t.spec_dispatch(4, 2, 2)
        t.spec_slot(0, 3, 4, 4)
        t.reject(5)
        # resilience hooks (PR 7) ride the same zero-alloc contract
        t.tier_change(0, 1, 9)
        t.req_tier(1, 1)
        t.shed(1, 0, "deadline", 3)
        t.failover(1, 0)
        t.fault("crash", "injected")

    deltas = []
    for _ in range(3):
        hot_path()
        gc.collect()
        before = sys.getallocatedblocks()
        hot_path()
        deltas.append(sys.getallocatedblocks() - before)
    assert deltas[-1] == 0, f"disabled tracer allocated: deltas={deltas}"


def test_null_tracer_returns_empty_views():
    assert NULL_TRACER.request_spans() == {}
    assert NULL_TRACER.export() is None
    assert isinstance(NULL_TRACER, NullTracer)
    assert Tracer(TraceConfig()).enabled       # and the real one is on


# ---------------------------------------------------------------------------
# spans reconcile exactly with ServeMetrics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("decode_chunk", [1, 3])
def test_spans_reconcile_with_metrics(decode_chunk):
    """Step-clock span fields == the metrics records, including at K>1
    where micro-steps advance the emission clock between dispatches."""
    eng = _engine(decode_chunk=decode_chunk, trace=TraceConfig())
    reqs = [eng.submit([1, 2, 3, 4, 5], 6),
            eng.submit([7, 8, 9], 4, arrival_step=1),
            eng.submit([5, 4, 3, 2], 5, arrival_step=2)]
    eng.run()
    spans = eng.trace.request_spans()
    assert len(spans) == len(reqs)
    for r in reqs:
        s, rec = spans[r.id], eng.metrics.records[r.id]
        assert s["ttft_steps"] == rec.first_token_step - rec.arrival_step
        assert s["latency_steps"] == rec.finish_step - rec.arrival_step
        assert s["queue_steps"] == rec.start_step - rec.arrival_step
        assert s["tokens"] == rec.n_generated == len(r.generated)
        assert s["n_prompt"] == rec.n_prompt
        assert s["first_token_step"] == rec.first_token_step
        assert s["finish_step"] == rec.finish_step
        # wall spans are intervals on the monotonic clock: non-negative
        assert s["ttft_s"] >= 0.0 and s["latency_s"] >= s["ttft_s"]


def test_trace_counts_match_metrics_counters():
    eng = _engine(decode_chunk=2, trace=TraceConfig())
    eng.submit([1, 2, 3], 4)
    eng.submit([4, 5], 3, arrival_step=1)
    eng.run()
    evs = list(eng.trace.events)
    by_kind = {}
    for ev in evs:
        by_kind.setdefault(ev["ev"], []).append(ev)
    assert len(by_kind["decode"]) == eng.metrics.decode_steps
    assert len(by_kind["submit"]) == 2
    assert len(by_kind["admit"]) == eng.metrics.prefills
    assert len(by_kind["finish"]) == 2
    syncs = sum(1 for e in by_kind["host_sync"] if e["kind"] == "decode")
    assert syncs == eng.metrics.host_syncs["decode"]
    # every decode dispatch recorded its duration and occupancy
    for e in by_kind["decode"]:
        assert e["dur"] >= 0.0 and 0.0 < e["occupancy"] <= 1.0
        assert e["k"] == 2


def test_admit_and_prefill_events_time_their_phases():
    """`admit` is stamped where the admission starts and pushed before the
    prefill dispatch; `prefill` starts where admission ends and carries its
    duration, as `decode` does."""
    eng = _engine(trace=TraceConfig())
    eng.submit([1, 2, 3, 4, 5], 3)
    eng.run()
    evs = list(eng.trace.events)
    kinds = [e["ev"] for e in evs]
    i_admit, i_prefill = kinds.index("admit"), kinds.index("prefill")
    i_first = kinds.index("first_token")
    assert kinds.index("submit") < i_admit < i_prefill < i_first
    submit, admit = evs[kinds.index("submit")], evs[i_admit]
    prefill, first = evs[i_prefill], evs[i_first]
    assert submit["t"] <= admit["t"]
    assert admit["dur"] >= 0.0 and prefill["dur"] > 0.0
    assert prefill["t"] == admit["t"] + admit["dur"]
    assert prefill["t"] + prefill["dur"] <= first["t"]


def test_ring_events_that_time_a_phase_are_named_as_its_span():
    timed = {"admit", "prefill", "first_token", "decode", "spec"}
    assert {f"serve.{k}" for k in timed} | {"serve.step", "serve.emit"} \
        == set(SPANS)
    eng = _engine(decode_chunk=2, trace=TraceConfig())
    eng.submit([1, 2, 3], 4)
    eng.run()
    kinds = {e["ev"] for e in eng.trace.events}
    assert timed - {"spec"} <= kinds


def test_format_timeline_mentions_the_numbers():
    eng = _engine(trace=TraceConfig())
    r = eng.submit([1, 2, 3], 4)
    eng.run()
    text = eng.trace.format_timeline(r.id)
    assert f"req{r.id}" in text
    assert "ttft" in text and "generated 4 tokens" in text
    assert "no events retained" in eng.trace.format_timeline(999)


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------

def test_export_jsonl_schema(tmp_path):
    eng = _engine(trace=TraceConfig())
    eng.submit([1, 2, 3], 3)
    eng.run()
    path = str(tmp_path / "trace.jsonl")
    n = export_jsonl([eng.trace], path)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["ev"] == "meta"
    assert lines[0]["dropped"] == 0 and "clocks" in lines[0]
    assert len(lines) == n + 1                      # meta + events
    for ev in lines[1:]:
        assert "step" in ev and "t" in ev and ev["replica"] == 0


def test_export_chrome_reconciles_with_metrics(tmp_path):
    """The Chrome trace's per-request span args carry the SAME step-clock
    numbers ServeMetrics reports — the acceptance criterion that the trace
    is a richer view of the same events, not a second bookkeeping."""
    eng = _engine(decode_chunk=2, trace=TraceConfig())
    reqs = [eng.submit([1, 2, 3, 4], 5), eng.submit([9, 8], 4,
                                                    arrival_step=1)]
    eng.run()
    path = str(tmp_path / "trace.json")
    n = export_chrome([eng.trace], path)
    doc = json.load(open(path))
    assert n == len(doc["traceEvents"]) > 0
    req_spans = {e["name"]: e for e in doc["traceEvents"]
                 if e.get("cat") == "request"}
    assert len(req_spans) == len(reqs)
    for r in reqs:
        rec = eng.metrics.records[r.id]
        args = req_spans[f"req{r.id}"]["args"]
        assert args["ttft_steps"] == rec.first_token_step - rec.arrival_step
        assert args["latency_steps"] == rec.finish_step - rec.arrival_step
        assert args["tokens"] == len(r.generated)
        assert args["n_prompt"] == rec.n_prompt
    # structure: process metadata + dispatch track + occupancy counters
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "C", "i"} <= phases


def test_chrome_events_one_process_per_replica():
    eng0 = _engine(trace=TraceConfig())
    eng1 = _engine(trace=TraceConfig())
    router = ReplicaRouter([eng0, eng1])
    router.submit([1, 2, 3], 3)
    router.submit([4, 5, 6], 3)
    router.run()
    assert [t.replica for t in router.tracers] == [0, 1]
    evs = [e for t in router.tracers for e in chrome_events(t)]
    assert {e["pid"] for e in evs} == {0, 1}


def test_ring_buffer_caps_and_counts_drops():
    tr = Tracer(TraceConfig(capacity=4))
    for i in range(10):
        tr.host_sync("decode", 4)
    assert len(tr.events) == 4
    assert tr.dropped == 6
    # a request whose submit edge fell off the ring is omitted from spans
    tr2 = Tracer(TraceConfig(capacity=2))
    tr2.submit(1, 3, 0)
    for _ in range(3):
        tr2.host_sync("decode", 4)
    assert tr2.request_spans() == {}


def test_tracer_export_uses_config_paths(tmp_path):
    out = str(tmp_path / "a.jsonl")
    chrome = str(tmp_path / "b.json")
    eng = _engine(trace=TraceConfig(out=out, chrome=chrome))
    eng.submit([1, 2], 3)
    eng.run()
    eng.trace.export()
    assert json.loads(open(out).readline())["ev"] == "meta"
    assert "traceEvents" in json.load(open(chrome))


# ---------------------------------------------------------------------------
# page events (paged pool)
# ---------------------------------------------------------------------------

def test_paged_engine_emits_page_events():
    eng = InferenceEngine(_model(), EngineConfig(
        n_slots=2, max_len=64, page_size=8, trace=TraceConfig()))
    assert eng.pool.tracer is eng.trace
    eng.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    eng.run()
    kinds = {e["ev"] for e in eng.trace.events}
    assert "page_alloc" in kinds and "page_free" in kinds
    allocs = [e for e in eng.trace.events if e["ev"] == "page_alloc"]
    assert all(e["fresh"] >= 0 and e["shared"] >= 0 for e in allocs)


# ---------------------------------------------------------------------------
# speculative events
# ---------------------------------------------------------------------------

def test_speculative_engine_emits_spec_events():
    from repro.serve import DraftSpec
    model = _REGISTRY.load("nemotron-4-340b", draft_spec=DraftSpec(bits=8))
    eng = InferenceEngine(model, EngineConfig(
        n_slots=2, max_len=48, speculate=3, trace=TraceConfig()))
    r = eng.submit([1, 2, 3], 6)
    eng.run()
    kinds = {e["ev"] for e in eng.trace.events}
    assert "spec" in kinds and "spec_slot" in kinds
    slots = [e for e in eng.trace.events if e["ev"] == "spec_slot"]
    committed = sum(e["committed"] for e in slots
                    if e["slot"] == 0)
    assert committed >= len(r.generated) - 1    # first token from prefill
    for e in slots:
        assert 0 <= e["accepted"] <= e["proposed"]
        assert e["rolled_back"] == e["proposed"] - e["accepted"]


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_render():
    reg = TelemetryRegistry(prefix="t")
    reg.counter("toks").set(42)
    reg.gauge("depth").set(3.5)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render_prometheus()
    assert "# TYPE t_toks counter" in text and "t_toks 42" in text
    assert "t_depth 3.5" in text
    assert 't_lat_bucket{le="0.1"} 1' in text
    assert 't_lat_bucket{le="1"} 2' in text
    assert 't_lat_bucket{le="+Inf"} 3' in text and "t_lat_count 3" in text
    snap = reg.snapshot()
    assert snap["toks"] == 42 and snap["lat_count"] == 3.0
    with pytest.raises(AssertionError):
        reg.gauge("toks")                  # kind mismatch refuses


def test_engine_sample_and_jsonl(tmp_path):
    eng = _engine()
    eng.submit([1, 2, 3], 4)
    eng.run()
    jsonl = str(tmp_path / "tele.jsonl")
    exp = TelemetryExporter(lambda: engine_sample(eng),
                            TelemetryConfig(jsonl=jsonl))
    s = exp.sample()
    assert s["tokens_generated"] == 4.0
    assert s["n_slots"] == 2.0 and s["n_active"] == 0.0
    line = json.loads(open(jsonl).readline())
    assert line["sample"] == 1 and line["tokens_generated"] == 4.0
    # counter keys landed as counters, point-in-time keys as gauges
    assert exp.registry.counter("tokens_generated").value == 4.0
    assert exp.registry.gauge("mean_occupancy").value > 0.0


def test_router_sample_exposes_replica_depths():
    router = ReplicaRouter([_engine(), _engine()])
    router.submit([1, 2], 3)
    router.run()
    s = router_sample(router)
    assert s["n_replicas"] == 2.0
    assert "replica0_n_waiting" in s and "replica1_n_active" in s
    assert s["overflow_depth"] == 0.0


def test_prometheus_http_endpoint():
    eng = _engine()
    eng.submit([1, 2, 3], 3)
    eng.run()
    exp = TelemetryExporter(lambda: engine_sample(eng),
                            TelemetryConfig(interval=30.0, port=0))
    exp.start()
    try:
        assert exp.port and exp.port > 0
        url = f"http://127.0.0.1:{exp.port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "serve_tokens_generated 3" in body
        assert "# TYPE serve_tokens_generated counter" in body
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=10)
    finally:
        exp.stop()
    # stop() tore the server down
    with pytest.raises(Exception):
        urllib.request.urlopen(url, timeout=2)


# ---------------------------------------------------------------------------
# traced run stays token-identical
# ---------------------------------------------------------------------------

def test_tracing_does_not_change_tokens():
    prompts = [([1, 2, 3, 4], 5), ([9, 8, 7], 4)]
    outs = []
    for trace in (None, TraceConfig()):
        eng = _engine(decode_chunk=2, trace=trace)
        reqs = [eng.submit(p, g, arrival_step=i)
                for i, (p, g) in enumerate(prompts)]
        eng.run()
        outs.append([list(r.generated) for r in reqs])
    assert outs[0] == outs[1]


def test_profiler_bracket_captures_or_raises(tmp_path):
    """An asked-for profiler capture either lands on disk or raises: it is
    never skipped in silence."""
    import jax
    eng = _engine(trace=TraceConfig(profile_dir=str(tmp_path / "ok"),
                                    profile_dispatches=1))
    eng.submit(np.arange(5), 3)
    eng.run()
    evs = [e["ev"] for e in eng.trace.events]
    assert "profile_start" in evs and "profile_stop" in evs
    assert list((tmp_path / "ok").rglob("*.xplane.pb"))

    # a second capture cannot start while one is running
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        eng = _engine(trace=TraceConfig(profile_dir=str(tmp_path / "inner"),
                                        profile_dispatches=1))
        eng.submit(np.arange(5), 3)
        with pytest.raises(RuntimeError):
            eng.run()
    finally:
        jax.profiler.stop_trace()
