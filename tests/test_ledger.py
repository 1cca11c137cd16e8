"""serve.ledger: device-resident ineffectual-work counters.

The contract under test, per the module docstring: zero-cost when
disabled (NULL_LEDGER allocates nothing), zero EXTRA host syncs when
enabled (the counter matrix drains inside the dispatch's existing token
device_get), step-clock deterministic (bit-identical counters and
histograms across identical runs), greedy-token-neutral (probes observe,
never perturb), and per-tier quality gauges that match an offline
recompute of the recorded probe log exactly.
"""

import gc
import sys

import numpy as np
import pytest

from repro.serve import (EngineConfig, InferenceEngine, LedgerConfig,
                         ModelRegistry, NULL_LEDGER, hist_checksum)
from repro.serve.ledger import (C_DEAD_KB, C_ELEMS, C_HIST, C_KBLOCKS,
                                C_NEAR, C_ZEROS, LedgerProbe, LedgerSink)

_REGISTRY = ModelRegistry()


def _trace(model, n=3, prompt=8, gen=6, seed=0):
    rng = np.random.default_rng(seed)
    return [(i, rng.integers(0, model.cfg.vocab, prompt), gen)
            for i in range(n)]


def _run(model, trace, *, ledger=None, temperature=0.0, decode_chunk=2,
         **cfg_kw):
    eng = InferenceEngine(model, EngineConfig(
        n_slots=2, max_len=48, decode_chunk=decode_chunk, ledger=ledger,
        **cfg_kw))
    reqs = [eng.submit(p, g, arrival_step=a, temperature=temperature)
            for a, p, g in trace]
    eng.run()
    return eng, reqs


# ---------------------------------------------------------------------------
# disabled: zero cost
# ---------------------------------------------------------------------------

def test_null_ledger_zero_alloc():
    """The disabled sink's hot-path calls (one per dispatch) allocate
    NOTHING — same contract and measurement idiom as NULL_TRACER."""
    led = NULL_LEDGER

    def hot_path():
        led.on_drain(None, 7)
        led.rebase()

    deltas = []
    for _ in range(3):
        hot_path()
        gc.collect()
        before = sys.getallocatedblocks()
        hot_path()
        deltas.append(sys.getallocatedblocks() - before)
    assert deltas[-1] == 0, f"disabled ledger allocated: deltas={deltas}"
    assert not led.enabled
    assert led.summary() == {}


def test_ledger_requires_device_loop():
    model = _REGISTRY.load("h2o-danube-1.8b")
    with pytest.raises(ValueError):
        InferenceEngine(model, EngineConfig(
            n_slots=2, max_len=48, device_loop=False,
            ledger=LedgerConfig()))


# ---------------------------------------------------------------------------
# probe math vs a numpy recompute
# ---------------------------------------------------------------------------

def test_probe_measure_matches_numpy():
    cfg = LedgerConfig(threshold=0.25, group=4, k_block=4)
    probe = LedgerProbe(cfg)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3, 16)).astype(np.float32)
    x[x < 0.4] = 0.0                       # plant plenty of exact zeros
    row = np.asarray(probe.measure(x, 8))

    near = np.abs(x) <= cfg.threshold
    assert row[C_ELEMS] == x.size
    assert row[C_ZEROS] == (x == 0.0).sum()
    assert row[C_NEAR] == near.sum()
    grouped = near.reshape(3, 4, 4).sum(axis=-1)
    hist = np.bincount(grouped.ravel(), minlength=cfg.group + 1)
    assert np.array_equal(row[C_HIST:], hist.astype(np.float32))
    dead = near.reshape(3, 4, 4).all(axis=-1)
    assert row[C_KBLOCKS] == dead.size
    assert row[C_DEAD_KB] == dead.sum()


def test_hist_checksum_orders():
    """The checksum must distinguish permuted histograms (it is the ONE
    scalar the qor gate uses for whole-matrix bit-determinism)."""
    a = np.zeros((2, C_HIST + 5))
    b = np.zeros((2, C_HIST + 5))
    a[0, C_HIST] = 3.0
    b[0, C_HIST + 2] = 3.0
    assert hist_checksum(a, 4) != hist_checksum(b, 4)


# ---------------------------------------------------------------------------
# engine integration: sync economy, determinism, neutrality
# ---------------------------------------------------------------------------

def test_ledger_no_extra_host_syncs():
    """host_syncs_decode must equal the dispatch count exactly — the
    ledger rides the existing token device_get — and must equal the
    disabled engine's count on the same trace."""
    model = _REGISTRY.load("nemotron-4-340b")
    trace = _trace(model)
    eng_off, _ = _run(model, trace)
    eng_on, _ = _run(model, trace, ledger=LedgerConfig())
    on, off = eng_on.metrics.report(), eng_off.metrics.report()
    assert on["host_syncs_decode"] == on["decode_steps"]
    assert on["host_syncs_decode"] == off["host_syncs_decode"]
    assert on["ledger_dispatches"] == on["decode_steps"]
    assert on["host_syncs_quality"] == 0      # quality_every=0


def test_ledger_greedy_tokens_unchanged():
    """Probes observe; they must not perturb the decoded stream."""
    model = _REGISTRY.load("nemotron-4-340b")
    trace = _trace(model)
    _, reqs_off = _run(model, trace)
    _, reqs_on = _run(model, trace, ledger=LedgerConfig())
    for r_off, r_on in zip(reqs_off, reqs_on):
        assert r_off.generated == r_on.generated


def test_ledger_step_clock_deterministic():
    """Two identical runs: every counter, per-layer fraction, and the
    full per-layer histogram matrix bit-identical."""
    model = _REGISTRY.load("nemotron-4-340b")
    trace = _trace(model)
    led = LedgerConfig(group=8, k_block=8)
    eng1, _ = _run(model, trace, ledger=led)
    eng2, _ = _run(model, trace, ledger=led)
    s1, s2 = eng1.ledger.summary(), eng2.ledger.summary()
    assert s1 == s2
    assert s1["act_zeros"] > 0               # squared-ReLU makes real zeros
    assert s1["act_hist_checksum"] == s2["act_hist_checksum"]


def test_ledger_measures_relu_zeros():
    model = _REGISTRY.load("nemotron-4-340b")
    eng, _ = _run(model, _trace(model), ledger=LedgerConfig(k_block=8))
    rep = eng.metrics.report()
    assert rep["act_zeros"] > 0
    assert 0.0 < rep["act_zero_fraction"] < 1.0
    assert rep["flops_effective"] <= rep["flops_dense"]
    assert rep["bytes_effective"] <= rep["bytes_dense"]
    s = eng.ledger.summary()
    # fixed traffic: probe totals reconcile between metrics and sink
    assert rep["act_probe_elems"] == s["act_probe_elems"]
    assert rep["act_zeros"] == s["act_zeros"]


def test_ledger_paged_matches_slab():
    """The paged dispatch carries the same ledger operand: greedy tokens
    must match the slab engine on the same trace, and the measured zero
    fractions must agree closely. Counters are NOT required to be
    bit-equal ACROSS layouts — paged gathers fuse differently, so
    borderline activations can differ by an ulp — but each layout must be
    bit-deterministic against itself (the qor gate always compares like
    with like)."""
    model = _REGISTRY.load("nemotron-4-340b")
    trace = _trace(model)
    led = LedgerConfig(k_block=8)
    eng_slab, reqs_slab = _run(model, trace, ledger=led)
    eng_paged, reqs_paged = _run(model, trace, ledger=led, page_size=8)
    for rs, rp in zip(reqs_slab, reqs_paged):
        assert rs.generated == rp.generated
    ss, sp = eng_slab.ledger.summary(), eng_paged.ledger.summary()
    assert sp["act_zeros"] > 0
    f_slab = ss["act_zeros"] / ss["act_probe_elems"]
    f_paged = sp["act_zeros"] / sp["act_probe_elems"]
    assert abs(f_slab - f_paged) < 0.01
    eng_paged2, _ = _run(model, trace, ledger=led, page_size=8)
    assert eng_paged2.ledger.summary() == sp


def test_ledger_speculative_counts_target_only():
    """Spec decode probes only the TARGET verify forwards (the draft is
    accounted analytically); the ledger must still drain once per
    dispatch and stay token-identical with the unledgered engine."""
    from repro.serve import DraftSpec
    model = _REGISTRY.load("nemotron-4-340b", draft_spec=DraftSpec(bits=8))
    trace = _trace(model)
    eng_off, reqs_off = _run(model, trace, speculate=2, decode_chunk=1)
    eng_on, reqs_on = _run(model, trace, speculate=2, decode_chunk=1,
                           ledger=LedgerConfig(k_block=8))
    for r_off, r_on in zip(reqs_off, reqs_on):
        assert r_off.generated == r_on.generated
    rep = eng_on.metrics.report()
    assert rep["ledger_dispatches"] == rep["spec_dispatches"]
    assert rep["act_zeros"] > 0
    assert rep["host_syncs_decode"] \
        == eng_off.metrics.report()["host_syncs_decode"]


# ---------------------------------------------------------------------------
# quality probes
# ---------------------------------------------------------------------------

def test_quality_gauges_match_offline_recompute():
    """The per-tier gauges must be EXACTLY recomputable from the probe
    log; on a single-tier engine the tier-0 shadow is the same compiled
    prefill, so agreement is exact (top1 rate 1.0, MAD 0.0)."""
    model = _REGISTRY.load("nemotron-4-340b")
    eng, _ = _run(model, _trace(model, n=4),
                  ledger=LedgerConfig(quality_every=2))
    rep = eng.metrics.report()
    assert rep["quality_probes"] == len(eng.quality_log) == 2
    assert rep["host_syncs_quality"] == 2 * rep["quality_probes"]
    # quality syncs are tracked separately: the decode invariant holds
    assert rep["host_syncs_decode"] == rep["decode_steps"]

    # offline recompute from the probe log
    by_tier = {}
    for e in eng.quality_log:
        t = by_tier.setdefault(e["tier"], [0, 0, 0.0])
        t[0] += 1
        t[1] += bool(e["top1"])
        t[2] += e["mad"]
    expect = {tier: {"probes": n, "top1_rate": hits / n, "logit_mad": m / n}
              for tier, (n, hits, m) in by_tier.items()}
    assert eng.metrics.quality_by_tier() == expect
    assert rep["quality_top1_rate"] == 1.0
    assert rep["quality_logit_mad"] == 0.0


def test_quality_probe_deterministic():
    model = _REGISTRY.load("nemotron-4-340b")
    led = LedgerConfig(quality_every=2)
    eng1, _ = _run(model, _trace(model, n=4), ledger=led)
    eng2, _ = _run(model, _trace(model, n=4), ledger=led)
    assert eng1.quality_log == eng2.quality_log


# ---------------------------------------------------------------------------
# sink accounting
# ---------------------------------------------------------------------------

def test_sink_delta_and_rebase():
    """on_drain computes per-dispatch deltas against the cumulative device
    matrix; rebase() re-zeroes the snapshot so totals keep growing."""
    cfg = LedgerConfig(group=2, k_block=2)
    sink = LedgerSink(cfg, n_layers=2)
    cum = np.zeros((2, cfg.width), np.float32)
    cum[0, C_ELEMS] = 10.0
    sink.on_drain(cum, step=1)
    cum2 = cum.copy()
    cum2[0, C_ELEMS] = 25.0
    sink.on_drain(cum2, step=2)
    assert sink.total[0, C_ELEMS] == 25.0
    sink.rebase()                    # device buffer was zeroed
    cum3 = np.zeros_like(cum)
    cum3[0, C_ELEMS] = 5.0
    sink.on_drain(cum3, step=3)
    assert sink.total[0, C_ELEMS] == 30.0


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the published table by device kind; an unknown kind
    raises instead of falling back to assumed numbers."""
    from repro.analysis import roofline as RL
    v5e = RL.peaks_for("TPU v5 lite")
    assert (v5e.peak_flops, v5e.peak_bw) == (197e12, 819e9) and v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        RL.peaks_for("cpu")
    # explicit peaks for a CPU-side check of the arithmetic
    peaks = RL.Peaks(peak_flops=100.0, peak_bw=10.0)
    mem = RL.roofline_point(50.0, 10.0, 1.0, peaks)      # intensity 5 < 10
    assert mem["bound"] == "memory" and mem["utilization"] == 1.0
    comp = RL.roofline_point(400.0, 10.0, 8.0, peaks)    # intensity 40
    assert comp["bound"] == "compute" and comp["utilization"] == 0.5
    rep = RL.replica_roofline({"flops_dense": 400.0, "flops_effective": 200.0,
                               "bytes_dense": 10.0, "bytes_effective": 5.0},
                              8.0, peaks=peaks)
    assert rep["skip_speedup_bound"] == 2.0
