"""The scoped reading of a capture: the wire-format walk against
`xplane.load`, scope attribution by hand, the engine's spans in a CPU
capture, and the decode readers on two captures recorded on the chip: one
of a program that names its layers, and one of a program that does not."""

import gzip
import importlib
import json
import re
from pathlib import Path

import numpy as np
import pytest

from tiny_cell import ROOT
from bench import opscope, xplane
from bench.context import DECODE_PROGRAM, Ctx

DATA = Path(__file__).resolve().parent / "data"
PLANE = "/device:TPU:0"
CONFIG = json.loads((ROOT / "bench/configs/danube-1.8b-s50w8.json")
                    .read_text())
READERS = ("decode_attn_ms", "decode_cache_ms", "decode_unscoped_ms",
           "step_idle_ms")


def test_scope_of_takes_the_innermost_contract_name():
    assert opscope.scope_of(
        "jit(decode)/decode_loop/while/body/closed_call/layer_scan/while/"
        "body/closed_call/attn_core/bhqk,bhkd->bhqd/dot_general") \
        == "attn_core"
    assert opscope.scope_of(
        "jit(decode)/decode_loop/while/body/closed_call/layer_scan/while/"
        "body/closed_call/gemm/wq/pallas_call") == "gemm"
    assert opscope.scope_of("jit(decode)/decode_loop/while") == "decode_loop"
    assert opscope.scope_of(
        "jit(decode)/decode_loop/while/body/closed_call/gemm/wo/reshape;"
        "attn_core/reshape;attn_core/transpose") == "gemm"
    assert opscope.scope_of("jit(decode)/while/body/squeeze") == ""
    assert opscope.scope_of("") == ""


def test_split_leaves_out_containers_and_counts_the_rest():
    ops = [("%while.3 = (f32[2]) while(...)", 0, 100, "jit(decode)/while"),
           ("%fusion.1 = f32[2] fusion(...)", 0, 30, "a/attn_core/mul"),
           ("%copy.2 = f32[2] copy(...)", 30, 50, ""),
           ("%fusion.4 = f32[2] fusion(...)", 50, 90, "a/kv_write/scatter")]
    split = opscope.split_ns(ops)
    assert split["attn_core"] == 30 and split[""] == 20
    assert split["kv_write"] == 40 and sum(split.values()) == 90


def test_idle_ns_by_hand():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    spans = [("serve.step", 0, 25), ("serve.step", 28, 50)]
    # 20-25 in the first span; 28-30 and 40-50 in the second
    assert opscope.idle_ns(ops, spans) == 5 + 2 + 10
    assert opscope.idle_ns(ops, []) == 0


def test_the_benchmark_copy_of_the_contract_matches_the_program():
    from repro.instrument import SCOPES
    from repro.serve.trace import SPANS
    assert opscope.SCOPES == SCOPES
    assert all(s.startswith(opscope.SPAN_PREFIX) for s in SPANS)


# -- a capture of a tiny engine run on the CPU: the engine's own spans


@pytest.fixture(scope="module")
def cpu_capture(tmp_path_factory):
    import jax
    from repro.serve import EngineConfig, InferenceEngine, ModelRegistry
    engine = InferenceEngine(ModelRegistry().load("h2o-danube-1.8b"),
                             EngineConfig(n_slots=2, max_len=48,
                                          decode_chunk=2))
    prompts = [np.arange(1, 6), np.arange(3, 12), np.arange(2, 9)]
    for p in prompts:                   # compile outside the capture
        engine.submit(p, 4)
    engine.run()
    out = tmp_path_factory.mktemp("cpu_trace")
    jax.profiler.start_trace(str(out))
    try:
        for i, p in enumerate(prompts):
            engine.submit(p, 5, arrival_step=engine.step_count + i)
        engine.run()
    finally:
        jax.profiler.stop_trace()
    return opscope.load(xplane.find_xplane(str(out))), len(prompts)


def test_cpu_capture_holds_the_engine_spans_nested_in_steps(cpu_capture):
    scoped, n_requests = cpu_capture
    names = [s[0] for s in scoped.spans]
    assert set(names) == {"serve.step", "serve.admit", "serve.prefill",
                          "serve.first_token", "serve.decode", "serve.emit"}
    assert names.count("serve.admit") == n_requests
    steps = [s for s in scoped.spans if s[0] == "serve.step"]
    inner = [s for s in scoped.spans if s[0] != "serve.step"]
    assert xplane.inside(inner, steps) == inner
    # each admission: admit, then its prefill, then its first token
    for phase in ("serve.admit", "serve.prefill", "serve.first_token"):
        assert names.count(phase) == n_requests
    for a, p, f in zip(*([s for s in scoped.spans if s[0] == phase]
                         for phase in ("serve.admit", "serve.prefill",
                                       "serve.first_token"))):
        assert a[2] <= p[1] and p[2] <= f[1]
    # the CPU has no TPU plane: no device ops to read
    assert scoped.ops == {}


# -- captures recorded on one TPU v5e (`record_trace.py`): the
# danube-1.8b-s50w8 engine serving three requests for one second, before
# the program named its layers (s50w8) and after (s50w8-scoped)


def _unpack(name, tmp_path_factory):
    d = tmp_path_factory.mktemp(name)
    path = d / f"{name}.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{name}.xplane.pb.gz").read_bytes()))
    return name, d, str(path)


@pytest.fixture(scope="module", params=["s50w8", "s50w8-scoped"])
def recorded(request, tmp_path_factory):
    return _unpack(request.param, tmp_path_factory)


@pytest.fixture(scope="module")
def scoped_recorded(tmp_path_factory):
    return _unpack("s50w8-scoped", tmp_path_factory)


def test_opscope_ops_match_xplane_load(recorded):
    _, _, path = recorded
    cap, scoped = xplane.load(path), opscope.load(path)
    assert set(scoped.ops) == set(cap.ops) == {PLANE}
    assert [op[:3] for op in scoped.ops[PLANE]] == cap.ops[PLANE]


def _read(recorded, monkeypatch):
    from bench import cell
    name, d, path = recorded
    monkeypatch.setattr(cell, "TRACE_DIR", d)
    cap = xplane.load(path)
    ctx = Ctx(CONFIG, {}, None, {}, cap, PLANE)
    values = {m: importlib.import_module(f"bench.metrics.{m}").read(ctx)
              for m in READERS}
    return name, ctx, values


def test_decode_readers_cover_the_decode_programs(recorded, monkeypatch):
    name, ctx, values = _read(recorded, monkeypatch)
    split = opscope.decode_split_ms(ctx)
    runs = ctx.programs(DECODE_PROGRAM)
    per = len(runs) * CONFIG["engine"]["decode_chunk"]
    program_ms = sum(e - s for _, s, e in runs) / 1e6 / per
    # every non-container op of the decode runs falls in one bucket, and
    # those ops fill the programs' device time to within 1%
    assert sum(split.values()) == pytest.approx(program_ms, rel=0.01)
    assert all(v is not None and v >= 0.0 for v in values.values())
    assert values["step_idle_ms"] > 0.0
    if name == "s50w8":
        # a program that names no scope: all of its time reads unscoped
        assert values["decode_attn_ms"] == values["decode_cache_ms"] == 0.0
        assert values["decode_unscoped_ms"] == sum(split.values())
    else:
        assert values["decode_attn_ms"] > 0.0
        assert values["decode_cache_ms"] > 0.0
        assert split["gemm"] > 0.0
        assert values["decode_unscoped_ms"] < 0.2 * sum(split.values())


def test_scoped_capture_names_the_gemm_kernels_and_the_steps(
        scoped_recorded, monkeypatch):
    """The packed GEMM kernels that `gemm_roofline` matches by operand
    types all lie under the `gemm` scope, and the engine's step spans hold
    its admissions and dispatches."""
    _, ctx, _ = _read(scoped_recorded, monkeypatch)
    scoped = opscope.for_ctx(ctx)
    runs = ctx.programs(DECODE_PROGRAM)
    pattern = re.compile(CONFIG["gemm_ops"])
    kernels = [op for op in xplane.inside(scoped.ops[PLANE], runs)
               if pattern.search(op[0])]
    assert kernels
    assert {opscope.scope_of(op[3]) for op in kernels} == {"gemm"}
    names = {s[0] for s in scoped.spans}
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.decode",
            "serve.emit"} <= names
    steps = [s for s in scoped.spans if s[0] == "serve.step"]
    inner = [s for s in scoped.spans if s[0] != "serve.step"]
    assert xplane.inside(inner, steps) == inner


def test_scoped_capture_puts_the_slab_attention_under_attn_core(
        scoped_recorded, monkeypatch):
    """Which scope the chip's ops carry, not only some: the K/V broadcast
    to the query heads and the scores over every slot's positions, the
    largest ops of `decode_attn_ms`, lie under `attn_core`, and the
    layer scan's write-back of the stacked K/V slab under `layer_scan`."""
    _, ctx, _ = _read(scoped_recorded, monkeypatch)
    m, e = CONFIG["model"], CONFIG["engine"]
    kv, group = m["n_kv_heads"], m["n_heads"] // m["n_kv_heads"]
    dims = f'{e["n_slots"]},{kv},{group},{e["max_len"]}'
    ops = xplane.inside(opscope.for_ctx(ctx).ops[PLANE],
                        ctx.programs(DECODE_PROGRAM))

    def scopes(pattern):
        found = {opscope.scope_of(op[3]) for op in ops
                 if re.search(pattern, op[0])}
        assert found, pattern
        return found

    assert scopes(rf"= f32\[{dims},{m['head_dim']}\]") == {"attn_core"}
    assert scopes(rf"= f32\[{dims}\]") == {"attn_core"}
    slab = f'{m["n_layers"]},{e["n_slots"]},{kv},{e["max_len"]},' \
        f'{m["head_dim"]}'
    assert scopes(rf"dynamic-update-slice_fusion[.\d]* = f32\[{slab}\]") \
        == {"layer_scan"}
