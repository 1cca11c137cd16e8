"""The trace reduction: interval arithmetic and gap naming by hand, and
busy time, programs and GEMM ops in a capture recorded on the chip."""

import gzip
import json
import re
from pathlib import Path

import pytest

from tiny_cell import ROOT
from bench import xplane
from bench.context import DECODE_PROGRAM, PREFILL_PROGRAM


def test_merge_and_busy_by_hand():
    evs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 35, 38)]
    assert xplane.merge(((s, e) for _, s, e in evs), 0, 100) == \
        [(0, 20), (30, 40)]
    assert xplane.busy_ns(evs, 0, 100) == 30
    assert xplane.busy_ns(evs, 8, 32) == 14          # clipped to the window
    assert xplane.gaps(evs, 0, 50) == [(20, 30), (40, 50)]


def test_inside_keeps_only_contained_events():
    spans = [("p", 0, 10), ("p", 20, 30)]
    evs = [("x", 1, 2), ("y", 9, 11), ("z", 21, 29), ("w", 31, 32)]
    assert [e[0] for e in xplane.inside(evs, spans)] == ["x", "z"]


def test_centred_in_keeps_events_whose_midpoint_is_in_a_span():
    spans = [("p", 0, 10), ("p", 20, 30)]
    evs = [("x", 1, 2), ("y", 8, 11), ("z", 18, 23), ("w", 27, 40)]
    assert [e[0] for e in xplane.centred_in(evs, spans)] == ["x", "y", "z"]


def test_idle_gaps_are_named_by_the_open_host_span():
    cap = xplane.Capture(
        ops={"/device:TPU:0": [("fusion", 0, 10), ("fusion", 50, 60)]},
        modules={},
        host=[("engine.step", 0, 100), ("decode_dispatch", 40, 60)])
    b = xplane.breakdown(cap, "/device:TPU:0", 0, 100)
    assert b["device_ops"] == [["fusion", 20e-9]]
    assert b["idle_gaps"][0] == ["engine.step", 40e-9]
    assert ["decode_dispatch", 40e-9] not in b["idle_gaps"]



# -- a capture recorded on one TPU v5e (`record_trace.py`): the
# danube-1.8b-s50w8 engine serving three requests for one second

RECORDED = Path(__file__).resolve().parent / "data" / "s50w8.xplane.pb.gz"
PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("capture") / "s50w8.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return xplane.load(str(path))


def test_recorded_busy_union_by_hand(recorded):
    ops = recorded.ops[PLANE]
    lo, hi = xplane.window_ns(recorded)
    # a loop's event spans the ops it runs, so ops nest: count busy time
    # by a sweep over every start and end
    edges = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in ops if e > lo and s < hi])
    by_hand, depth, last = 0, 0, lo
    for t, step in edges:
        if depth:
            by_hand += t - last
        depth, last = depth + step, t
    assert xplane.busy_ns(ops, lo, hi) == by_hand
    assert 0 < by_hand <= hi - lo
    assert by_hand < sum(e - s for _, s, e in ops)


def test_recorded_programs_lie_inside_the_engine_steps(recorded):
    steps = [h for h in recorded.host if h[0] == "engine.step"]
    decode = xplane.programs(recorded, PLANE, DECODE_PROGRAM)
    prefill = xplane.programs(recorded, PLANE, PREFILL_PROGRAM)
    assert steps and decode and prefill
    assert xplane.inside(decode, steps) == sorted(decode, key=lambda e: e[1])
    assert xplane.centred_in(decode, steps) == decode
    # one fused decode dispatch per step that decoded, never more
    assert len(decode) <= len(steps)
    # a decode program's device time is its ops' time: the device is
    # busy through nearly all of it
    for name, s, e in decode:
        inner = xplane.inside(recorded.ops[PLANE], [(name, s, e)])
        assert 0.9 * (e - s) < xplane.busy_ns(inner, s, e) <= e - s


@pytest.mark.parametrize("config", ["danube-1.8b-s50w8",
                                    "danube-1.8b-dense"])
def test_recorded_gemm_ops_are_matched_by_the_configuration(recorded, config):
    """The s50w8 engine's decode runs its four packed projection shapes
    (wq, wk/wv, w_gate/w_up, w_down and wo share two) through the block-
    sparse kernel, and no op that the dense pattern matches."""
    pattern = re.compile(json.loads(
        (ROOT / f"bench/configs/{config}.json").read_text())["gemm_ops"])
    decode = xplane.programs(recorded, PLANE, DECODE_PROGRAM)
    ops = [op for op in xplane.inside(recorded.ops[PLANE], decode)
           if pattern.search(op[0])]
    per_run = 24 * 7 * 4        # layers x projections x micro-steps
    assert len(ops) == (len(decode) * per_run
                        if config == "danube-1.8b-s50w8" else 0)
