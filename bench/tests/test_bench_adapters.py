"""The harness builds the program from the configuration's files alone: its
ModelConfig from the published one and the stated cut, its parameter tree
and its work counts from the architecture's adapter
(`bench/adapters/<reference>.py`)."""

import json
import time

import pytest

from tiny_cell import ROOT, TINY_MODEL, cut, tiny
from bench import check, system, workcount
from bench.adapters import gqa_decoder
from bench.peaks import peaks_for

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ENTRIES = {c["name"]: c for c in BENCH["configs"]}
FILES = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
CELL = "danube-s50w8.chat"


def _file(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _changed(config):
    """Keys of the file's `model` that differ from the published config."""
    from repro import configs as C
    published = C.get_config(config["arch"])
    return {k for k, v in config["model"].items()
            if getattr(published, k) != v}


def test_danube_builds_the_published_config_and_tree():
    import jax
    from repro import configs as C
    from repro.models import transformer as T
    config = _file("danube-1.8b-s50w8")
    cfg = system.program_config(config)
    assert cfg == C.get_config("h2o-danube-1.8b")
    # at full size, as shapes: the served tree is the program's own
    ref = check.reference(config)
    w = jax.eval_shape(lambda: ref.make_weights(1, config["model"],
                                                config["kratos"]))
    tree = system.adapter(config).program_tree(w, config["model"])
    init = jax.eval_shape(lambda k: T.init(k, cfg), jax.random.PRNGKey(0))
    shape = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
    assert shape(tree) == shape(init)
    # on real (tiny) weights every leaf is the reference's own buffer
    small, _, _, _ = tiny(CELL)
    w = ref.make_weights(1, small["model"], small["kratos"])
    leaves = jax.tree_util.tree_leaves(
        system.adapter(small).program_tree(w, small["model"]))
    own = {id(x) for x in jax.tree_util.tree_leaves(w)}
    assert len(leaves) == len(own) and {id(x) for x in leaves} == own


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_entries_state_the_files_cut(name):
    config = json.loads((ROOT / ENTRIES[name]["file"]).read_text())
    assert ENTRIES[name]["reduced"] == config.get("reduced", [])
    assert _changed(config) <= set(ENTRIES[name]["reduced"])


@pytest.mark.parametrize("name", FILES)
def test_every_configuration_file_builds(name):
    config = _file(name)
    system.program_config(config)
    system.adapter(config)
    assert _changed(config) <= set(config.get("reduced", []))


def test_a_cut_in_depth_stated_in_reduced_is_served_and_correct():
    """A danube file cut to two layers runs through the tiny cell and is
    correct against a two-layer reference: the program serves the cut."""
    import jax
    from bench import cell
    jax.config.update("jax_enable_compilation_cache", False)
    from bench.run import load_cell
    _, config, mix, _, _ = load_cell(CELL)
    config, mix = cut(config, mix, model=dict(TINY_MODEL, n_layers=2))
    assert "n_layers" in config["reduced"]
    assert system.program_config(config).n_layers == 2
    r = cell.run(config, mix, 2**31 + 57, 1.5, False, time.perf_counter(),
                 [], [])
    assert r["correct"] is True and r["failed"] == 0
    assert r["notes"]["compared"]["tokens"] > 0


def _no_reduced(config):
    config.pop("reduced")
    return config, "n_layers"


def _unknown_key(config):
    config["model"]["n_expertz"] = 8
    config["reduced"].append("n_expertz")
    return config, "n_expertz"


def _no_adapter(config):
    config["reference"] = "no_such_decoder"
    return config, "bench/adapters/no_such_decoder.py"


@pytest.mark.parametrize("fault", [_no_reduced, _unknown_key, _no_adapter],
                         ids=["cut_not_in_reduced", "key_not_in_ModelConfig",
                              "reference_without_adapter"])
def test_a_file_that_does_not_state_its_cut_is_refused(fault):
    sound = tiny(CELL)[0]
    w = check.reference(sound).make_weights(1, sound["model"], {})
    config, named = fault(tiny(CELL)[0])
    with pytest.raises((ValueError, FileNotFoundError), match=named):
        system.load_model(config, w)


S50W8 = _file("danube-1.8b-s50w8")
DENSE = _file("danube-1.8b-dense")


# the parent's formulas' floats, bit for bit: gemm_roofline's numerator at
# the cell's 14 slots, and decode_mfu's
@pytest.mark.parametrize("count, expected", [
    (lambda: workcount.decode_gemm_least_s(
        gqa_decoder, S50W8["model"], S50W8["kratos"], 14,
        peaks_for("TPU v5 lite")), 0.0010947056996336996),
    (lambda: workcount.model_flops_per_token(
        gqa_decoder, S50W8["model"], S50W8["kratos"]), 1831075840.0),
    (lambda: gqa_decoder.attention_flops(S50W8["model"], 2048),
     503316480.0),
    (lambda: workcount.decode_gemm_least_s(
        gqa_decoder, DENSE["model"], DENSE["kratos"], 8,
        peaks_for("TPU v5 lite")), 0.0081850383003663),
    (lambda: workcount.model_flops_per_token(
        gqa_decoder, DENSE["model"], DENSE["kratos"]), 3498311680.0),
], ids=["s50w8_gemm_least_s", "s50w8_flops_per_token",
        "s50w8_attention_flops", "dense_gemm_least_s",
        "dense_flops_per_token"])
def test_gqa_work_counts_are_the_parents(count, expected):
    assert count() == expected
