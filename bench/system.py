"""The benchmark's one adapter to the system under test.

Everything the harness calls in the program goes through here: the
program's model configuration built from the benchmark's file, the served
model built from the benchmark's own weights, the engine, the warm-up of
exactly the programs a cell's traffic reaches, and host spans around the
engine's calls into its layers. The program gets the weights; it never
hands anything back to the reference. What depends on the architecture,
the parameter tree and the work one decode step applies, lives in
`bench/adapters/<reference>.py`, found by the configuration's `reference`.
"""

from __future__ import annotations

import dataclasses
import importlib
from pathlib import Path
from typing import Dict, Iterable

import jax

ADAPTERS = Path(__file__).resolve().parent / "adapters"


def adapter(config: Dict):
    """The module that maps the configuration's reference onto the
    program: `bench/adapters/<reference>.py`, with `program_tree`,
    `decode_projections` and `attention_flops`."""
    name = config["reference"]
    if not (ADAPTERS / f"{name}.py").is_file():
        raise FileNotFoundError(
            f"{config['name']}: reference {name!r} has no adapter "
            f"bench/adapters/{name}.py")
    return importlib.import_module(f"bench.adapters.{name}")


def kratos_spec(kratos: Dict):
    from repro.core.kratos import DENSE, KratosSpec
    return KratosSpec(**kratos) if kratos else DENSE


def program_config(config: Dict):
    """The program's ModelConfig that the file states: the published
    configuration of `arch` with the file's `model` keys as overrides.
    Every key whose value differs from the published one is a cut and must
    be named in the file's `reduced` (missing reads as []). Raises, naming
    the keys, for a key that ModelConfig lacks and for a change that
    `reduced` does not name."""
    from repro import configs as C
    published = C.get_config(config["arch"])
    model = config["model"]
    unknown = sorted(set(model) - {f.name for f in
                                   dataclasses.fields(published)})
    if unknown:
        raise ValueError(f"{config['name']}: {', '.join(unknown)} not keys "
                         f"of the program's ModelConfig")
    unlisted = [f"{k} ({getattr(published, k)!r} -> {v!r})"
                for k, v in model.items() if getattr(published, k) != v
                and k not in config.get("reduced", [])]
    if unlisted:
        raise ValueError(f"{config['name']}: changes {', '.join(unlisted)} "
                         f"from the published {config['arch']} without "
                         f"naming them in `reduced`")
    return C.get_config(config["arch"], **model)


def load_model(config: Dict, w: Dict):
    """Pack the weights at the configuration's Kratos point and serve them
    under the configuration's own ModelConfig (`program_config`)."""
    from repro.serve.registry import ModelRegistry
    cfg = program_config(config)
    tree = adapter(config).program_tree(w, config["model"])
    model = ModelRegistry().load(
        config["arch"], kratos_spec(config["kratos"]), params=tree,
        smoke=False)
    # given the parameters, the registry uses its published config of
    # `arch` for nothing but the artifact's `cfg`, which this replaces
    return dataclasses.replace(
        model, cfg=dataclasses.replace(cfg, kratos=model.spec))


def make_engine(config: Dict, model):
    from repro.serve.engine import EngineConfig, InferenceEngine
    return InferenceEngine(model, EngineConfig(**config["engine"]))


def warm_up(engine, lengths: Iterable[int], vocab: int) -> int:
    """Compile every program the traffic reaches: one prefill program per
    (padded length, exact) pair its prompt lengths reach (an exact one
    reads the last column only), the slot install, first-token sampling
    and the fused decode block. Returns the number of warm-up requests."""
    picks = {}
    for n in sorted({int(x) for x in lengths}):
        padded = engine._prefill_len(n)
        picks.setdefault((padded, padded == n), n)
    for n in picks.values():
        engine.submit([1 + (i % (vocab - 1)) for i in range(n)],
                      max_new_tokens=2 * engine.cfg.decode_chunk)
    engine.run()
    return len(picks)


def annotate(engine) -> None:
    """Host spans (jax.profiler.TraceAnnotation) around the engine's calls
    into its layers: admission with its prefill, the fused decode dispatch.
    Installed on this engine object only, for a traced run."""
    TA = jax.profiler.TraceAnnotation

    def wrap(obj, name, span):
        inner = getattr(obj, name)

        def call(*a, **k):
            with TA(span):
                return inner(*a, **k)
        setattr(obj, name, call)

    wrap(engine, "_start", "admit+prefill")
    wrap(engine.backend, "decode_block", "decode_dispatch")

