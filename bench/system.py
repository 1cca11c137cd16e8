"""The benchmark's one adapter to the system under test.

Everything the harness calls in the program goes through here: building
the served model from the benchmark's own weights, the engine, the warm-up
of exactly the programs a cell's traffic reaches, and host spans around
the engine's calls into its layers. The program gets the weights; it never
hands anything back to the reference.
"""

from __future__ import annotations

from typing import Dict, Iterable

import jax


def program_tree(w: Dict) -> Dict:
    """The program's parameter tree (one scanned block slot, no prelude)
    over the reference's weight dict; shares every buffer."""
    lw = w["layers"]
    return {
        "embed": {"emb": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "head": {"w": w["head"]},
        "prelude": [],
        "blocks": [{
            "pre_norm": {"scale": lw["attn_norm"]},
            "mixer": {k: {"w": lw[k]} for k in ("wq", "wk", "wv", "wo")},
            "ffn_norm": {"scale": lw["mlp_norm"]},
            "ffn": {k: {"w": lw[k]} for k in ("w_gate", "w_up", "w_down")},
        }],
    }


def kratos_spec(kratos: Dict):
    from repro.core.kratos import DENSE, KratosSpec
    return KratosSpec(**kratos) if kratos else DENSE


def _smoke(config: Dict) -> bool:
    """Which of the program's configurations of `arch` the file states:
    the published one (False) or its small CPU one (True). Raises when
    the file's sizes match neither: the file states what is run."""
    from repro import configs as C
    for smoke, get in ((False, C.get_config), (True, C.get_smoke)):
        cfg = get(config["arch"])
        if all(getattr(cfg, k) == v for k, v in config["model"].items()):
            return smoke
    raise ValueError(f"{config['name']}: the model sizes match neither of "
                     f"the program's configurations of {config['arch']!r}")


def load_model(config: Dict, w: Dict):
    """Pack the weights at the configuration's Kratos point."""
    from repro.serve.registry import ModelRegistry
    return ModelRegistry().load(
        config["arch"], kratos_spec(config["kratos"]),
        params=program_tree(w), smoke=_smoke(config))


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

