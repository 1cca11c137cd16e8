"""A cell cut to CPU size for the benchmark's own tests.

The same harness, generator, engine settings and reference as a real
cell, with the model's sizes cut to `TINY_MODEL` (each key it changes
listed in `reduced`, as a cut configuration file lists its own),
`backend="ref"`, four slots and short requests.
"""

import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"n_layers": 4, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
              "head_dim": 8, "d_ff": 192, "vocab": 256, "activation": "silu",
              "window": 8, "tie_embeddings": False, "rope_theta": 10000.0,
              "norm_eps": 1e-6}


def tiny(cell_name: str, backend: str = "ref"):
    """(config, mix, e2e specs, per-layer specs) of a named cell, cut down."""
    from bench.run import load_cell
    _, config, mix, e2e, per_layer = load_cell(cell_name)
    return cut(config, mix, backend) + (e2e, per_layer)


def tiny_files(config_name: str, mix_name: str, backend: str = "ref"):
    """(config, mix) of a configuration file and a traffic mix that no
    cell names, cut down."""
    from bench import traffic
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{config_name}.json").read_text())
    return cut(config, traffic.load_mix(mix_name), backend)


def cut(config, mix, backend: str = "ref", model=None):
    """A configuration and a mix cut to CPU size: the model's sizes to
    `model` (TINY_MODEL by default)."""
    model = dict(model or TINY_MODEL)
    cut_keys = {k for k, v in model.items() if config["model"].get(k) != v}
    config = dict(config, model=model,
                  reduced=sorted(cut_keys | set(config.get("reduced", []))),
                  engine=dict(config["engine"], backend=backend, n_slots=4,
                              max_len=64))
    # float32 on the CPU against the float32 reference: the served tokens
    # are the reference's argmax, every gap reads 0 to rounding
    config["check"] = {"mean_logit_gap": {"limit": 1e-6}}
    if config["kratos"]:
        config["kratos"] = dict(config["kratos"], bk=8, bn=8)
    mix = copy.deepcopy(mix)
    mix["prompt"].update(min=4, max=40)
    mix["output"].update(min=2, max=20)
    for part, median in (("prompt", 12), ("output", 6)):
        if "median" in mix[part]:
            mix[part]["median"] = median
    if "rate_rps" in mix:
        mix["rate_rps"] = 5.0
    if "block" in mix:
        mix["block"] = 8
    return config, mix


def run_tiny(cell_name: str, seed: int = 2**31 + 17, seconds: float = 1.5,
             trace: bool = False, more_metrics=()):
    """One run of the cut-down cell (with `more_metrics`, per-layer specs
    read beside the cell's own); the persistent compile cache stays off,
    so CPU programs never land in the checkout's cache."""
    import time
    import jax
    from bench import cell
    jax.config.update("jax_enable_compilation_cache", False)
    config, mix, e2e, per_layer = tiny(cell_name)
    return cell.run(config, mix, seed, seconds, trace, time.perf_counter(),
                    e2e, per_layer + list(more_metrics))


def dumps(result) -> str:
    return json.dumps(result)
