"""Run one cell of the benchmark and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from `BENCHMARK.json`: its
configuration file (`bench/configs/`), its traffic mix
(`bench/traffic/<name>.json`) and, in a traced run, the reader of each
per-layer metric (`bench/metrics/<name>.py`). The run fails, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def reports(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    """Whether `cell` reports `metric`: a metric with a `workloads` list in
    those cells; an end-to-end metric without one in every cell; a
    per-layer metric without one wherever its `moves` metric is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_of_cell


def load_config(name: str, root: Path = ROOT) -> dict:
    """A configuration by name: the file `BENCHMARK.json` gives it, or
    `bench/configs/<name>.json` for one that no cell uses yet."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    path = files.get(name, f"bench/configs/{name}.json")
    return json.loads((root / path).read_text())


def load_cell(name: str, root: Path = ROOT):
    """(cell, configuration, mix, end-to-end specs, per-layer specs)."""
    from bench import traffic
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = load_config(cell["config"], root)
    mix = traffic.load_mix(cell["traffic"])
    e2e = [m for m in bench["end_to_end"] if reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if reports(m, name, names)]
    return cell, config, mix, e2e, per_layer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, config, mix, e2e, per_layer = load_cell(args.workload)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    from bench import cell as run_cell
    result = run_cell.run(config, mix, args.seed, args.seconds,
                          bool(args.trace), T_START, e2e, per_layer)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
