"""Readings for the limits of `correct`: the program and its control, seed by seed.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 12

For each seed, in one process: one run of the cell at its own load with a
short window, the readings of the program's served tokens on the compared
requests (the lower readings), and on the same requests and positions the
readings of the token that the reference computed in bfloat16 puts first
(the control; the upper readings), with the control's own verdict against
the configuration's limits. Prints one JSON line per seed and a summary.
The benchmark's own runs never run the control.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import cell
    from bench.run import load_cell
    _, config, mix, e2e, per_layer = load_cell(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = cell.run(config, mix, seed, args.seconds, False,
                     time.perf_counter(), e2e, per_layer, control=True)
        row = {"seed": seed, "correct": r["correct"],
               "control_correct": (r["control"] or {}).get("correct"),
               **{f"{side}.{k}": v
                  for side, read in (r["readings"] or {}).items()
                  for k, v in read.items()},
               "tokens": r["notes"]["compared"]["tokens"],
               "failed": r["failed"], "attempted": r["attempted"],
               "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = sorted({k.split(".", 1)[1] for r in rows for k in r if "." in k})
    print(json.dumps({
        "workload": args.workload,
        "lower": {k: max(r[f"program.{k}"] for r in rows) for k in keys},
        "upper": {k: min(r[f"control.{k}"] for r in rows) for k in keys},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
