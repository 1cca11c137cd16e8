"""Record the small chip capture that `test_bench_xplane.py` reads.

    python3 bench/tests/record_trace.py --out bench/tests/data/s50w8.xplane.pb.gz

On one TPU v5e: builds the `danube-1.8b-s50w8` engine as its
configuration states, warms it up, and profiles a one-second window of the
harness's own loop (`bench/loop.py`) serving three requests, with the
benchmark's host spans. The capture is written gzipped.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONFIG = "bench/configs/danube-1.8b-s50w8.json"
PROMPTS = (200, 300, 400)
N_OUT = 40


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    from bench import cell, check, loop, system, traffic, xplane
    config = json.loads((ROOT / CONFIG).read_text())
    cell._configure_cache()
    m = config["model"]
    model = system.load_model(config, check.reference(config).make_weights(
        1, m, config["kratos"]))
    engine = system.make_engine(config, model)
    system.warm_up(engine, PROMPTS, m["vocab"])
    system.annotate(engine)
    trace_dir = ROOT / ".bench_trace" / "record"
    shutil.rmtree(trace_dir, ignore_errors=True)
    rng = np.random.default_rng(1)
    reqs = [traffic.Req(0.1 * i, rng.integers(0, m["vocab"], n,
                                               dtype=np.int32), N_OUT)
            for i, n in enumerate(PROMPTS)]
    driver = loop.Driver(engine, 1.0, 30.0, str(trace_dir), 1.0)
    window = driver.run_open(reqs)
    path = xplane.find_xplane(str(trace_dir))
    with open(path, "rb") as f, gzip.open(args.out, "wb") as g:
        g.write(f.read())
    print(json.dumps({"out": args.out, "steps": len(window.steps),
                      "traced": [window.trace_t0, window.trace_t1]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
