"""Find an open-loop cell's knee: the same mix at several fixed rates.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 30 --seed 5
    python3 bench/sweep.py --config <config> --traffic <mix> --rates 4,6,8 --seconds 30

The second form sweeps a configuration and a mix that no cell names yet.

One process builds the cell's engine once and runs one window per rate,
in ascending order, draining between them, and stops after the first rate
past the knee. For each rate it prints the requests due, those
that failed, the queue at the window's close, and the end-to-end tails.
The knee is the highest rate at which no request failed and the queue at
the close holds no more than a quarter of `n_slots` requests (a queue of
`n_slots` admits rates past what the engine serves: the 8-slot dense
point's TTFT reached 15 s in a 30 s window at such a rate); a cell runs at
about four fifths of it, written into its traffic file as a number. The benchmark's own runs never sweep.
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
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--config", help="with --traffic, in place of a cell")
    ap.add_argument("--traffic")
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import numpy as np
    from bench import cell, check, loop, system, traffic
    from bench.run import load_cell, load_config
    if args.workload:
        _, config, mix, _, _ = load_cell(args.workload)
    elif args.config and args.traffic:
        config, mix = load_config(args.config), traffic.load_mix(args.traffic)
    else:
        raise SystemExit("name a --workload, or a --config and a --traffic")
    if mix["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop mix")
    cell._configure_cache()
    rates = sorted(float(r) for r in args.rates.split(","))
    m, eng = config["model"], config["engine"]
    model = system.load_model(config, check.reference(config).make_weights(
        args.seed, m, config["kratos"]))
    engine = system.make_engine(config, model)
    lengths = np.concatenate([traffic.prompt_lengths(
        dict(mix, rate_rps=r), args.seconds) for r in rates])
    system.warm_up(engine, lengths, m["vocab"])
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    knee = None
    for rate in rates:
        reqs = traffic.open_requests(dict(mix, rate_rps=rate), args.seconds,
                                     m["vocab"], args.seed)
        w = loop.Driver(engine, args.seconds, cell.DRAIN_S).run_open(reqs)
        row = {"rate_rps": rate, "attempted": len(w.recs),
               "failed": sum(not r.done for r in w.recs),
               "waiting_at_close": w.waiting_at_close,
               **cell.end_to_end(w, args.seconds)}
        print(json.dumps(row), flush=True)
        if w.waiting_at_close > eng["n_slots"] // 4 or row["failed"]:
            break
        knee = rate
    print(json.dumps({"workload": args.workload or
                      f"{args.config}.{args.traffic}", "knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
