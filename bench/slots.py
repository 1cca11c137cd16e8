"""The largest slot count a configuration builds and serves on this chip.

    python3 bench/slots.py --config danube-1.8b-s50w8 --traffic chat-0.68rps --slots 20,24,28

The configuration is found by name (`run.load_config`), so one that no
cell uses yet, such as a new cut, can be sized before its cell exists.

One process makes and packs the weights once, then, for each slot count in
ascending order, builds the engine, warms up the traffic's longest prompt
and the fused decode, and prints the device's bytes in use and its peak.
It stops at the first count that runs out of device memory. The benchmark's
own runs never probe.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--slots", required=True, help="comma-separated")
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import jax
    from bench import cell, check, system, traffic
    from bench.run import load_config
    config = load_config(args.config)
    mix = traffic.load_mix(args.traffic)
    cell._configure_cache()
    dev = jax.devices()[0]
    m = config["model"]
    model = system.load_model(config, check.reference(config).make_weights(
        args.seed, m, config["kratos"]))
    longest = [mix["prompt"]["max"]]
    for n in sorted(int(s) for s in args.slots.split(",")):
        row = {"n_slots": n}
        try:
            engine = system.make_engine(
                dict(config, engine=dict(config["engine"], n_slots=n)), model)
            system.warm_up(engine, longest, m["vocab"])
            stats = dev.memory_stats() or {}
            row.update(ok=True, bytes_in_use=stats.get("bytes_in_use"),
                       peak_bytes=stats.get("peak_bytes_in_use"),
                       bytes_limit=stats.get("bytes_limit"))
            del engine
        except Exception as e:            # noqa: BLE001 - report and stop
            row.update(ok=False, error=str(e).splitlines()[0][:300])
        gc.collect()
        print(json.dumps(row), flush=True)
        if not row["ok"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
