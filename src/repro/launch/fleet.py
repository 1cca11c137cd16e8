"""Fleet launcher: N serving processes + the cross-process control plane.

The missing piece between "replicas on submeshes of one host" and
"fleets of hosts": this module SPAWNS the processes that
serve.control/serve.router coordinate. Two entry modes share one file so
the wire protocol and its two ends can never drift apart:

  coordinator (default)  binds a ControlListener, spawns N worker
                         subprocesses (each with its own
                         XLA_FLAGS=--xla_force_host_platform_device_count
                         so a laptop/CI box becomes an N-process CPU
                         fleet), waits for their hellos, and builds a
                         FleetRouter over the RemoteProcess handles.
  --worker               one serving process: optional
                         `jax.distributed.initialize` (real multi-host
                         runs pass --jax-coordinator/--process-index/
                         --processes; local CPU fleets skip it — no
                         cross-process collectives, nothing to
                         coordinate), model load, DistributedBackend
                         replicas on `process_meshes` submeshes, then
                         WorkerServer against the coordinator's socket.

`python -m repro.launch.fleet --processes 2` runs the built-in smoke:
the dense Poisson trace through the 2-process fleet, greedy outputs
checked token-identical against a single in-coordinator engine, fleet
topology printed per process — the CI `serve-fleet` job's first step and
the dev loop for anything touching the control plane. The gated
benchmark lives in benchmarks/serve_bench.py (--fleet), which imports
`spawn_fleet` from here.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

# Wall-clock control-plane horizons for REAL subprocess fleets (the
# coordinator paces its step loop with PACE-second sleeps, so these are
# roughly seconds/PACE steps). Deterministic tests use tighter step-clock
# FleetConfigs on LocalProcess handles instead.
PACE = 0.002
WALL_STALENESS = 400.0          # ~0.8 s of snapshot age tolerated
WALL_HEARTBEAT_TIMEOUT = 2500.0  # ~5 s of silence before death verdict
# jax.distributed fleets pay multi-second first-dispatch compiles per
# replica; the single-threaded worker cannot heartbeat through them, so
# the death verdict needs a compile-sized horizon (slower true-death
# detection is the honest price — tune down once steps are warm)
WALL_HEARTBEAT_TIMEOUT_DISTRIBUTED = 30000.0  # ~60 s


def worker_flags(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--worker", action="store_true",
                    help="run as one fleet serving process (spawned by the "
                         "coordinator; rarely typed by hand)")
    ap.add_argument("--connect", default="",
                    help="coordinator control address host:port")
    ap.add_argument("--process-index", type=int, default=0)
    ap.add_argument("--processes", type=int, default=2,
                    help="fleet size (coordinator: how many workers to "
                         "spawn; worker: jax.distributed num_processes)")
    ap.add_argument("--jax-coordinator", default="",
                    help="jax.distributed coordinator address for real "
                         "multi-host meshes ('auto' on the coordinator "
                         "picks a local port; empty = no jax.distributed — "
                         "local CPU fleets need none)")
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--decode-chunk", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas PER PROCESS (disjoint submeshes "
                         "of the process's local devices)")
    ap.add_argument("--devices-per-process", type=int, default=1,
                    help="forced CPU device count per worker (XLA_FLAGS)")
    ap.add_argument("--heartbeat-every", type=int, default=2)


def _warm_replicas(router, model) -> None:
    """Compile before hello: run one throwaway request through EVERY
    replica engine (each owns its own backend and mesh, so each pays its
    own prefill-bucket + decode jit). The worker's serve loop is single-
    threaded — a multi-second first-dispatch compile after admission
    would be heartbeat SILENCE, and the coordinator would declare a
    perfectly healthy process dead. Warming before the handshake keeps
    the death horizon tight instead of compile-sized. Metrics and step
    counters reset after, so reports describe only real traffic."""
    from repro.serve import ServeMetrics
    for eng in router.replicas:
        eng.submit([1, 2, 3], 2)
        eng.run()
        eng.metrics = ServeMetrics()
    router.step_count = 0


def worker_entry(args) -> None:
    """One fleet serving process, start to finish. Order is load-bearing:
    jax.distributed BEFORE any jax backend touch (device queries pin the
    backend), model load + engine build after, the socket loop last."""
    if args.jax_coordinator:
        from repro.serve.backend import ensure_distributed
        ensure_distributed(args.jax_coordinator, args.processes,
                           args.process_index)
    from repro.serve import (DistributedBackend, EngineConfig, FleetConfig,
                             ModelRegistry, ReplicaRouter, WorkerServer)
    from repro.serve.control import connect

    reg = ModelRegistry()
    model = reg.load(args.arch)
    cfg = EngineConfig(n_slots=args.slots, max_len=args.max_len,
                       decode_chunk=args.decode_chunk,
                       max_waiting=args.slots)
    mesh_shape = (args.replicas, 1)     # 1 device per replica, TP=1: the
    #                                     CPU-fleet shape; real runs widen

    def backend_factory(i: int) -> DistributedBackend:
        return DistributedBackend(
            mesh_shape=mesh_shape, n_replicas=args.replicas, replica=i,
            coordinator_address=args.jax_coordinator or None,
            num_processes=args.processes, process_id=args.process_index)

    router = ReplicaRouter.build(model, cfg, args.replicas,
                                 backend_factory=backend_factory)
    for i, eng in enumerate(router.replicas):
        eng.trace.process = args.process_index   # tag before any event
    _warm_replicas(router, model)
    endpoint = connect(args.connect)
    WorkerServer(router, endpoint, args.process_index,
                 cfg=FleetConfig(heartbeat_every=args.heartbeat_every,
                                 staleness=WALL_STALENESS,
                                 heartbeat_timeout=WALL_HEARTBEAT_TIMEOUT)
                 ).serve_forever()
    endpoint.close()


# --------------------------------------------------------------- coordinator

def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _await_hello(endpoint, timeout: float = 120.0) -> Dict[str, Any]:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        for msg in endpoint.poll():
            if msg.get("kind") == "hello":
                return msg
        if not endpoint.alive:
            raise RuntimeError("worker hung up before hello")
        time.sleep(0.01)
    raise TimeoutError("no hello from worker within timeout")


class Fleet:
    """A spawned local fleet: worker Popens + the FleetRouter over them.
    Context-manages cleanup so a failed bench never leaks processes."""

    def __init__(self, router, workers: List[subprocess.Popen],
                 listener) -> None:
        self.router = router
        self.workers = workers
        self.listener = listener

    def drive(self, max_seconds: float = 600.0) -> None:
        """Pump the router until every request finishes. Wall-paced: the
        coordinator's `now` advances one step per PACE sleep, which is
        what calibrates WALL_* horizons to seconds."""
        t0 = time.monotonic()
        while any(not r.finished for r in self.router.requests.values()):
            if time.monotonic() - t0 > max_seconds:
                raise TimeoutError("fleet did not drain in time")
            live = [pi for pi in self.router.processes
                    if pi not in self.router.state.dead]
            if not live:
                raise RuntimeError("every fleet process died")
            self.router.step()
            time.sleep(PACE)

    def shutdown(self) -> None:
        try:
            self.router.stop()
        finally:
            for w in self.workers:
                try:
                    w.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    w.kill()
            self.listener.close()

    def __enter__(self) -> "Fleet":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
        return None


def check_parent_platform() -> None:
    """Refuse to spawn workers from a process that already holds a TPU."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() \
            and jax.default_backend() == "tpu":
        raise RuntimeError(
            "spawn_fleet: this process already initialized jax on a TPU "
            "and holds the chip, so no worker process could use it; spawn "
            "the fleet before touching jax, or serve in this one process")


def worker_env(devices: int) -> dict:
    """A worker's environment: the parent's (JAX_PLATFORMS included, so a
    worker runs where the parent would), plus its own forced CPU device
    count and this checkout's `src` on PYTHONPATH."""
    repo_src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = repo_src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def spawn_fleet(n_processes: int, *, arch: str = "h2o-danube-1.8b",
                n_slots: int = 4, max_len: int = 96, decode_chunk: int = 4,
                replicas_per_process: int = 1,
                devices_per_process: int = 0,
                jax_coordinator: str = "", heartbeat_every: int = 2,
                cfg=None, hello_timeout: float = 300.0) -> Fleet:
    """Spawn `n_processes` worker subprocesses and return a Fleet whose
    router admits across them. Workers run on the parent's platform (they
    inherit JAX_PLATFORMS, if set) and force their own CPU device counts
    (XLA_FLAGS in the child env — set BEFORE the child imports jax, the
    only reliable point to do it). On CPU the parent's jax state is never
    touched, so spawn_fleet is safe to call from pytest or a bench that
    already initialized jax; a parent that holds a TPU is refused, since
    a chip belongs to one process and the workers could not reach it."""
    from repro.serve import FleetConfig, FleetRouter
    from repro.serve.control import ControlListener, RemoteProcess

    check_parent_platform()
    listener = ControlListener()
    if jax_coordinator == "auto":
        jax_coordinator = f"127.0.0.1:{_free_port()}"
    devices = devices_per_process or replicas_per_process
    workers = []
    for i in range(n_processes):
        env = worker_env(devices)
        cmd = [sys.executable, "-m", "repro.launch.fleet", "--worker",
               "--connect", listener.address,
               "--process-index", str(i), "--processes", str(n_processes),
               "--arch", arch, "--slots", str(n_slots),
               "--max-len", str(max_len),
               "--decode-chunk", str(decode_chunk),
               "--replicas", str(replicas_per_process),
               "--heartbeat-every", str(heartbeat_every)]
        if jax_coordinator:
            cmd += ["--jax-coordinator", jax_coordinator]
        workers.append(subprocess.Popen(cmd, env=env))
    try:
        handles = []
        for _ in range(n_processes):
            ep = listener.accept(timeout=hello_timeout)
            hello = _await_hello(ep, timeout=hello_timeout)
            handles.append(RemoteProcess(ep, int(hello["process_index"])))
        handles.sort(key=lambda h: h.process_index)
        cfg = cfg or FleetConfig(
            heartbeat_every=heartbeat_every,
            staleness=WALL_STALENESS,
            heartbeat_timeout=WALL_HEARTBEAT_TIMEOUT_DISTRIBUTED
            if jax_coordinator else WALL_HEARTBEAT_TIMEOUT)
        return Fleet(FleetRouter(handles, cfg=cfg), workers, listener)
    except Exception:
        for w in workers:
            w.kill()
        listener.close()
        raise


# --------------------------------------------------------------------- smoke

def run_smoke(args) -> int:
    """2-process fleet vs one in-coordinator engine on the same Poisson
    trace: token identity is the pass/fail; topology + throughput print.
    Optionally (--inject-death) kills one worker mid-trace and requires
    the heartbeat-timeout failover to keep the outputs token-identical."""
    import numpy as np
    from repro.serve import EngineConfig, InferenceEngine, ModelRegistry

    reg = ModelRegistry()
    model = reg.load(args.arch)
    rng = np.random.default_rng(args.seed)
    trace = []
    t = 0.0
    # death injection needs every request mid-generation long enough for
    # the kill to land: stretch the decode phase, same trace both sides
    gen_extra = 16 if args.inject_death else 0
    for _ in range(args.requests):
        t += rng.exponential(0.75)
        trace.append((int(t), rng.integers(0, model.cfg.vocab,
                                           int(rng.integers(4, 12))),
                      int(rng.integers(4, 10)) + gen_extra))

    eng = InferenceEngine(model, EngineConfig(
        n_slots=args.slots, max_len=args.max_len,
        decode_chunk=args.decode_chunk))
    ref = [eng.submit(p, g, arrival_step=a) for a, p, g in trace]
    eng.run()
    ref_toks = [list(r.generated) for r in ref]

    with spawn_fleet(args.processes, arch=args.arch, n_slots=args.slots,
                     max_len=args.max_len, decode_chunk=args.decode_chunk,
                     replicas_per_process=args.replicas,
                     jax_coordinator=args.jax_coordinator) as fleet:
        reqs = [fleet.router.submit(p, g, arrival_step=a)
                for a, p, g in trace]
        if args.inject_death:
            # crash a process while it is MID-GENERATION: the victim is
            # picked live (a process observed with an unfinished request
            # that has accumulated tokens) — a fixed victim races, e.g.
            # a slow-starting worker that never got any requests homed
            victim = None
            deadline = time.monotonic() + 60.0
            while victim is None:
                alive = [r.process for r in reqs
                         if r.process >= 0 and not r.finished
                         and len(r.tokens)]
                if alive:
                    victim = max(alive)
                    break
                if all(r.finished for r in reqs):
                    raise RuntimeError(
                        "trace drained before death injection — grow "
                        "--requests or --gen to widen the window")
                fleet.router.step()
                time.sleep(PACE)
                if time.monotonic() > deadline:
                    raise TimeoutError("no progress before death injection")
            fleet.router.processes[victim].kill()
            print(f"# injected death: process {victim}")
        fleet.drive()
        fleet.router.stop()
        rep = fleet.router.report()

    fleet_toks = [list(r.tokens) for r in reqs]
    identical = fleet_toks == ref_toks
    print(f"fleet {args.processes}x{args.replicas}: "
          f"{int(rep.get('fleet_requests_completed', 0))} reqs, "
          f"{int(rep.get('fleet_tokens', 0))} toks | "
          f"tokens/fleet-step {rep.get('tokens_per_fleet_step', 0):.2f} | "
          f"failovers {int(rep.get('fleet_failovers', 0))}, "
          f"dead {int(rep.get('processes_dead', 0))}, "
          f"resurrections ignored "
          f"{int(rep.get('resurrections_ignored', 0))} | "
          f"token-identical vs single: {identical}")
    if args.inject_death and not rep.get("fleet_failovers", 0):
        print("# FAIL: death injected but no failover happened")
        return 1
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"identical": identical, "report": rep}, f, indent=2)
            f.write("\n")
    return 0 if identical else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Spawn a local N-process serving fleet (or run as one "
                    "of its workers).")
    worker_flags(ap)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject-death", action="store_true",
                    help="smoke: kill one worker mid-trace and require "
                         "token-identical failover")
    ap.add_argument("--out", default="", help="write smoke result JSON")
    args = ap.parse_args(argv)
    if args.worker:
        if not args.connect:
            ap.error("--worker requires --connect host:port")
        worker_entry(args)
        return 0
    return run_smoke(args)


if __name__ == "__main__":
    sys.exit(main())
