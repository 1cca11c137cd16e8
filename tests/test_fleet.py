"""Fleet serving integration (PR 10): LocalProcess fleets must be
token-identical to a single engine — including across snapshot delay and
mid-stream process death — and DistributedBackend must be placement-only
(identical tokens to the single-process backends it generalizes).

Subprocess fleets (launch.fleet spawn path) are exercised by the CI
serve-fleet job's smoke + bench gates; everything here is in-process and
deterministic on the step clock."""

import numpy as np
import pytest

from repro.serve import (DistributedBackend, EngineConfig, FleetConfig,
                         FleetRouter, InferenceEngine, LocalProcess,
                         ModelRegistry, ReplicaRouter, ServeMetrics)
from repro.serve.telemetry import TelemetryRegistry
from repro.launch import mesh as M

ARCH = "h2o-danube-1.8b"
_REGISTRY = ModelRegistry()


def _model():
    return _REGISTRY.load(ARCH)


def _prompts(model, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, model.cfg.vocab, int(rng.integers(4, 9)))
            for _ in range(n)]


def _ecfg(**kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_waiting", 16)
    return EngineConfig(**kw)


def _reference(model, prompts, gen):
    eng = InferenceEngine(model, _ecfg())
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    return [list(r.generated) for r in reqs]


def _local_fleet(model, n_processes, fcfg=None, delay=0):
    fcfg = fcfg or FleetConfig(heartbeat_every=1, staleness=8.0,
                               heartbeat_timeout=25.0)
    procs = [LocalProcess(ReplicaRouter.build(model, _ecfg(), 1),
                          process_index=i, cfg=fcfg, delay=delay)
             for i in range(n_processes)]
    return FleetRouter(procs, cfg=fcfg)


# ------------------------------------------------------------ token identity

def test_two_process_fleet_token_identical_to_single_engine():
    model = _model()
    prompts, gen = _prompts(model, 6), 6
    ref = _reference(model, prompts, gen)
    fleet = _local_fleet(model, 2)
    reqs = [fleet.submit(p, gen) for p in prompts]
    fleet.run()
    fleet.stop()
    assert [list(r.tokens) for r in reqs] == ref
    # and the work actually spread: both processes served something
    assert len({r.process for r in reqs}) == 2
    rep = fleet.report()
    assert rep["n_processes"] == 2.0
    assert rep["fleet_requests_completed"] == 6.0
    assert rep["fleet_tokens"] == float(sum(len(t) for t in ref))
    assert rep["fleet_steps"] > 0
    assert rep["tokens_per_fleet_step"] > 0
    assert rep["fleet_failovers"] == 0.0


def test_delayed_snapshots_fleet_still_token_identical():
    """Satellite (b) at integration level: with every control message
    lagged 2 pumps, placement decisions run on stale snapshots + credits
    — tokens must not change, and admission must not collapse onto one
    process."""
    model = _model()
    prompts, gen = _prompts(model, 6, seed=1), 6
    ref = _reference(model, prompts, gen)
    fleet = _local_fleet(model, 2, delay=2)
    reqs = [fleet.submit(p, gen) for p in prompts]
    fleet.run()
    fleet.stop()
    assert [list(r.tokens) for r in reqs] == ref
    assert len({r.process for r in reqs}) == 2


# ------------------------------------------------------------------ failover

def test_process_death_fails_over_token_identical():
    """Kill one process mid-generation: silence crosses the heartbeat
    horizon, its unfinished requests re-prefill (prompt + accumulated
    progress deltas) on the survivor, and greedy decode makes the final
    streams token-identical to a single engine. Late messages from the
    corpse are counted ignored, never folded in."""
    model = _model()
    prompts, gen = _prompts(model, 6, seed=2), 12
    ref = _reference(model, prompts, gen)
    fcfg = FleetConfig(heartbeat_every=1, staleness=4.0,
                       heartbeat_timeout=6.0)
    fleet = _local_fleet(model, 2, fcfg=fcfg)
    reqs = [fleet.submit(p, gen) for p in prompts]
    victim = None
    for _ in range(200):
        fleet.step()
        mid = [r.process for r in reqs
               if r.process >= 0 and not r.finished and r.tokens]
        if mid and len({r.process for r in reqs if r.process >= 0}) == 2:
            victim = max(mid)
            break
    assert victim is not None, "fleet never reached mid-generation state"
    fleet.processes[victim].kill()
    fleet.run()
    fleet.stop()
    assert [list(r.tokens) for r in reqs] == ref
    rep = fleet.report()
    assert rep["fleet_failovers"] >= 1
    assert rep["processes_dead"] == 1.0
    assert victim in fleet.state.dead
    # resurrection: a zombie status from the dead index is dropped+counted
    from repro.serve.control import ProcessStatus
    zombie = ProcessStatus(process_index=victim, seq=10_000, step=0,
                           replica_loads=[0], n_free_slots=4, n_waiting=0,
                           page_occupancy=0.0, qos_tier=0, submits_seen=0,
                           progress={str(reqs[0].rid): [1, 2, 3]})
    before = [list(r.tokens) for r in reqs]
    fleet._handle(victim, zombie.to_wire())
    assert [list(r.tokens) for r in reqs] == before
    assert fleet.state.resurrections_ignored >= 1


# ------------------------------------------------- distributed backend/mesh

def test_distributed_backend_token_identical_single_process():
    """DistributedBackend is placement-only: on one process with no
    coordinator it is ShardedBackend over process_meshes of the local
    devices — tokens must match the default backend exactly."""
    model = _model()
    prompts, gen = _prompts(model, 3, seed=3), 6
    ref = _reference(model, prompts, gen)
    eng = InferenceEngine(model, _ecfg(),
                          backend=DistributedBackend(mesh_shape=(1, 1)))
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    assert [list(r.generated) for r in reqs] == ref


def test_process_meshes_matches_replica_meshes_degenerate():
    import jax
    pm = M.process_meshes(1, 1, 1)
    rm = M.replica_meshes(1, 1, 1)
    assert len(pm) == len(rm) == 1
    assert pm[0].devices.ravel().tolist() == [jax.local_devices()[0]]
    assert pm[0].axis_names == rm[0].axis_names == ("data", "model")


def test_plan_fleet_topology_validates_and_describes():
    plan = M.plan_fleet_topology(2, 2, data=2, model=1, n_replicas=2)
    assert plan["num_processes"] == 2
    assert plan["global_device_count"] == 4
    assert len(plan["processes"]) == 2
    p0 = plan["processes"][0]
    assert len(p0["local_devices"]) == 2
    assert len(p0["replica_meshes"]) == 2
    assert p0["replica_meshes"][0]["shape"] == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="does not divide"):
        M.plan_fleet_topology(2, 4, data=2, model=1, n_replicas=3)
    with pytest.raises(ValueError):
        M.plan_fleet_topology(0, 1, data=1, model=1, n_replicas=1)
    with pytest.raises(ValueError):    # 1 device cannot host a 2x1 mesh
        M.plan_fleet_topology(2, 1, data=2, model=1, n_replicas=1)


# ---------------------------------------------------- fleet-pooled metrics

def test_metrics_payload_roundtrip_and_aggregate():
    model = _model()
    eng = InferenceEngine(model, _ecfg())
    for p in _prompts(model, 3, seed=4):
        eng.submit(p, 4)
    eng.run()
    back = ServeMetrics.from_payload(eng.metrics.to_payload())
    a, b = eng.metrics.report(), back.report()
    for k in ("tokens_generated", "requests_completed", "decode_steps"):
        assert a[k] == b[k], k
    agg = ServeMetrics.aggregate([eng.metrics, back])
    assert agg["tokens_generated"] == 2 * a["tokens_generated"]


def test_telemetry_process_index_label():
    def fill(reg):
        reg.counter("tokens").inc(5)
        reg.gauge("occupancy").set(0.5)
        reg.histogram("latency", buckets=(1.0, 2.0)).observe(1.5)
        return reg.render_prometheus()

    plain = fill(TelemetryRegistry(prefix="serve"))
    assert "process_index" not in plain          # byte-identical w/o fleet
    assert "serve_tokens 5" in plain
    labeled = fill(TelemetryRegistry(prefix="serve", process_index=3))
    assert 'serve_tokens{process_index="3"} 5' in labeled
    assert 'process_index="3",le="1"' in labeled
    assert 'serve_latency_count{process_index="3"}' in labeled
    # same metric set, only the label differs
    assert len(plain.splitlines()) == len(labeled.splitlines())


def test_workers_inherit_platform_and_tpu_parent_is_refused(monkeypatch):
    """Workers run where the parent would (no forced CPU), and a parent
    that already holds a TPU cannot spawn workers that need it."""
    import jax
    from repro.launch import fleet as F
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert F.worker_env(2)["JAX_PLATFORMS"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    env = F.worker_env(2)
    assert "JAX_PLATFORMS" not in env
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=2"
    jax.devices()                         # this process has initialized jax
    F.check_parent_platform()             # ... on the CPU: fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the chip"):
        F.spawn_fleet(2)
