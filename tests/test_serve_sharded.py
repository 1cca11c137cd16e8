"""Mesh-aware serving on 8 virtual CPU devices: ShardedBackend equivalence,
slab/state placement, donation under pjit, router over replica submeshes.

Same subprocess isolation as test_multidevice.py (jax locks the device
count at first init): every test runs a script under
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")

IDENTITY_SCRIPT = """
    import numpy as np
    from repro.serve import (EngineConfig, InferenceEngine, ModelRegistry,
                             ShardedBackend)
    arch = {arch!r}
    reg = ModelRegistry()
    m = reg.load(arch)
    rng = np.random.default_rng(11)
    jobs = [(rng.integers(0, m.cfg.vocab, s0), gen)
            for s0, gen in [(5, 6), (9, 4), (7, 5)]]
    def run(backend=None, k=1):
        eng = InferenceEngine(
            m, EngineConfig(n_slots=4, max_len=32, decode_chunk=k),
            backend=backend)
        rs = [eng.submit(p, g, arrival_step=i)
              for i, (p, g) in enumerate(jobs)]
        eng.run()
        return [r.generated for r in rs], eng
    local, _ = run()
    sh1, eng1 = run(backend=ShardedBackend(mesh_shape=(4, 2)), k=1)
    sh3, _ = run(backend=ShardedBackend(mesh_shape=(4, 2)), k=3)
    assert local == sh1, (local, sh1)          # token identity, K=1
    assert local == sh3, (local, sh3)          # ... and for any chunk K
    d = eng1.backend.describe()
    assert d["mesh_shape"] == [4, 2] and d["n_devices"] == 8
    print(arch, "sharded identity OK")
"""


def run_script(body: str, timeout=420) -> str:
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, timeout=timeout,
                       env=ENV, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b",     # transformer + SWA
                                  "falcon-mamba-7b",     # pure SSM
                                  "minicpm3_4b"])        # MLA
def test_sharded_backend_greedy_identity(arch):
    """Greedy decode through ShardedBackend on a (data=4, model=2) mesh is
    token-identical to LocalBackend for K=1 and K=3 — placement is not
    allowed to change outputs, per architecture family."""
    run_script(IDENTITY_SCRIPT.format(arch=arch))


def test_sharded_pallas_kernels_run_per_device():
    """Pallas kernels cannot be partitioned by GSPMD: under a multi-device
    mesh each call runs whole on every device (ops._per_device). The packed
    sparse+int8 engine on a (data=1, model=4) mesh, kernels in interpret
    mode, is token-identical to the one-device engine."""
    run_script("""
        import numpy as np
        from repro.core import kratos as kr
        from repro.serve import (EngineConfig, InferenceEngine,
                                 ModelRegistry, ShardedBackend)
        m = ModelRegistry().load(
            "h2o-danube-1.8b", kr.KratosSpec(sparsity=0.5, bits=8, bk=8,
                                             bn=8))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, m.cfg.vocab, n) for n in (5, 9)]
        def run(backend=None):
            eng = InferenceEngine(
                m, EngineConfig(n_slots=2, max_len=24, decode_chunk=2,
                                backend="interpret"), backend=backend)
            rs = [eng.submit(p, 4) for p in prompts]
            eng.run()
            return [r.generated for r in rs]
        local = run()
        sharded = run(ShardedBackend(mesh_shape=(1, 4)))
        assert local == sharded, (local, sharded)
        print("per-device pallas OK")
    """)


def test_slab_and_state_actually_shard_over_the_mesh():
    """The slab's slot axis lands on 'data', kv-heads on 'model', the
    per-slot state vectors on 'data'; a non-divisible slot count falls back
    to a replicated slot axis instead of seq-sharding the slab."""
    run_script("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.distributed import sharding as SH, steps as ST
        from repro.serve import EngineConfig, InferenceEngine, ModelRegistry
        from repro.serve import ShardedBackend
        from repro.models import transformer as T
        from repro import configs as C

        m = ModelRegistry().load("h2o-danube-1.8b")
        eng = InferenceEngine(
            m, EngineConfig(n_slots=4, max_len=32),
            backend=ShardedBackend(mesh_shape=(4, 2)))
        k = eng.pool.caches["blocks"][0]["mixer"]["k"]   # (L, B, KV, S, dh)
        spec = k.sharding.spec
        assert spec[1] in ("data", ("data",)), spec      # slots over data
        assert spec[2] == "model", spec                  # kv heads over TP
        assert spec[3] is None, spec                     # seq NEVER sharded
        st = eng.backend.state
        assert st["tokens"].sharding.spec == P("data")
        assert st["key"].sharding.spec in (P(None), P())

        # non-divisible slots: replicated fallback, not seq-over-data
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        caches = T.make_caches(C.get_smoke("h2o_danube_1_8b"), 3, 32)
        specs = SH.cache_pspecs(caches, mesh, 3, slab=True)
        for leaf in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            assert all(ax not in ("data", ("data",)) for ax in leaf), leaf
        state_specs = ST.decode_state_pspecs(mesh, 3)
        assert state_specs["tokens"] == P(None)
        print("slab/state placement OK")
    """)


def test_sharded_decode_still_donates_under_pjit():
    """out_shardings pinned to the donated inputs' shardings: the lowered
    SPMD module still carries input->output aliasing for slab and state
    (no per-dispatch slab copy on donation-capable backends)."""
    run_script("""
        import jax.numpy as jnp
        from repro.serve import (EngineConfig, InferenceEngine,
                                 ModelRegistry, ShardedBackend)
        m = ModelRegistry().load("h2o-danube-1.8b")
        eng = InferenceEngine(
            m, EngineConfig(n_slots=4, max_len=32, decode_chunk=2),
            backend=ShardedBackend(mesh_shape=(4, 2)))
        bk = eng.backend
        txt = bk._decode.lower(bk.params, eng.pool.caches,
                               bk.state).as_text()
        assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt
        txt_w = eng.pool._write.lower(
            eng.pool.caches, eng.pool.single_template,
            jnp.asarray(0, jnp.int32)).as_text()
        assert "tf.aliasing_output" in txt_w or "jax.buffer_donor" in txt_w
        print("sharded donation OK")
    """)


def test_router_over_disjoint_replica_submeshes():
    """replica_meshes splits the data axis into disjoint per-replica
    submeshes; the router drives sharded replicas exactly like local ones
    and the fleet drains a bursty trace."""
    run_script("""
        import numpy as np
        from repro.launch import mesh as M
        from repro.serve import (EngineConfig, ModelRegistry, ReplicaRouter,
                                 ShardedBackend)
        meshes = M.replica_meshes(4, 2, 2)
        devs = [frozenset(d.id for d in mm.devices.ravel()) for mm in meshes]
        assert devs[0].isdisjoint(devs[1])
        assert all(len(d) == 4 for d in devs)
        m = ModelRegistry().load("h2o-danube-1.8b")
        router = ReplicaRouter.build(
            m, EngineConfig(n_slots=2, max_len=32, decode_chunk=2,
                            max_waiting=2),
            2, backend_factory=lambda i: ShardedBackend(mesh=meshes[i]))
        rng = np.random.default_rng(0)
        reqs = [router.submit(rng.integers(0, m.cfg.vocab, 6), 5,
                              arrival_step=0) for _ in range(6)]
        router.run()
        assert all(len(r.generated) == 5 for r in reqs)
        rep = router.report()
        assert rep["requests_completed"] == 6.0
        assert {e.backend.name for e in router.replicas} == {"sharded"}
        print("router over submeshes OK, spills", int(rep["spills"]))
    """)


def test_sharded_tier_swap_and_cancel_hygiene():
    """PR 7 grid, sharded leg: the QoS tier swap re-jits the decode step
    per tier on the mesh (params are a pinned non-donated operand, so the
    swap is KV-safe), mid-flight cancel + deadline shed release slots
    cleanly, and a tier-0 sharded run stays token-identical to local."""
    run_script("""
        import numpy as np
        from repro.serve import (DraftSpec, EngineConfig, InferenceEngine,
                                 ModelRegistry, QoSConfig, ShardedBackend)
        tiers = (DraftSpec.from_args(8, 0.5, 0),)
        m = ModelRegistry().load("h2o-danube-1.8b", tier_specs=tiers)
        rng = np.random.default_rng(2)
        jobs = [(rng.integers(0, m.cfg.vocab, 6), 6) for _ in range(6)]

        # degradation under load on the mesh: all complete across the swap
        eng = InferenceEngine(
            m, EngineConfig(n_slots=2, max_len=32,
                            qos=QoSConfig(demote_depth=2, promote_depth=0,
                                          hysteresis=1)),
            backend=ShardedBackend(mesh_shape=(4, 2)))
        reqs = [eng.submit(p, g) for p, g in jobs]
        eng.run()
        assert all(r.state == "done" and len(r.generated) == 6
                   for r in reqs)
        assert eng.metrics.tier_demotions >= 1
        assert eng.tier == 0                    # drained: re-promoted
        assert eng.pool.n_free == 2

        # cancel + doomed deadline on the sharded engine: explicit
        # terminal states, slots released, survivor completes
        eng2 = InferenceEngine(
            m, EngineConfig(n_slots=2, max_len=32),
            backend=ShardedBackend(mesh_shape=(4, 2)))
        keep = eng2.submit(jobs[0][0], 6)
        victim = eng2.submit(jobs[1][0], 6)
        doomed = eng2.submit(jobs[2][0], 10, deadline_steps=2)
        assert doomed.state == "shed" and doomed.shed_reason == "deadline"
        for _ in range(2):
            eng2.step()
        eng2.cancel(victim)
        assert victim.state == "shed" and victim.shed_reason == "cancel"
        eng2.run()
        assert keep.state == "done" and len(keep.generated) == 6
        assert eng2.pool.n_active == 0 and eng2.pool.n_free == 2

        # tier-0 sharded output with resident tiers == plain local output
        local = InferenceEngine(
            ModelRegistry().load("h2o-danube-1.8b"),
            EngineConfig(n_slots=2, max_len=32))
        lk = local.submit(jobs[0][0], 6)
        local.run()
        assert tuple(keep.generated) == tuple(lk.generated)
        print("sharded tier swap + cancel hygiene OK")
    """)
