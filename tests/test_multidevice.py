"""Multi-device semantics on 8 virtual CPU devices.

jax locks the device count at first init, so each test runs a small script
in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8 —
the same isolation discipline the dry-run uses.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")


def run_script(body: str, timeout=240) -> str:
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, timeout=timeout,
                       env=ENV, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_pipeline_parallelism_matches_sequential():
    """GPipe over a 4-pod axis == sequential stage application (exact)."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_apply, pipeline_reference
        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("pod",))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
        stage_fn = lambda w, h: jnp.tanh(h @ w)
        got = pipeline_apply(ws, x, stage_fn, mesh, axis="pod")
        want = pipeline_reference(ws, x, stage_fn)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        print("pipeline OK")
    """)


def test_pipeline_gradients_flow():
    """Backprop through the ppermute schedule: grads match sequential."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_apply, pipeline_reference
        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("pod",))
        ws = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8))
        fn = lambda w, h: jnp.tanh(h @ w)
        g1 = jax.grad(lambda w: jnp.sum(pipeline_apply(w, x, fn, mesh)**2))(ws)
        g2 = jax.grad(lambda w: jnp.sum(pipeline_reference(w, x, fn)**2))(ws)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)
        print("pipeline grads OK")
    """)


def test_cross_pod_int8_psum():
    """int8-on-the-wire all-reduce: near-f32 psum, 4x fewer wire bytes."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.distributed.compression import cross_pod_psum_int8
        devs = np.array(jax.devices()).reshape(8)
        mesh = Mesh(devs, ("pod",))
        # distinct per-pod partials, replicated layout
        def make(i):
            return jax.random.normal(jax.random.PRNGKey(i), (32, 32))
        xs = [np.asarray(make(i)) for i in range(8)]
        want = np.sum(xs, axis=0)
        # place per-device values via device_put on a sharded axis then shard_map
        x = jnp.stack(xs)                   # (8, 32, 32)
        sh = NamedSharding(mesh, P("pod"))
        xd = jax.device_put(x, sh)
        import functools
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                           out_specs=P("pod"), check_vma=False)
        def reduce_fn(xx):
            from repro.distributed.compression import _quant_int8
            q, scale = _quant_int8(xx[0])
            smax = jax.lax.pmax(scale, "pod")
            qq = jnp.clip(jnp.round(xx[0] / smax), -127, 127).astype(jnp.int32)
            total = jax.lax.psum(qq, "pod")
            return (total.astype(jnp.float32) * smax)[None]
        got = np.asarray(reduce_fn(xd))[0]
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert err < 0.05, err
        print("int8 psum OK, rel err", err)
    """)


def test_elastic_remesh_restore():
    """Checkpoint saved while sharded on mesh A restores onto mesh B."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.launch.mesh import make_mesh
        mesh_a = make_mesh((8, 1), ("data", "model"))
        mesh_b = make_mesh((2, 4), ("data", "model"))
        tree = {"w": jax.device_put(
            jnp.arange(64.0).reshape(8, 8),
            NamedSharding(mesh_a, P("data", None)))}
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(1, tree)
        target = {"w": NamedSharding(mesh_b, P("data", "model"))}
        restored, step = mgr.restore(tree, shardings=target)
        assert step == 1
        assert restored["w"].sharding == target["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("remesh OK")
    """)


def test_spmd_train_step_8dev_matches_1dev():
    """The sharded train step computes the same loss as single-device."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs as C
        from repro.distributed import sharding as SH, steps as ST
        from repro.optim import adamw as O
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = C.get_smoke("h2o_danube_1_8b")
        opt = O.OptimizerConfig()
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                       jnp.int32)}
        state = ST.init_train_state(jax.random.PRNGKey(0), cfg, opt)
        step = ST.make_train_step(cfg, opt)
        _, m_ref = jax.jit(step)(state, batch)     # default: 1-device exec

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        with SH.use_mesh(mesh):
            psh = SH.param_shardings(mesh, state["params"])
            st_sh = {"params": psh,
                     "opt": {"m": psh, "v": psh,
                             "count": NamedSharding(mesh, P())},
                     "step": NamedSharding(mesh, P())}
            bsh = {k: NamedSharding(mesh, P(("data",), None))
                   for k in batch}
            sharded = jax.jit(step, in_shardings=(st_sh, bsh))
            _, m_spmd = sharded(state, batch)
        l1, l2 = float(m_ref["loss"]), float(m_spmd["loss"])
        assert abs(l1 - l2) / l1 < 1e-3, (l1, l2)
        print("spmd==1dev OK", l1, l2)
    """)


def test_mini_dryrun_smoke_config_on_8dev_mesh():
    """End-to-end dry-run machinery on a small mesh: lower+compile+analyze."""
    run_script("""
        import jax, numpy as np
        from repro import configs as C
        from repro.analysis import hlo as HA
        from repro.distributed import sharding as SH, steps as ST
        from repro.optim import adamw as O
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        cfg = C.get_smoke("gemma2_27b")
        mesh = make_mesh((4, 2), ("data", "model"))
        opt = O.OptimizerConfig()
        state = jax.eval_shape(
            lambda k: ST.init_train_state(k, cfg, opt),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        from jax.sharding import NamedSharding, PartitionSpec as P
        with SH.use_mesh(mesh):
            psh = SH.param_shardings(mesh, state["params"])
            st_sh = {"params": psh,
                     "opt": {"m": psh, "v": psh,
                             "count": NamedSharding(mesh, P())},
                     "step": NamedSharding(mesh, P())}
            bsh = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
            step = ST.make_train_step(cfg, opt)
            compiled = jax.jit(step, in_shardings=(st_sh, bsh)).lower(
                state, batch).compile()
        r = HA.analyze(compiled.as_text())
        assert r["flops"] > 0 and r["wire_bytes"] > 0
        assert compiled.memory_analysis() is not None
        print("mini dryrun OK flops=%.3g wire=%.3g" % (r["flops"], r["wire_bytes"]))
    """)
