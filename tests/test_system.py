"""End-to-end system behaviour: learning, serving, optimizer, benchmarks."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.core import bench_specs as BS
from repro.data.pipeline import DataConfig
from repro.optim import adamw as O
from repro.train import TrainLoopConfig, run_training


def test_training_learns_markov_task():
    cfg = C.get_smoke("h2o_danube_1_8b")
    out = run_training(
        cfg, O.OptimizerConfig(lr=2e-3, warmup_steps=10, total_steps=60),
        DataConfig(vocab=cfg.vocab, batch=8, seq=32, seed=1),
        TrainLoopConfig(steps=60, log_every=0))
    losses = [h["loss"] for h in out["history"]]
    assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])


def test_grad_accum_equivalent_gradients():
    from repro.distributed import steps as ST
    cfg = C.get_smoke("h2o_danube_1_8b")
    opt = O.OptimizerConfig(lr=0.0, weight_decay=0.0, clip_norm=None)
    state = ST.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                   jnp.int32)}
    _, m1 = jax.jit(ST.make_train_step(cfg, opt, grad_accum=1))(state, batch)
    _, m4 = jax.jit(ST.make_train_step(cfg, opt, grad_accum=4))(state, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    rel = abs(float(m1["grad_norm"]) - float(m4["grad_norm"])) \
        / float(m1["grad_norm"])
    assert rel < 1e-3


def test_adamw_converges_on_quadratic():
    opt_cfg = O.OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=200,
                                weight_decay=0.0, clip_norm=None,
                                min_lr_ratio=1.0)
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = O.adamw_init(params, opt_cfg)
    target = jnp.asarray([1.0, 2.0, 3.0])

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: jnp.sum((q["w"] - target) ** 2))(p)
        return O.adamw_update(g, s, p, opt_cfg)[:2]

    for _ in range(200):
        params, state = step(params, state)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_adamw_schedule_and_clip():
    cfg = O.OptimizerConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(O.warmup_cosine(cfg, jnp.int32(s))) for s in (0, 5, 10, 100)]
    assert lrs[0] == 0.0 and lrs[1] == pytest.approx(5e-4)
    assert lrs[2] == pytest.approx(1e-3)
    assert lrs[3] == pytest.approx(1e-4, rel=1e-2)     # min_lr_ratio * lr
    g = {"w": jnp.full((4,), 10.0)}
    clipped, gn = O.clip_by_global_norm(g, 1.0)
    assert gn == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["w"])) == pytest.approx(1.0, rel=1e-4)


def test_bench_specs_table2_grid():
    assert len(BS.TABLE_II) == 16                      # 8 kernels x S/L
    assert len(BS.SPARSITIES) == 10 and len(BS.PRECISIONS) == 4
    swept = BS.sweep(BS.BY_NAME["gemmt-RP-S"])
    assert len(swept) == 10 * 5                        # + bf16 baseline row
    for spec in BS.TABLE_II:
        m, n, p = spec.gemm_dims()
        assert m > 0 and n > 0 and p > 0
        assert spec.ops_per_invocation() <= m * n * p
        r = spec.resource_report()
        assert r["mac_fraction"] == 1.0                # base grid is dense


def test_bench_kernel_instantiations_execute():
    import dataclasses
    for name in ("gemmt-RP-S", "gemms-RP-S", "conv1d-FU-S", "conv2d-RP-S"):
        spec = dataclasses.replace(BS.BY_NAME[name], sparsity=0.5)
        params, x, fn = BS.instantiate(spec)
        y = jax.jit(fn)(params, x)
        assert np.isfinite(np.asarray(y)).all(), name


def test_frontend_stubs_shapes():
    from repro.models import frontends as F
    wav = np.random.default_rng(0).standard_normal((2, 48000)).astype(np.float32)
    frames = F.whisper_frames(wav, d_model=64)
    assert frames.shape == (2, 1500, 64)
    img = np.random.default_rng(1).random((2, 336, 336, 3)).astype(np.float32)
    patches = F.llava_patches(img, d_model=64)
    assert patches.shape == (2, 2880, 64)
    # determinism (fixed projections)
    np.testing.assert_array_equal(np.asarray(F.llava_patches(img, 64)),
                                  np.asarray(patches))


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The entry points' cache lands where JAX_COMPILATION_CACHE_DIR says,
    else in the checkout's fixed .jax_cache — never a temp or pid path."""
    from pathlib import Path
    from repro.launch import compile_cache as CC
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(CC.ENV, raising=False)
    else:
        monkeypatch.setenv(CC.ENV, str(tmp_path / env_dir))
    try:
        got = CC.configure()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    if env_dir is None:
        root = Path(__file__).resolve().parents[1]
        assert got == now == str(root / ".jax_cache")
    else:
        assert got == str(tmp_path / env_dir)
        assert now == prev                  # JAX reads the variable itself
