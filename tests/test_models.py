"""Model substrate: per-arch smoke tests + decode/prefill consistency +
MoE / SSM / attention oracles."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.core import kratos as kr
from repro.distributed import steps as ST
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import transformer as T
from repro.optim import adamw as O


def _batch_for(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    out = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.enc_dec:
        out["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.enc_positions, cfg.d_model)) * 0.1,
            jnp.float32)
    if cfg.n_img_tokens:
        out["img_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.n_img_tokens, cfg.d_model)) * 0.1,
            jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Per-arch smoke: one train step, reduced config, finite loss + right shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_arch_smoke_train_step(arch):
    cfg = C.get_smoke(arch)
    state = ST.init_train_state(jax.random.PRNGKey(0), cfg, O.OptimizerConfig())
    batch = _batch_for(cfg)
    step = jax.jit(ST.make_train_step(cfg, O.OptimizerConfig()))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_state["step"]) == 1
    # params changed and stayed finite
    leaves = jax.tree_util.tree_leaves(new_state["params"])
    assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in leaves)


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_arch_smoke_forward_shapes(arch):
    cfg = C.get_smoke(arch)
    params = T.init(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg)
    enc_out = None
    if cfg.enc_dec:
        enc_out = T.encode(params, batch["frames"], cfg)
        assert enc_out.shape == (2, cfg.enc_positions, cfg.d_model)
    logits, aux, _ = T.forward(params, batch["tokens"], cfg,
                               img_embeds=batch.get("img_embeds"),
                               enc_out=enc_out)
    exp_s = 16 + cfg.n_img_tokens
    assert logits.shape == (2, exp_s, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# ---------------------------------------------------------------------------
# decode == forward (teacher-forced): THE serving-correctness invariant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["h2o_danube_1_8b", "gemma2_27b",
                                  "minicpm3_4b", "falcon_mamba_7b",
                                  "jamba_v0_1_52b", "whisper_large_v3",
                                  "deepseek_v2_lite_16b"])
def test_decode_matches_forward(arch):
    """Prefill s0 tokens then decode the rest one-by-one; logits must match
    the full-sequence forward at every position.

    MoE archs run at no-drop capacity: capacity-based routing drops *depend
    on the routing-group token count by design*, so exact prefill/forward
    equivalence only holds when no token overflows an expert."""
    cfg = C.get_smoke(arch)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    b, s0, s1 = 2, 8, 4
    params = T.init(jax.random.PRNGKey(1), cfg)
    batch = _batch_for(cfg, b=b, s=s0 + s1, seed=3)
    toks = batch["tokens"]
    enc_out = None
    if cfg.enc_dec:
        enc_out = T.encode(params, batch["frames"], cfg)
    img = batch.get("img_embeds")

    full_logits, _, _ = T.forward(params, toks, cfg, enc_out=enc_out,
                                  img_embeds=img)

    n_img = cfg.n_img_tokens
    caches = T.make_caches(cfg, b, s0 + s1 + n_img)
    pre_logits, _, caches = T.forward(params, toks[:, :s0], cfg,
                                      caches=caches, enc_out=enc_out,
                                      img_embeds=img)
    np.testing.assert_allclose(
        np.asarray(pre_logits, np.float32),
        np.asarray(full_logits[:, :s0 + n_img], np.float32),
        rtol=2e-2, atol=2e-3)

    for t in range(s1):
        index = jnp.int32(n_img + s0 + t)
        step_logits, _, caches = T.forward(
            params, toks[:, s0 + t:s0 + t + 1], cfg, caches=caches,
            index=index, enc_out=enc_out)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0], np.float32),
            np.asarray(full_logits[:, n_img + s0 + t], np.float32),
            rtol=2e-2, atol=2e-3,
            err_msg=f"decode step {t} diverged from forward")


def test_swa_decode_beyond_window():
    """Sliding-window decode with the circular cache: decoding past the
    window must equal full forward (which masks to the window anyway)."""
    cfg = C.get_smoke("h2o_danube_1_8b")
    assert cfg.window is not None
    w = cfg.window
    total = w + 6                       # decode well past the window
    b = 1
    params = T.init(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, total)), jnp.int32)
    full_logits, _, _ = T.forward(params, toks, cfg)

    caches = T.make_caches(cfg, b, w)   # cache is O(window), not O(total)!
    _, _, caches = T.forward(params, toks[:, :4], cfg, caches=caches)
    for t in range(4, total):
        step_logits, _, caches = T.forward(
            params, toks[:, t:t + 1], cfg, caches=caches, index=jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=2e-2, atol=2e-3, err_msg=f"pos {t}")


# ---------------------------------------------------------------------------
# MoE: grouped-capacity routing vs per-token dense oracle
# ---------------------------------------------------------------------------

def test_moe_matches_dense_oracle_high_capacity():
    cfg = M.MoEConfig(d_model=32, n_experts=8, top_k=2, d_ff_expert=64,
                      n_shared=1, capacity_factor=8.0)   # no drops
    params = M.moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32)) * 0.5
    y, aux = M.moe_apply(params, x, cfg)
    want = M.moe_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-3, atol=1e-4)
    assert float(aux) > 0.0


def test_moe_capacity_drops_are_partial_not_nan():
    cfg = M.MoEConfig(d_model=16, n_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=0.25)              # heavy drops
    params = M.moe_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 16))
    y, _ = M.moe_apply(params, x, cfg)
    assert np.isfinite(np.asarray(y)).all()


def test_moe_decode_single_token_group():
    cfg = M.MoEConfig(d_model=16, n_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=8.0)
    params = M.moe_init(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (8, 1, 16)) * 0.5
    y, _ = M.moe_apply(params, x, cfg)
    want = M.moe_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# SSM: chunked associative scan vs sequential recurrence; decode streaming
# ---------------------------------------------------------------------------

def test_ssm_chunked_scan_matches_sequential():
    b, s, di, st = 2, 37, 8, 4          # deliberately not a chunk multiple
    rng = np.random.default_rng(9)
    dA = jnp.asarray(rng.uniform(0.7, 1.0, (b, s, di, st)), jnp.float32)
    dBx = jnp.asarray(rng.standard_normal((b, s, di, st)) * 0.1, jnp.float32)
    cfg = S.MambaConfig(d_model=16, d_inner=di, d_state=st, chunk=8)
    got = S._scan_chunked(dA, dBx, cfg)
    want = S.mamba_scan_ref(dA, dBx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_mamba_decode_matches_full():
    cfg = S.MambaConfig(d_model=16, d_inner=32, d_state=4, d_conv=4, chunk=8)
    params = S.mamba_init(jax.random.PRNGKey(10), cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 12, 16)) * 0.5
    y_full, _ = S.mamba_apply(params, x, cfg)

    cache = S.make_mamba_cache(cfg, 2)
    y_pre, cache = S.mamba_apply(params, x[:, :6], cfg, cache=cache)
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(y_full[:, :6]),
                               rtol=1e-3, atol=1e-4)
    for t in range(6, 12):
        y_t, cache = S.mamba_apply(params, x[:, t:t + 1], cfg, cache=cache,
                                   index=jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(y_t[:, 0]), np.asarray(y_full[:, t]),
            rtol=1e-3, atol=1e-4, err_msg=f"pos {t}")


# ---------------------------------------------------------------------------
# attention details
# ---------------------------------------------------------------------------

def test_chunked_attention_equals_dense():
    b, h, s, d = 1, 2, 32, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d))
               for i in (12, 13, 14))
    pos = jnp.arange(s)
    dense = A.attention_positional(q, k, v, pos, pos, causal=True)
    chunked = A.attention_chunked(q, k, v, pos, pos, causal=True, chunk=8)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=1e-4, atol=1e-5)


def test_rope_preserves_relative_positions():
    """<rope(q,i), rope(k,j)> depends only on i - j."""
    d = 16
    q = jax.random.normal(jax.random.PRNGKey(15), (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(16), (1, 1, 1, d))

    def score(i, j):
        qr = L.apply_rope(q, jnp.asarray([i]))
        kr_ = L.apply_rope(k, jnp.asarray([j]))
        return float(jnp.sum(qr * kr_))

    assert abs(score(5, 3) - score(105, 103)) < 1e-3
    assert abs(score(5, 3) - score(7, 3)) > 1e-4   # but not absolute-invariant


def test_gqa_head_grouping_matches_repeat():
    cfg = A.AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
    params = A.gqa_init(jax.random.PRNGKey(17), cfg)
    x = jax.random.normal(jax.random.PRNGKey(18), (2, 8, 32))
    y, _ = A.gqa_apply(params, x, cfg)
    assert y.shape == (2, 8, 32)
    assert np.isfinite(np.asarray(y)).all()


def test_mla_cache_is_compressed():
    """MLA decode cache stores (kv_lora + rope) per token, not 2*H*dh."""
    cfg = C.get_smoke("deepseek_v2_lite_16b")
    caches = T.make_caches(cfg, batch=1, max_len=64)
    sizes = [np.prod(l.shape) for l in jax.tree_util.tree_leaves(caches)]
    acfg = T.attn_cfg_for(cfg, T.layer_kind(cfg, 1))
    per_tok_full = 2 * cfg.n_heads * acfg.q_head_dim
    per_tok_mla = cfg.kv_lora_rank + cfg.qk_rope_dim
    assert per_tok_mla < per_tok_full / 3
    total = sum(sizes)
    assert total <= cfg.n_layers * 64 * per_tok_mla * 1 * 1.1


# ---------------------------------------------------------------------------
# Kratos attached to a whole model
# ---------------------------------------------------------------------------

def test_kratos_spec_through_full_model():
    spec = kr.KratosSpec(sparsity=0.5, bits=8, bk=8, bn=8)
    cfg = dataclasses.replace(C.get_smoke("h2o_danube_1_8b"), kratos=spec)
    state = ST.init_train_state(jax.random.PRNGKey(19), cfg,
                                O.OptimizerConfig())
    batch = _batch_for(cfg)
    step = jax.jit(ST.make_train_step(cfg, O.OptimizerConfig()))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # pruned blocks stayed zero after the update (masked-weight training)
    wq = state["params"]["blocks"][0]["mixer"]["wq"]["w"][0]
    plan = kr.plan_for(*wq.shape, spec)
    from repro.core import sparsity as sp
    mask = sp.plan_mask(plan)
    np.testing.assert_allclose(np.asarray(wq) * (1 - mask), 0.0, atol=1e-6)


def test_stack_layers_matches_stack_and_releases_layers():
    """Init stacks the scanned layers leaf by leaf into the same arrays a
    whole-tree stack gives, and lets go of the per-layer list it was given
    (eager init at published widths would otherwise hold two copies)."""
    cfg = C.get_smoke("h2o-danube-1.8b")
    layers = [T._layer_init(jax.random.PRNGKey(i), cfg, 0) for i in range(3)]
    want = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    given = list(layers)
    got = T._stack_layers(given)
    assert given == []
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
