"""Block-pattern transformer covering all 10 assigned architectures.

One `ModelConfig` describes any of: dense decoder (GQA/MLA/SWA/local-global/
softcap), MoE decoder, Mamba/attention hybrid, pure SSM, and the whisper
encoder-decoder. Per-layer structure is a *periodic pattern* (`scan_period`,
plus `prelude_layers` un-scanned leading layers, e.g. deepseek's first dense
layer); parameters of layers in the same pattern slot are stacked and the
stack is consumed by one `lax.scan` — a 96-layer nemotron lowers to
period-size HLO, which is what keeps 512-device dry-run compile times sane.

Residual blocks are (optionally) wrapped in `jax.checkpoint` with a
configurable policy — required to fit the 340B train step in 16 GB/chip.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kratos as kr
from repro.instrument import scope, scoped
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 128
    vocab: int = 256
    head_dim: int = 0                     # 0 -> d_model // n_heads
    activation: str = "silu"
    gated_mlp: Optional[bool] = None      # None -> infer from activation
    norm: str = "rmsnorm"                 # 'rmsnorm' | 'layernorm'
    norm_eps: float = 1e-6
    rmsnorm_plus_one: bool = False        # gemma convention
    sandwich_norm: bool = False           # gemma2 pre+post norms
    tie_embeddings: bool = True
    use_rope: bool = True
    rope_theta: float = 10000.0
    emb_scale: float = 1.0
    residual_scale: float = 1.0           # minicpm scale_depth / sqrt(L)
    logit_softcap: Optional[float] = None
    attn_softcap: Optional[float] = None
    attn_scale: Optional[float] = None
    qk_norm: bool = False
    # windows: `window` applies to all attn layers; local_global_period=2
    # alternates local(window)/global (gemma2, local first)
    window: Optional[int] = None
    local_global_period: Optional[int] = None
    # MLA
    mla: bool = False
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    moe_period: int = 1                   # every Nth layer is MoE
    moe_offset: int = 0
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.001
    # Mamba / hybrid
    is_ssm: bool = False                  # all-mamba (falcon)
    attn_period: int = 0                  # jamba: attn layer every N (else mamba)
    attn_offset: int = 4
    d_state: int = 16
    d_conv: int = 4
    mamba_expand: int = 2
    bcdt_rms: bool = False
    ssm_chunk: int = 256
    # enc-dec (whisper)
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_positions: int = 1500             # whisper 30 s of frames
    # frontend stubs
    frontend: Optional[str] = None        # 'audio' | 'vision'
    n_img_tokens: int = 0                 # vision tokens prepended (llava)
    # scanning / remat
    scan_period: int = 1
    prelude_layers: int = 0
    remat: bool = True
    remat_policy: str = "nothing"         # 'nothing' | 'dots' | 'none'
    # the paper's technique, attachable to every projection
    kratos: kr.KratosSpec = kr.DENSE
    # compute dtypes
    param_dtype: str = "float32"
    dtype: str = "float32"                # activation dtype

    # ---- derived ----
    @property
    def dh(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def adtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND roofline accounting)."""
        cfg = self
        d, v = cfg.d_model, cfg.vocab
        total = v * d  # embeddings
        if not cfg.tie_embeddings:
            total += v * d
        for i in range(cfg.n_layers):
            kind = layer_kind(cfg, i)
            if kind["mixer"] == "attn":
                if cfg.mla:
                    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
                    if cfg.q_lora_rank:
                        total += d * cfg.q_lora_rank + cfg.q_lora_rank * cfg.n_heads * qd
                    else:
                        total += d * cfg.n_heads * qd
                    total += d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
                    total += cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)
                    total += cfg.n_heads * cfg.v_head_dim * d
                else:
                    total += d * cfg.n_heads * cfg.dh + 2 * d * cfg.n_kv_heads * cfg.dh \
                        + cfg.n_heads * cfg.dh * d
            else:
                di, r, st = cfg.d_inner, max(1, -(-d // 16)), cfg.d_state
                total += d * 2 * di + cfg.d_conv * di + di * (r + 2 * st) \
                    + r * di + di * st + di + di * d
            if kind["ffn"] == "moe":
                total += d * cfg.n_experts  # router
                total += cfg.n_experts * 3 * d * cfg.d_ff_expert
                total += cfg.n_shared_experts * 3 * d * cfg.d_ff_expert
            elif kind["ffn"] == "mlp":
                gated = cfg.gated_mlp if cfg.gated_mlp is not None \
                    else cfg.activation in ("silu", "gelu", "gelu_tanh")
                total += (3 if gated else 2) * d * cfg.d_ff
        if cfg.enc_dec:
            for _ in range(cfg.n_enc_layers):
                total += 4 * d * cfg.n_heads * cfg.dh + 3 * d * cfg.d_ff
            # decoder cross-attn
            total += cfg.n_layers * 4 * d * cfg.n_heads * cfg.dh
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k only) for 6·N_active·D."""
        cfg = self
        if not cfg.n_experts:
            return self.param_count()
        total = self.param_count()
        # subtract inactive routed experts
        for i in range(cfg.n_layers):
            if layer_kind(cfg, i)["ffn"] == "moe":
                inactive = cfg.n_experts - cfg.top_k
                total -= inactive * 3 * cfg.d_model * cfg.d_ff_expert
        return int(total)


# ---------------------------------------------------------------------------
# Per-layer pattern
# ---------------------------------------------------------------------------

def layer_kind(cfg: ModelConfig, i: int) -> Dict[str, Any]:
    """What lives at layer i: mixer ('attn'|'mamba') + window + ffn kind."""
    if cfg.is_ssm:
        mixer = "mamba"
    elif cfg.attn_period:
        mixer = "attn" if i % cfg.attn_period == cfg.attn_offset else "mamba"
    else:
        mixer = "attn"
    window = cfg.window
    if cfg.local_global_period and mixer == "attn":
        window = cfg.window if i % cfg.local_global_period == 0 else None
    if cfg.n_experts and i >= cfg.prelude_layers \
            and i % cfg.moe_period == cfg.moe_offset:
        ffn = "moe"
    elif cfg.n_experts and i < cfg.prelude_layers:
        ffn = "mlp"
    elif mixer == "mamba" and cfg.is_ssm:
        ffn = "none"                       # pure mamba blocks have no FFN
    elif cfg.attn_period and cfg.n_experts:
        ffn = "moe" if i % cfg.moe_period == cfg.moe_offset else "mlp"
    else:
        ffn = "mlp"
    return {"mixer": mixer, "window": window, "ffn": ffn}


def attn_cfg_for(cfg: ModelConfig, kind: Dict) -> A.AttnConfig:
    return A.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dh, rope_theta=cfg.rope_theta, use_rope=cfg.use_rope,
        causal=True, window=kind.get("window"), softcap=cfg.attn_softcap,
        qk_norm=cfg.qk_norm, attn_scale=cfg.attn_scale, mla=cfg.mla,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim)


def moe_cfg_for(cfg: ModelConfig) -> M.MoEConfig:
    return M.MoEConfig(
        d_model=cfg.d_model, n_experts=cfg.n_experts, top_k=cfg.top_k,
        d_ff_expert=cfg.d_ff_expert, n_shared=cfg.n_shared_experts,
        capacity_factor=cfg.capacity_factor, aux_loss_coef=cfg.aux_loss_coef,
        activation=cfg.activation)


def mamba_cfg_for(cfg: ModelConfig) -> S.MambaConfig:
    return S.MambaConfig(
        d_model=cfg.d_model, d_inner=cfg.d_inner, d_state=cfg.d_state,
        d_conv=cfg.d_conv, bcdt_rms=cfg.bcdt_rms, chunk=cfg.ssm_chunk)


def _norm_init(cfg: ModelConfig):
    return (L.layernorm_init if cfg.norm == "layernorm" else L.rmsnorm_init)(
        cfg.d_model, cfg.pdtype())


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return L.layernorm(p, x, cfg.norm_eps)
    return L.rmsnorm(p, x, cfg.norm_eps, scale_plus_one=cfg.rmsnorm_plus_one)


# ---------------------------------------------------------------------------
# Layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, i: int, cross: bool = False) -> Dict:
    kind = layer_kind(cfg, i)
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"pre_norm": _norm_init(cfg)}
    if cfg.sandwich_norm:
        p["post_norm"] = _norm_init(cfg)
    if kind["mixer"] == "attn":
        p["mixer"] = A.attn_init(ks[0], attn_cfg_for(cfg, kind), cfg.kratos,
                                 cfg.pdtype())
    else:
        p["mixer"] = S.mamba_init(ks[0], mamba_cfg_for(cfg), cfg.kratos,
                                  cfg.pdtype())
    if cross:
        p["cross_norm"] = _norm_init(cfg)
        ccfg = dataclasses.replace(attn_cfg_for(cfg, kind), cross=True,
                                   causal=False, use_rope=False)
        p["cross"] = A.attn_init(ks[1], ccfg, cfg.kratos, cfg.pdtype())
    if kind["ffn"] == "mlp":
        p["ffn_norm"] = _norm_init(cfg)
        if cfg.sandwich_norm:
            p["ffn_post_norm"] = _norm_init(cfg)
        gated = cfg.gated_mlp if cfg.gated_mlp is not None \
            else cfg.activation in ("silu", "gelu", "gelu_tanh")
        p["ffn"] = L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, gated=gated,
                              spec=cfg.kratos, dtype=cfg.pdtype())
    elif kind["ffn"] == "moe":
        p["ffn_norm"] = _norm_init(cfg)
        p["ffn"] = M.moe_init(ks[2], moe_cfg_for(cfg), cfg.kratos, cfg.pdtype())
    return p


def _probe_fanout(cfg: ModelConfig, kind: Dict, site: str) -> int:
    """Dense GEMM fan-out a probed activation feeds (serve.ledger probes:
    the FLOP/byte columns are fan-out-weighted trace-time constants)."""
    if site == "mixer":
        if kind["mixer"] == "mamba":
            return 2 * cfg.d_inner
        if cfg.mla:
            q = cfg.q_lora_rank if cfg.q_lora_rank \
                else cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
            return int(q + cfg.kv_lora_rank + cfg.qk_rope_dim)
        return (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.dh
    if kind["ffn"] == "moe":
        return int(cfg.n_experts
                   + 2 * (cfg.top_k + cfg.n_shared_experts) * cfg.d_ff_expert)
    gated = cfg.gated_mlp if cfg.gated_mlp is not None \
        else cfg.activation in ("silu", "gelu", "gelu_tanh")
    return (2 if gated else 1) * cfg.d_ff


@scoped("norm")
def _residual(x, h, rs):
    """The residual stream's add, named with the norms it feeds."""
    return x + h * rs


def _on_layer(apply, cache, layer, in_place: bool):
    """`apply(cache, **kw) -> (y, new_cache)` against one layer's cache.

    layer None: `cache` is the layer's own. Otherwise `cache` is the
    layer-stacked decode carry of the layer scan and `layer` this layer's
    row: a mixer that writes its rows in place gets the stack and `layer`
    and returns the stack; for any other (Mamba state, cross K/V) the row
    is read out and its update written back into the carry."""
    if layer is None or cache is None:
        return apply(cache)
    if in_place:
        return apply(cache, layer=layer)
    with scope("kv_write"):
        row = jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False),
            cache)
    y, new = apply(row)
    with scope("kv_write"):
        new = jax.tree_util.tree_map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(
                c, n.astype(c.dtype), layer, 0), cache, new)
    return y, new


def _layer_apply(p: Dict, x, cfg: ModelConfig, kind: Dict, *, backend="ref",
                 positions=None, cache=None, index=None, enc_out=None,
                 cross_cache=None, pages=None, probe=None, layer=None):
    """One residual block. Returns (x, aux, new_cache, new_cross_cache).

    pages: page-table operand for native paged decode — consumed by the
    ATTENTION mixer only (mamba state is O(1) resident, cross caches are
    written once at prefill; both keep the slab layout in the page store).
    probe: serve.ledger probe (or None) — taps the normalized mixer/FFN
    GEMM inputs (and, inside attention, the pre-wo merged heads) at trace
    time; the forward drains one summed row per layer.
    layer: decode over layer-stacked caches (see `_on_layer`): `cache` and
    `cross_cache` hold every scanned layer's leaves, `layer` is this one.
    """
    aux = jnp.zeros((), jnp.float32)
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    # batch-pinning constraints are differentiable: the transpose constrains
    # the COTANGENT too, which stops GSPMD from all-gathering the full
    # microbatch in backward dx/dW dots (4.5 GiB/layer on nemotron-340b).
    # 'dm_in' resolves to None in training and to 'data' under the 2D-TP
    # serving rules (weights stay fully sharded; activations psum instead).
    h = L.shard(_norm(cfg, p["pre_norm"], x), "batch", None, "dm_in")
    if probe is not None:
        probe.tap(h, _probe_fanout(cfg, kind, "mixer"))
    new_cache = new_cross = None
    if kind["mixer"] == "attn":         # GQA and MLA write rows in place
        h, new_cache = _on_layer(
            lambda c, **kw: A.attn_apply(
                p["mixer"], h, attn_cfg_for(cfg, kind), spec=cfg.kratos,
                backend=backend, positions=positions, cache=c, index=index,
                pages=pages, probe=probe, **kw),
            cache, layer, True)
    else:
        h, new_cache = _on_layer(
            lambda c: S.mamba_apply(
                p["mixer"], h, mamba_cfg_for(cfg), spec=cfg.kratos,
                backend=backend, cache=c, index=index),
            cache, layer, False)
    if cfg.sandwich_norm:
        h = _norm(cfg, p["post_norm"], h)
    x = _residual(x, h, rs)
    if "cross" in p:
        h = L.shard(_norm(cfg, p["cross_norm"], x), "batch", None, "dm_in")
        ccfg = dataclasses.replace(attn_cfg_for(cfg, kind), cross=True,
                                   causal=False, use_rope=False)
        h, new_cross = _on_layer(
            lambda c: A.attn_apply(
                p["cross"], h, ccfg, spec=cfg.kratos, backend=backend,
                kv_source=enc_out, cache=c, index=index, probe=probe),
            cross_cache, layer, False)
        x = _residual(x, h, rs)
    if kind["ffn"] != "none":
        h = L.shard(_norm(cfg, p["ffn_norm"], x), "batch", None, "dm_in")
        if probe is not None:
            probe.tap(h, _probe_fanout(cfg, kind, "ffn"))
        if kind["ffn"] == "moe":
            h, aux = M.moe_apply(p["ffn"], h, moe_cfg_for(cfg),
                                 spec=cfg.kratos, backend=backend)
        else:
            h = L.mlp_apply(p["ffn"], h, activation=cfg.activation,
                            spec=cfg.kratos, backend=backend, probe=probe)
        if cfg.sandwich_norm:
            h = _norm(cfg, p["ffn_post_norm"], h)
        x = _residual(x, h, rs)
    # 'seq_res' = sequence-sharded residual stream (SP): the remat carry that
    # lives across the whole layer scan is sharded over the 'model' axis, which
    # is what fits 96-layer x 4k-seq saved activations in 16 GB/chip. The cast
    # keeps the carry in the activation dtype — mixed-precision dots upcast to
    # f32 and the saved stack must not inherit that (2x remat memory).
    with scope("norm"):
        x = x.astype(cfg.adtype())
    x = L.shard(x, "batch", "seq_res", None)
    return x, aux, new_cache, new_cross


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def _stack_layers(layer_params: List[Dict]) -> Dict:
    """Stack per-layer trees leaf by leaf; consumes `layer_params`.

    Each leaf's per-layer arrays are dropped as soon as that leaf is
    stacked, so eager init holds the unstacked layers plus ONE stacked
    leaf, not two full copies of the weights (at published widths the
    difference is ~6 GiB of device memory)."""
    treedef = jax.tree_util.tree_structure(layer_params[0])
    cols = [list(c) for c in zip(*(jax.tree_util.tree_leaves(p)
                                   for p in layer_params))]
    layer_params.clear()
    stacked = []
    for i in range(len(cols)):
        stacked.append(jnp.stack(cols[i]))
        cols[i] = None
    return jax.tree_util.tree_unflatten(treedef, stacked)


def init(key, cfg: ModelConfig) -> Dict:
    """Build the full parameter tree."""
    n, period, prelude = cfg.n_layers, cfg.scan_period, cfg.prelude_layers
    if (n - prelude) % period:
        raise ValueError(f"(n_layers - prelude) = {n - prelude} not divisible "
                         f"by scan_period {period}")
    # pattern periodicity sanity: every scanned layer must match its slot
    for i in range(prelude, n):
        slot = (i - prelude) % period
        if layer_kind(cfg, i) != layer_kind(cfg, prelude + slot):
            raise ValueError(
                f"layer {i} kind {layer_kind(cfg, i)} != slot {slot} kind "
                f"{layer_kind(cfg, prelude + slot)}; adjust scan_period")
    keys = jax.random.split(key, n + cfg.n_enc_layers + 4)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[-1], cfg.vocab, cfg.d_model, cfg.pdtype()),
        "final_norm": _norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = kr.init(keys[-2], cfg.d_model, cfg.vocab, kr.DENSE,
                                 cfg.pdtype())
    cross = cfg.enc_dec
    params["prelude"] = [
        _layer_init(keys[i], cfg, i, cross) for i in range(prelude)]
    n_periods = (n - prelude) // period
    slots = []
    for s in range(period):
        layer_ids = [prelude + t * period + s for t in range(n_periods)]
        slots.append(_stack_layers(
            [_layer_init(keys[i], cfg, i, cross) for i in layer_ids]))
    params["blocks"] = slots
    if cfg.enc_dec:
        ek = keys[n:n + cfg.n_enc_layers]
        enc_cfg = dataclasses.replace(
            cfg, mla=False, is_ssm=False, attn_period=0, n_experts=0,
            use_rope=False)
        enc_layers = []
        for i in range(cfg.n_enc_layers):
            lp = _layer_init(ek[i], enc_cfg, i, cross=False)
            enc_layers.append(lp)
        params["enc_blocks"] = _stack_layers(enc_layers)
        params["enc_norm"] = _norm_init(cfg)
    return params


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _remat_wrap(cfg: ModelConfig, fn):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def encode(params, frames: jnp.ndarray, cfg: ModelConfig, *, backend="ref"):
    """Whisper encoder: frames are stub frame-embeddings (B, S_enc, d)."""
    x = frames.astype(cfg.adtype())
    x = x + _sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
    enc_cfg = dataclasses.replace(cfg, mla=False, is_ssm=False, attn_period=0,
                                  n_experts=0, use_rope=False)
    kind = {"mixer": "attn", "window": None, "ffn": "mlp"}

    def body(x, lp):
        acfg = dataclasses.replace(attn_cfg_for(enc_cfg, kind), causal=False)
        h = _norm(cfg, lp["pre_norm"], x)
        h, _ = A.attn_apply(lp["mixer"], h, acfg, spec=cfg.kratos,
                            backend=backend)
        x = x + h
        h = _norm(cfg, lp["ffn_norm"], x)
        h = L.mlp_apply(lp["ffn"], h, activation=cfg.activation,
                        spec=cfg.kratos, backend=backend)
        return x + h, None

    x, _ = jax.lax.scan(_remat_wrap(cfg, body), x, params["enc_blocks"])
    return _norm(cfg, params["enc_norm"], x)


def forward(params, tokens: jnp.ndarray, cfg: ModelConfig, *, backend="ref",
            img_embeds=None, enc_out=None, caches=None, index=None,
            last_only: bool = False, pages=None, probe=None,
            ) -> Tuple[jnp.ndarray, ...]:
    """Decoder forward. tokens: (B, S_text). Returns (logits, aux, caches) —
    or (logits, aux, caches, probe_mat) when `probe` is passed.

    img_embeds: (B, n_img, d) vision-stub tokens prepended (llava).
    enc_out: (B, S_enc, d) encoder output for cross-attention (whisper).
    caches: pytree matching params['prelude'/'blocks'] (+ 'cross') or None.
    index: decode position (None = full-sequence). A scalar decodes the
    whole batch at one shared position (lock-step serving); a (B,) vector
    gives every batch row its own position — the continuous-batching slab
    decode, where requests at different depths share one step.
    last_only: compute logits only for the final position (prefill) — the
    (B, S, vocab) logits tensor is by far the largest in a 32k prefill, and
    only the last column is consumed.
    pages: native paged-decode operand ({'table': (B, pp) int32 page table,
    'size': page_size, 'len': cache_len}); with it, `caches`' positional
    attention leaves are PAGE-MAJOR store leaves (serve.paging
    PageLayout.as_tree) that the attention layers read/write through the
    table — no slab view is ever materialized. Requires `index` (decode).
    probe: serve.ledger.LedgerProbe (or None). With a probe, every layer's
    GEMM taps sum into one (probe.cfg.width,) row; prelude rows collect in
    Python, scanned rows exit the layer scan as stacked ys, and the rows
    assemble into an (n_layers, width) matrix appended to the return tuple.
    Layer order is TRUE model order: scanned slot s, period t is layer
    `prelude + t * scan_period + s`.
    """
    with scope("embed"):
        x = L.embed(params["embed"], tokens,
                    scale=cfg.emb_scale).astype(cfg.adtype())
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    if cfg.enc_dec and not cfg.use_rope:
        s = x.shape[1]
        pe = _sinusoidal_positions(32768 if index is not None else s,
                                   cfg.d_model).astype(x.dtype)
        if index is None:
            x = x + pe[:s]
        elif jnp.ndim(index) == 0:
            x = x + jax.lax.dynamic_slice_in_dim(pe, index, s, axis=0)
        else:                       # per-slot positions: gather (B, S, d)
            x = x + jnp.take(pe, A._positions_for(index, s), axis=0)
    positions = None if index is None else A._positions_for(index, x.shape[1])
    aux_total = jnp.zeros((), jnp.float32)

    new_caches: Optional[Dict] = None if caches is None else \
        {"prelude": [], "blocks": [None] * cfg.scan_period}

    prelude_rows: List[jnp.ndarray] = []

    # prelude layers (unscanned)
    for li, lp in enumerate(params["prelude"]):
        kind = layer_kind(cfg, li)
        c = caches["prelude"][li] if caches is not None else None
        cc = c.get("cross") if (c is not None and "cross" in c) else None
        mc = c.get("mixer") if c is not None else None
        x, aux, nm, ncr = _layer_apply(
            lp, x, cfg, kind, backend=backend, positions=positions,
            cache=mc, index=index, enc_out=enc_out, cross_cache=cc,
            pages=pages, probe=probe)
        aux_total += aux
        if probe is not None:
            prelude_rows.append(probe.layer_row())
        if caches is not None:
            entry = {"mixer": nm}
            if ncr is not None:
                entry["cross"] = ncr
            new_caches["prelude"].append(entry)

    # scanned periodic blocks. Decode carries the stacked cache through the
    # scan and each layer writes its new rows into it (`layer` = the
    # layer's row); as scan xs/ys the whole stack would be sliced out and
    # stacked back every layer and micro-step. Prefill and training make a
    # cache rather than update one, and keep the xs/ys form.
    decode = caches is not None and index is not None
    n_periods = (cfg.n_layers - cfg.prelude_layers) // cfg.scan_period
    slot_rows: List[jnp.ndarray] = []
    for slot in range(cfg.scan_period):
        kind = layer_kind(cfg, cfg.prelude_layers + slot)
        stacked = params["blocks"][slot]
        c_stack = caches["blocks"][slot] if caches is not None else None

        def body(carry, xs, _kind=kind):
            x, aux, carried = carry
            if decode:
                (lp, layer), cache_sl = xs, carried
            elif caches is not None:
                (lp, cache_sl), layer = xs, None
            else:
                lp, cache_sl, layer = xs, {}, None
            x, a, nm, ncr = _layer_apply(
                lp, x, cfg, _kind, backend=backend, positions=positions,
                cache=cache_sl.get("mixer"), index=index, enc_out=enc_out,
                cross_cache=cache_sl.get("cross"), pages=pages, probe=probe,
                layer=layer)
            out = None
            if caches is not None:
                out = {"mixer": nm}
                if ncr is not None:
                    out["cross"] = ncr
            if decode:
                carried, out = out, None
            if probe is not None:
                out = (out, probe.layer_row())   # row exits via scan ys
            return (x, aux + a, carried), out

        if decode:
            init, xs = (x, aux_total, c_stack), \
                (stacked, jnp.arange(n_periods, dtype=jnp.int32))
        else:
            init = (x, aux_total, None)
            xs = (stacked, c_stack) if caches is not None else stacked
        with scope("layer_scan"):
            (x, aux_total, carried), new_stack = jax.lax.scan(
                _remat_wrap(cfg, body), init, xs)
        if probe is not None:
            new_stack, rows = new_stack          # (n_periods, width)
            slot_rows.append(rows)
        if caches is not None:
            new_caches["blocks"][slot] = carried if decode else new_stack

    with scope("norm"):
        if last_only:
            x = x[:, -1:]
        x = _norm(cfg, params["final_norm"], x)
    x = L.shard(x, "batch", "seq", None)
    logits = L.unembed(params["embed"], x, params.get("head"),
                       softcap=cfg.logit_softcap)
    if probe is None:
        return logits, aux_total, new_caches
    # assemble the per-layer probe matrix in true layer order
    mat = jnp.zeros((cfg.n_layers, probe.cfg.width), jnp.float32)
    for li, row in enumerate(prelude_rows):
        mat = mat.at[li].set(row)
    for slot, rows in enumerate(slot_rows):
        ids = cfg.prelude_layers + slot \
            + cfg.scan_period * jnp.arange(n_periods)
        mat = mat.at[ids].set(rows)
    return logits, aux_total, new_caches, mat


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def make_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.float32) -> Dict:
    """Decode caches matching the params tree layout (prelude + stacked)."""
    def one(i: int) -> Dict:
        kind = layer_kind(cfg, i)
        if kind["mixer"] == "attn":
            mc = A.make_cache(attn_cfg_for(cfg, kind), batch, max_len, dtype)
        else:
            mc = S.make_mamba_cache(mamba_cfg_for(cfg), batch, dtype)
        entry = {"mixer": mc}
        if cfg.enc_dec:
            entry["cross"] = {
                "k": jnp.zeros((batch, cfg.n_kv_heads, cfg.enc_positions, cfg.dh), dtype),
                "v": jnp.zeros((batch, cfg.n_kv_heads, cfg.enc_positions, cfg.dh), dtype),
            }
        return entry

    prelude = [one(i) for i in range(cfg.prelude_layers)]
    n_periods = (cfg.n_layers - cfg.prelude_layers) // cfg.scan_period
    blocks = []
    for s in range(cfg.scan_period):
        ids = [cfg.prelude_layers + t * cfg.scan_period + s
               for t in range(n_periods)]
        blocks.append(_stack_layers([one(i) for i in ids]))
    return {"prelude": prelude, "blocks": blocks}


# ---------------------------------------------------------------------------
# Sampling (device-side; fused into the serving decode step)
# ---------------------------------------------------------------------------

@scoped("sample")
def sample_tokens(logits: jnp.ndarray, key, temperature: jnp.ndarray,
                  ) -> jnp.ndarray:
    """Per-row Gumbel-max / greedy sampling, fully on device.

    logits: (B, vocab); temperature: (B,) float32 per-slot vector (<= 0 means
    greedy argmax for that row — no rng consumed semantics: the key is split
    by the caller per micro-step regardless, which is what makes K-step decode
    blocks reproducible for any K). Returns (B,) int32 token ids.

    Gumbel-max sampling (argmax(logits/T + G)) is exactly categorical
    sampling from softmax(logits/T), so the full-vocab softmax never needs to
    be materialized and only the sampled ids ever cross to the host.
    """
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    g = jax.random.gumbel(key, logits.shape, jnp.float32)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    scores = jnp.where((temperature > 0.0)[:, None],
                       logits / safe_t + g, logits)
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray,
            mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean cross-entropy; labels (B, S) int32; mask (B, S) optional."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
