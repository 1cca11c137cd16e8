"""Mixture-of-Experts: shared + routed experts with top-k capacity routing.

Covers deepseek-moe-16b / deepseek-v2-lite (2 shared + 64 routed, top-6,
fine-grained experts) and jamba (16 routed, top-2, no shared).

Dispatch is **grouped sort-based** (GShard-style groups, static shapes,
EP-friendly):

  * tokens are routed in groups — one group per batch row for full
    sequences (so the argsort/searchsorted run *locally* per data shard; no
    distributed sort in the SPMD partition), or a single global group for
    decode steps (S=1, where per-row groups would waste E*C slots per
    token);
  * within a group: top-k gate -> stable sort by expert id -> each expert
    takes its contiguous run up to capacity C = ceil(G*k/E * factor);
    overflow tokens drop (residual passes through);
  * expert batches (E, C, d) are einsum'd against expert weights with E
    sharded over the 'expert' (model) mesh axis — XLA inserts the
    dispatch/combine all-to-alls;
  * combine: weighted scatter-add back to token order.

Expert FFN projections are Kratos-able: with a KratosSpec attached, every
expert's gate/up/down GEMM runs block-sparse/quantized (same plan across
experts, different learned values).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kratos as kr
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    router_norm_topk: bool = True       # normalize top-k weights (deepseek)
    aux_loss_coef: float = 0.001
    activation: str = "silu"


def moe_init(key, cfg: MoEConfig, spec: kr.KratosSpec = kr.DENSE,
             dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    std = d ** -0.5
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, e), jnp.float32) * std},
        "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * std,
        "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * std,
        "w_down": jax.random.normal(ks[3], (e, f, d), dtype) * (f ** -0.5),
    }
    if cfg.n_shared:
        p["shared"] = L.mlp_init(ks[4], d, cfg.n_shared * f, gated=True,
                                 spec=spec, dtype=dtype)
    return p


def capacity(cfg: MoEConfig, group_tokens: int) -> int:
    c = int(-(-group_tokens * cfg.top_k // cfg.n_experts)
            * cfg.capacity_factor)
    return max(cfg.top_k, min(c, group_tokens))


def _expert_ffn(p, xe: jnp.ndarray, cfg: MoEConfig, spec: kr.KratosSpec,
                backend: str) -> jnp.ndarray:
    """xe: (G, E, C, d) -> (G, E, C, d). Kratos-sparse when spec set."""
    act = L.ACTIVATIONS[cfg.activation]
    tree = (not spec.is_identity and spec.impl == "tree"
            and kr.plan_for(cfg.d_model, cfg.d_ff_expert, spec) is not None)
    if not tree:
        g = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(xe.dtype))
        u = jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(xe.dtype))
        h = act(g) * u
        h = L.shard(h, None, "expert", None, "ffn")
        return jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(xe.dtype))

    # tree path: vmap the Kratos gathered-block matmul over experts
    def one(we_gate, we_up, we_down, xx):      # xx: (G, C, d)
        g = kr.apply({"w": we_gate}, xx, spec, backend=backend,
                     name="w_gate")
        u = kr.apply({"w": we_up}, xx, spec, backend=backend, name="w_up")
        h = act(g) * u
        return kr.apply({"w": we_down}, h, spec, backend=backend,
                        name="w_down")

    out = jax.vmap(one, in_axes=(0, 0, 0, 1), out_axes=1)(
        p["w_gate"], p["w_up"], p["w_down"], xe)
    return out


def _route_group(xf, router_w, cfg: MoEConfig, c: int):
    """One routing group. xf: (G, d). Returns dispatch data + aux stats."""
    g_tokens = xf.shape[0]
    e, k = cfg.n_experts, cfg.top_k
    logits = xf.astype(jnp.float32) @ router_w                  # (G, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, k)                      # (G, k)
    if cfg.router_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)

    flat_e = top_e.reshape(-1)                                  # (G*k,)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos_in_e = jnp.arange(g_tokens * k) - starts[sorted_e]
    keep = pos_in_e < c
    slot = jnp.where(keep, sorted_e * c + pos_in_e, e * c)

    slot_to_assign = jnp.full((e * c + 1,), g_tokens * k, jnp.int32)
    slot_to_assign = slot_to_assign.at[slot].set(order.astype(jnp.int32))
    slot_assign = slot_to_assign[:e * c]
    slot_valid = slot_assign < g_tokens * k
    slot_token = jnp.where(slot_valid, slot_assign // k, 0)
    slot_weight = jnp.where(
        slot_valid, flat_w[jnp.where(slot_valid, slot_assign, 0)], 0.0)

    dispatch_frac = jnp.mean(
        jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1)) * k
    gate_frac = jnp.mean(gates, axis=0)
    return slot_token, slot_weight, slot_valid, dispatch_frac, gate_frac


def moe_apply(params: Dict, x: jnp.ndarray, cfg: MoEConfig, *,
              spec: kr.KratosSpec = kr.DENSE, backend: str = "ref",
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss)."""
    b, s, d = x.shape
    e = cfg.n_experts
    # group = batch row for sequences (local sort per data shard);
    # single global group for decode (S == 1).
    if s > 1:
        n_groups, g_tokens = b, s
    else:
        n_groups, g_tokens = 1, b * s
    c = capacity(cfg, g_tokens)
    xg = x.reshape(n_groups, g_tokens, d)

    slot_token, slot_weight, slot_valid, dfrac, gfrac = jax.vmap(
        lambda xf: _route_group(xf, params["router"]["w"], cfg, c))(xg)

    aux = cfg.aux_loss_coef * e * jnp.mean(
        jnp.sum(dfrac * gfrac, axis=-1))

    # dispatch: (G, E*C, d)
    xe = jnp.take_along_axis(xg, slot_token[..., None], axis=1)
    xe = xe * slot_valid[..., None].astype(xe.dtype)
    xe = xe.reshape(n_groups, e, c, d)
    xe = L.shard(xe, None, "expert", None, None)

    ye = _expert_ffn(params, xe, cfg, spec, backend)            # (G,E,C,d)
    ye = L.shard(ye, None, "expert", None, None)

    # combine: weighted scatter-add back to token order
    contrib = ye.reshape(n_groups, e * c, d) \
        * slot_weight[..., None].astype(x.dtype)
    tgt = jnp.where(slot_valid, slot_token, g_tokens)           # drop slot
    yg = jnp.zeros((n_groups, g_tokens, d), x.dtype)
    yg = jax.vmap(lambda acc, idx, val: acc.at[idx].add(val, mode="drop"))(
        yg, tgt, contrib)
    y = yg.reshape(b, s, d)

    if cfg.n_shared:
        y = y + L.mlp_apply(params["shared"], x, activation=cfg.activation,
                            spec=spec, backend=backend)
    return y, aux


def moe_ref(params: Dict, x: jnp.ndarray, cfg: MoEConfig) -> jnp.ndarray:
    """Dense per-token oracle (no capacity drops) for unit tests."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ params["router"]["w"]
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, cfg.top_k)
    if cfg.router_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)
    act = L.ACTIVATIONS[cfg.activation]

    def ffn_e(eid, xx):
        g = xx @ params["w_gate"][eid].astype(xx.dtype)
        u = xx @ params["w_up"][eid].astype(xx.dtype)
        return (act(g) * u) @ params["w_down"][eid].astype(xx.dtype)

    all_out = jnp.stack([ffn_e(i, xf) for i in range(cfg.n_experts)])  # (E,T,d)
    sel = all_out[top_e, jnp.arange(xf.shape[0])[:, None]]             # (T,k,d)
    yf = jnp.sum(sel * top_w[..., None].astype(x.dtype), axis=1)
    y = yf.reshape(b, s, d)
    if cfg.n_shared:
        y = y + L.mlp_apply(params["shared"], x, activation=cfg.activation)
    return y
