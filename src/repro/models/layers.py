"""Shared model building blocks: norms, RoPE, MLPs, embeddings.

Every weight-stationary projection goes through `core.kratos`, so any layer
can be made block-sparse / low-precision by attaching a KratosSpec in the
model config — the paper's technique as a cross-cutting feature.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kratos as kr
from repro.instrument import scope, scoped


# ---------------------------------------------------------------------------
# Sharding helper: models annotate activations with *logical* axes; the
# distributed runtime installs a resolver from logical -> mesh axes. On a
# bare CPU (smoke tests) the resolver is absent and this is the identity.
# ---------------------------------------------------------------------------

_LOGICAL_RESOLVER = None  # set by repro.distributed.sharding.use_mesh(...)


def set_logical_resolver(fn) -> None:
    global _LOGICAL_RESOLVER
    _LOGICAL_RESOLVER = fn


def shard(x: jnp.ndarray, *logical_axes: Optional[str]) -> jnp.ndarray:
    if _LOGICAL_RESOLVER is None:
        return x
    return _LOGICAL_RESOLVER(x, logical_axes)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> Dict:
    return {"scale": jnp.ones((d,), dtype)}


@scoped("norm")
def rmsnorm(params: Dict, x: jnp.ndarray, eps: float = 1e-6,
            scale_plus_one: bool = False) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(var + eps)
    s = params["scale"].astype(jnp.float32)
    if scale_plus_one:   # gemma-style (weights stored as deltas from 1)
        s = s + 1.0
    return (h * s).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32) -> Dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


@scoped("norm")
def layernorm(params: Dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    out = h * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


@scoped("rope")
def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (B, H, S, Dh) (Dh even); positions: (S,) or (B, S) absolute."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                       # (dh/2,)
    pos = positions.astype(jnp.float32)
    ang = pos[..., :, None] * inv                     # (..., S, dh/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if ang.ndim == 2:                                 # (S, dh/2) -> (1,1,S,dh/2)
        sin, cos = sin[None, None], cos[None, None]
    else:                                             # (B, S, dh/2) -> (B,1,S,dh/2)
        sin, cos = sin[:, None], cos[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated and plain), with Kratos-able projections
# ---------------------------------------------------------------------------

ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),     # nemotron squared-ReLU
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


def mlp_init(key, d: int, d_ff: int, *, gated: bool = True,
             spec: kr.KratosSpec = kr.DENSE, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 3)
    p = {}
    if gated:
        p["w_gate"] = kr.init(ks[0], d, d_ff, spec, dtype)
        p["w_up"] = kr.init(ks[1], d, d_ff, spec, dtype)
    else:
        p["w_up"] = kr.init(ks[1], d, d_ff, spec, dtype)
    p["w_down"] = kr.init(ks[2], d_ff, d, spec, dtype)
    return p


def mlp_apply(params: Dict, x: jnp.ndarray, *, activation: str = "silu",
              spec: kr.KratosSpec = kr.DENSE, backend: str = "ref",
              probe=None) -> jnp.ndarray:
    act = ACTIVATIONS[activation]
    up = kr.apply(params["w_up"], x, spec, backend=backend, name="w_up")
    if "w_gate" in params:
        gate = kr.apply(params["w_gate"], x, spec, backend=backend,
                        name="w_gate")
        with scope("mlp_act"):
            h = act(gate) * up
    else:
        with scope("mlp_act"):
            h = act(up)
    h = shard(h, "batch", "seq", "ffn")
    if probe is not None:
        # the activation-sparsity site: ReLU-family nonlinearities zero a
        # large fraction of h, and every zero row-element makes its w_down
        # k-slice ineffectual (serve.ledger)
        probe.tap(h, x.shape[-1])
    y = kr.apply(params["w_down"], h, spec, backend=backend, name="w_down")
    # pin the row-parallel product to batch-sharded rows: without this,
    # GSPMD may satisfy the weight's FSDP out-dim by all-gathering the
    # batch over 'data' (a 4.5 GiB/layer intermediate on nemotron-340b).
    return shard(y, "batch", None, "dm_in")


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int, dtype=jnp.float32) -> Dict:
    return {"emb": jax.random.normal(key, (vocab, d), dtype) * 0.02}


@scoped("embed")
def embed(params: Dict, tokens: jnp.ndarray, *, scale: float = 1.0) -> jnp.ndarray:
    out = jnp.take(params["emb"], tokens, axis=0)
    if scale != 1.0:
        out = out * jnp.asarray(scale, out.dtype)
    return shard(out, "batch", "seq", None)


def unembed(params: Dict, x: jnp.ndarray, head: Optional[Dict] = None,
            *, softcap: Optional[float] = None) -> jnp.ndarray:
    from repro.kernels import ref as kref   # accum-dtype switch (see ref.py)
    with scope("gemm", "head"):
        w = head["w"] if head is not None else params["emb"].T
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype),
                            preferred_element_type=kref._DOT_ACCUM)
        logits = logits.astype(jnp.float32)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
    return shard(logits, "batch", "seq", "vocab")
