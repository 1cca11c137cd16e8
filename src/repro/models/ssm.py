"""Mamba-1 selective state-space mixer (falcon-mamba-7b, jamba).

The recurrence  h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t ;  y_t = C_t h_t
+ D x_t  is evaluated with a *chunked associative scan*: the sequence is cut
into chunks of `chunk` tokens; within a chunk we use
`jax.lax.associative_scan` over the (decay, update) monoid, and chunk carries
propagate through an outer `lax.scan`. This bounds the materialized state
tensor to (B, chunk, d_inner, d_state) — without chunking, a 4k-token
training step of falcon-mamba would materialize ~17 GB of scan states per
device.

Decode is the O(1) single-step recurrence on a (B, d_inner, d_state) state +
a (B, d_conv-1, d_inner) conv tail — this is why the long_500k cell is
trivially feasible for SSM archs (DESIGN.md §shape-cell skips).

falcon-mamba adds RMSNorm on (B, C, dt) streams (`bcdt_rms=True`).

The in/out/x/dt projections are plain GEMMs and therefore Kratos-able; the
recurrence itself has no weight matrix to sparsify (DESIGN.md
§Arch-applicability).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kratos as kr
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_model: int
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0            # 0 -> ceil(d_model / 16)
    bcdt_rms: bool = False      # falcon-mamba
    chunk: int = 256

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)


def mamba_init(key, cfg: MambaConfig, spec: kr.KratosSpec = kr.DENSE,
               dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 6)
    d, di, st, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.rank
    p = {
        "in_proj": kr.init(ks[0], d, 2 * di, spec, dtype),
        "conv_w": jax.random.normal(ks[1], (cfg.d_conv, di), dtype) * 0.2,
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": kr.init(ks[2], di, r + 2 * st, spec, dtype),
        "dt_proj": {"w": jax.random.normal(ks[3], (r, di), dtype) * (r ** -0.5),
                    "b": jnp.log(jnp.expm1(jnp.full((di,), 0.01, dtype)))},
        "A_log": jnp.log(jnp.tile(jnp.arange(1, st + 1, dtype=jnp.float32)[None],
                                  (di, 1))),
        "D": jnp.ones((di,), dtype),
        "out_proj": kr.init(ks[4], di, d, spec, dtype),
    }
    if cfg.bcdt_rms:
        p["b_norm"] = L.rmsnorm_init(st, dtype)
        p["c_norm"] = L.rmsnorm_init(st, dtype)
        p["dt_norm"] = L.rmsnorm_init(r, dtype)
    return p


def _depthwise_conv(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                    tail: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal depthwise conv1d. u: (B, S, di); w: (K, di); tail: (B, K-1, di)."""
    k = w.shape[0]
    pad = tail if tail is not None else jnp.zeros(
        (u.shape[0], k - 1, u.shape[2]), u.dtype)
    up = jnp.concatenate([pad, u], axis=1)                  # (B, S+K-1, di)
    out = sum(up[:, i:i + u.shape[1]] * w[i] for i in range(k))
    return out + b


def _ssm_params(params, u, cfg: MambaConfig, spec, backend):
    """u: (B, S, di) -> dt (B,S,di), B_ (B,S,st), C_ (B,S,st)."""
    st, r = cfg.d_state, cfg.rank
    xdbc = kr.apply(params["x_proj"], u, spec, backend=backend,
                    name="x_proj")
    dt_in, b_, c_ = jnp.split(xdbc, [r, r + st], axis=-1)
    if cfg.bcdt_rms:
        dt_in = L.rmsnorm(params["dt_norm"], dt_in)
        b_ = L.rmsnorm(params["b_norm"], b_)
        c_ = L.rmsnorm(params["c_norm"], c_)
    dt = jax.nn.softplus(dt_in @ params["dt_proj"]["w"].astype(u.dtype)
                         + params["dt_proj"]["b"].astype(u.dtype))
    return dt, b_, c_


def _scan_chunked(dA, dBx, cfg: MambaConfig):
    """dA, dBx: (B, S, di, st) -> h: (B, S, di, st) via chunked assoc scan."""
    b, s, di, st = dA.shape
    ck = min(cfg.chunk, s)
    n_chunks = s // ck
    rem = s - n_chunks * ck

    def combine(a, b_):
        (a1, b1), (a2, b2) = a, b_
        return a1 * a2, b1 * a2 + b2

    def chunk_step(h0, xs):
        da, dbx = xs                                        # (B, ck, di, st)
        acc_a, acc_b = jax.lax.associative_scan(combine, (da, dbx), axis=1)
        h = acc_a * h0[:, None] + acc_b                     # prefix-applied
        return h[:, -1], h

    if n_chunks:
        da_c = dA[:, :n_chunks * ck].reshape(b, n_chunks, ck, di, st)
        dbx_c = dBx[:, :n_chunks * ck].reshape(b, n_chunks, ck, di, st)
        h_last, hs = jax.lax.scan(
            chunk_step, jnp.zeros((b, di, st), dA.dtype),
            (da_c.transpose(1, 0, 2, 3, 4), dbx_c.transpose(1, 0, 2, 3, 4)))
        h = hs.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * ck, di, st)
    else:
        h_last = jnp.zeros((b, di, st), dA.dtype)
        h = jnp.zeros((b, 0, di, st), dA.dtype)
    if rem:
        _, h_tail = chunk_step(h_last, (dA[:, -rem:], dBx[:, -rem:]))
        h = jnp.concatenate([h, h_tail], axis=1)
    return h


def mamba_apply(params, x, cfg: MambaConfig, *, spec=kr.DENSE, backend="ref",
                cache: Optional[Dict] = None, index=None,
                ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x: (B, S, d). cache: {'conv': (B,K-1,di), 'ssm': (B,di,st)} for decode."""
    b, s, d = x.shape
    di, st = cfg.d_inner, cfg.d_state
    ug = kr.apply(params["in_proj"], x, spec, backend=backend,
                    name="in_proj")
    u, gate = jnp.split(ug, 2, axis=-1)                     # (B,S,di) each

    decode = cache is not None and index is not None
    conv_tail = cache["conv"] if decode else None
    u_conv = _depthwise_conv(u, params["conv_w"].astype(u.dtype),
                             params["conv_b"].astype(u.dtype), conv_tail)
    u_act = jax.nn.silu(u_conv)
    u_act = L.shard(u_act, "batch", "seq", "ffn")

    dt, b_, c_ = _ssm_params(params, u_act, cfg, spec, backend)
    A = -jnp.exp(params["A_log"]).astype(jnp.float32)       # (di, st)
    dA = jnp.exp(dt.astype(jnp.float32)[..., None] * A)     # (B,S,di,st)
    dBx = (dt.astype(jnp.float32) * u_act.astype(jnp.float32))[..., None] \
        * b_.astype(jnp.float32)[:, :, None, :]             # (B,S,di,st)

    new_cache = None
    if decode:
        assert s == 1
        h = dA[:, 0] * cache["ssm"] + dBx[:, 0]             # (B,di,st)
        new_conv = jnp.concatenate([cache["conv"][:, 1:], u[:, :1]], axis=1) \
            if cfg.d_conv > 1 else cache["conv"]
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                     "ssm": h.astype(cache["ssm"].dtype)}
        y = jnp.einsum("bds,bs->bd", h, c_[:, 0].astype(jnp.float32))[:, None]
    else:
        kernel_ok = (backend in ("pallas", "interpret")
                     and di % 8 == 0 and s % 4 == 0)
        if kernel_ok:
            # fused Pallas path: the recurrence state stays in VMEM and the
            # (B,S,di,st) state tensor never touches HBM (EXPERIMENTS §H4)
            from repro.kernels import ops as kops
            bd = 128 if di % 128 == 0 else 8
            ck = 16 if s % 16 == 0 else 4
            y32, h_last = kops.ssm_scan(
                u_act.astype(jnp.float32), dt.astype(jnp.float32),
                b_.astype(jnp.float32), c_.astype(jnp.float32), A,
                backend=backend, bd=bd, ck=ck)
            y = y32
            if cache is not None:
                h = h_last[:, None]                         # (B,1,di,st)
        else:
            h = _scan_chunked(dA, dBx, cfg)                 # (B,S,di,st)
            y = jnp.einsum("bsdn,bsn->bsd", h, c_.astype(jnp.float32))
        if cache is not None:  # prefill: save final state + conv tail
            tail = jnp.concatenate(
                [jnp.zeros((b, max(0, cfg.d_conv - 1 - s), di), u.dtype),
                 u[:, -(cfg.d_conv - 1):]], axis=1) if cfg.d_conv > 1 else \
                jnp.zeros((b, 0, di), u.dtype)
            new_cache = {"conv": tail.astype(cache["conv"].dtype),
                         "ssm": h[:, -1].astype(cache["ssm"].dtype)}
    y = y.astype(x.dtype) + u_act * params["D"].astype(x.dtype)
    y = y * jax.nn.silu(gate)
    out = kr.apply(params["out_proj"], y, spec, backend=backend,
                    name="out_proj")
    out = L.shard(out, "batch", None, "dm_in")   # see layers.mlp_apply note
    return out, new_cache


def make_mamba_cache(cfg: MambaConfig, batch: int, dtype=jnp.float32) -> Dict:
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.d_state), dtype),
    }


def mamba_scan_ref(dA, dBx):
    """Naive sequential recurrence oracle for tests. (B,S,di,st) -> same."""
    def step(h, xs):
        da, dbx = xs
        h = da * h + dbx
        return h, h
    b, s, di, st = dA.shape
    _, hs = jax.lax.scan(step, jnp.zeros((b, di, st), dA.dtype),
                         (dA.transpose(1, 0, 2, 3), dBx.transpose(1, 0, 2, 3)))
    return hs.transpose(1, 0, 2, 3)
