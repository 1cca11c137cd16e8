"""Selective-scan (Mamba-1) Pallas kernel — the fix for the worst roofline
cell in the 40-cell table (falcon-mamba train/prefill, memory-dominated).

The XLA path materializes the state tensor h = (B, S, d_inner, d_state) in
HBM (assoc-scan levels make it ~10x worse): ~460 s memory term on the
production mesh. This kernel keeps the recurrence state in VMEM and streams
only the O(B*S*d_inner) inputs/outputs through HBM — the state never touches
HBM at all:

    HBM traffic = u, dt (B,S,di) in; B_, C_ (B,S,st) in; y (B,S,di) out
                ≈ 3-4 * B*S*di * bytes  (vs ~ B*S*di*st * levels for XLA)
                => st * ~10 = ~160x less state traffic.

Layout: the (di, st) state lives transposed as (st, bd) VMEM scratch so the
d_inner tile (bd=128) rides the 128-lane axis and d_state=16 the sublanes —
every per-step op is a full-width VPU op. The sequence axis is the innermost
('arbitrary') grid dim: chunks of ck positions stream through VMEM while the
scratch carries the state across chunks; inside a chunk the recurrence is
unrolled (ck small, default 16).

Numerics match models.ssm exactly: h_t = exp(dt_t*A)*h_{t-1} + dt_t*B_t*u_t,
y_t = C_t . h_t (the D*u and gating terms stay outside, they're elementwise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_ref, state_ref,
                *, ck: int, n_ck: int, return_final: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a_t = a_ref[...].T.astype(jnp.float32)             # (st, bd)
    ys = []
    for t in range(ck):
        dt_t = dt_ref[0, t].astype(jnp.float32)        # (bd,)
        u_t = u_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)          # (st,)
        c_t = c_ref[0, t].astype(jnp.float32)
        da = jnp.exp(dt_t[None, :] * a_t)              # (st, bd)
        dbx = (dt_t * u_t)[None, :] * b_t[:, None]
        state_ref[...] = da * state_ref[...] + dbx
        ys.append(jnp.sum(state_ref[...] * c_t[:, None], axis=0))  # (bd,)
    y_ref[0, ...] = jnp.stack(ys).astype(y_ref.dtype)

    if return_final:
        @pl.when(k == n_ck - 1)
        def _flush():
            h_ref[0, ...] = state_ref[...].T.astype(h_ref.dtype)


def ssm_scan(
    u: jnp.ndarray,        # (B, S, di) pre-activation inputs
    dt: jnp.ndarray,       # (B, S, di) softplus'd step sizes
    b: jnp.ndarray,        # (B, S, st) input gate
    c: jnp.ndarray,        # (B, S, st) output gate
    a: jnp.ndarray,        # (di, st)   negative state matrix (-exp(A_log))
    *,
    bd: int = 128,
    ck: int = 16,
    interpret: bool = False,
):
    """Returns (y (B, S, di), h_final (B, di, st))."""
    bsz, s, di = u.shape
    st = a.shape[1]
    if di % bd:
        raise ValueError(f"d_inner={di} not divisible by bd={bd}")
    if s % ck:
        raise ValueError(f"seq={s} not divisible by ck={ck}")
    n_ck = s // ck
    grid = (bsz, di // bd, n_ck)
    kernel = functools.partial(_ssm_kernel, ck=ck, n_ck=n_ck,
                               return_final=True)
    y, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, ck, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, ck, st), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, ck, st), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((bd, st), lambda i, j, k: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, bd, st), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), u.dtype),
            jax.ShapeDtypeStruct((bsz, di, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((st, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan",
        interpret=interpret,
    )(u, dt, b, c, a)
    return y, h


def ssm_scan_ref(u, dt, b, c, a):
    """Pure-jnp oracle (same math as models.ssm sequential reference)."""
    dA = jnp.exp(dt.astype(jnp.float32)[..., None] * a.astype(jnp.float32))
    dBx = (dt.astype(jnp.float32) * u.astype(jnp.float32))[..., None] \
        * b.astype(jnp.float32)[:, :, None, :]

    def step(h, xs):
        da, dbx = xs
        h = da * h + dbx
        return h, h

    bsz, s, di, st = dA.shape
    h0 = jnp.zeros((bsz, di, st), jnp.float32)
    h_last, hs = jax.lax.scan(step, h0, (dA.transpose(1, 0, 2, 3),
                                         dBx.transpose(1, 0, 2, 3)))
    hs = hs.transpose(1, 0, 2, 3)                       # (B, S, di, st)
    y = jnp.einsum("bsdn,bsn->bsd", hs, c.astype(jnp.float32))
    return y.astype(u.dtype), h_last
