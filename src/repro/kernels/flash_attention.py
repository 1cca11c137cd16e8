"""Tiled (flash) attention for TPU with causal / sliding-window / soft-cap
support and GQA-aware k/v streaming.

Not part of Kratos itself, but the perf-critical compute of the assigned LM
architectures — and the same Kratos philosophy applies at tile level: blocks
that are *structurally* dead (fully above the causal diagonal, or outside the
sliding window) are skipped entirely via `pl.when`, so compute scales with
the live fraction of the score matrix, exactly like tree-pruning dead MACs.

Layout: q (bh, sq, d); k, v (bh_kv, skv, d); GQA group g = bh // bh_kv is
resolved in the BlockSpec index map (no k/v broadcast is materialized).
Running max / denominator live in (bq, 128) VMEM scratch (lane-replicated),
the standard TPU idiom.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_compat as _compat

_LANES = 128
_NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bq: int, bkv: int, n_kv: int, scale: float,
               causal: bool, window: Optional[int],
               softcap: Optional[float], q_offset: int):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq + q_offset           # absolute position of this q tile
    kv_start = ikv * bkv

    # Structural block skipping (the "pruned tree" of attention):
    live = jnp.bool_(True)
    if causal:
        live &= kv_start <= q_start + bq - 1
    if window is not None:
        live &= kv_start + bkv - 1 > q_start - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.bool_(True)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ikv == n_kv - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,            # (bh, sq, d)
    k: jnp.ndarray,            # (bh_kv, skv, d)
    v: jnp.ndarray,            # (bh_kv, skv, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    bh_kv, skv, _ = k.shape
    assert bh % bh_kv == 0, (bh, bh_kv)
    g = bh // bh_kv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    if sq % bq or skv % bkv:
        raise ValueError(f"seq lengths ({sq},{skv}) not divisible by blocks ({bq},{bkv})")
    scale = (d ** -0.5) if scale is None else scale
    grid = (bh, sq // bq, skv // bkv)
    kernel = functools.partial(
        _fa_kernel, bq=bq, bkv=bkv, n_kv=skv // bkv, scale=scale,
        causal=causal, window=window, softcap=softcap, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Page-table-native decode attention (serve.paging)
# ---------------------------------------------------------------------------

def _paged_kernel(table_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, sq: int, s_pad: int, page: int,
                  n_pp: int, scale: float, window: Optional[int],
                  softcap: Optional[float]):
    """One (slot, head, kv-page) grid cell of paged decode attention.

    The page table and per-slot `last` clocks arrive as scalar-prefetch
    operands: the K/V BlockSpec index maps read `table_ref[b, ip]` to
    translate (slot, kv-block) -> page id, so K/V stream straight from the
    page-major store — no gathered slab view exists anywhere. Positions are
    derived from the grid (kv position = ip * page + column), and per-slot
    validity is the causal test against `last_ref[b]`: sink-page rows and
    write-headroom garbage all live at positions > last and mask out.
    """
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    last_b = last_ref[b]
    kv_start = ip * page
    # Runtime block skipping — the paged analogue of _fa_kernel's structural
    # `live`: pages wholly past the slot's clock (allocation headroom, sink
    # rows) or wholly behind its window never touch the MXU.
    live = kv_start <= last_b
    if window is not None:
        live &= kv_start + page - 1 > last_b - (sq - 1) - window

    @pl.when(live)
    def _compute():
        qv = q_ref[0, 0].astype(jnp.float32)            # (s_pad, d)
        kv = k_ref[0, 0].astype(jnp.float32)            # (page, d)
        s = jax.lax.dot_general(qv, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # query row i sits at absolute position last - (sq - 1) + i; padded
        # rows (i >= sq) see later positions and are sliced away by the
        # caller, so their extra visibility is harmless.
        qpos = (last_b - (sq - 1)
                + jax.lax.broadcasted_iota(jnp.int32, (s_pad, page), 0))
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (s_pad, page), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                                  preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ip == n_pp - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_flash_attention(
    q: jnp.ndarray,            # (b, h, sq, d) — decode block, sq small
    k_pages: jnp.ndarray,      # (n_pages, h_kv, P, d) page-major store leaf
    v_pages: jnp.ndarray,      # (n_pages, h_kv, P, d)
    table: jnp.ndarray,        # (b, pp) int32 page ids (sink page = 0)
    last: jnp.ndarray,         # (b,) int32 absolute position of q[:, -1]
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention reading K/V directly through the page table.

    Grid (b, h, pages_per_slot); `table`/`last` ride the scalar-prefetch
    path so the K/V index maps resolve page ids before each block's DMA.
    GQA resolves in the index map (h // g) exactly like flash_attention.
    Causal by construction (decode: queries are the stream tail).
    """
    b, h, sq, d = q.shape
    n_pages, h_kv, page, _ = k_pages.shape
    assert h % h_kv == 0, (h, h_kv)
    g = h // h_kv
    pp = table.shape[1]
    scale = (d ** -0.5) if scale is None else scale

    sub = _compat.sublane(q.dtype)
    s_pad = -(-sq // sub) * sub
    if s_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - sq), (0, 0)))

    kernel = functools.partial(
        _paged_kernel, sq=sq, s_pad=s_pad, page=page, n_pp=pp, scale=scale,
        window=window, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, pp),
        in_specs=[
            pl.BlockSpec((1, 1, s_pad, d),
                         lambda ib, ih, ip, tbl, lst: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda ib, ih, ip, tbl, lst: (tbl[ib, ip],
                                                       ih // g, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda ib, ih, ip, tbl, lst: (tbl[ib, ip],
                                                       ih // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s_pad, d),
                               lambda ib, ih, ip, tbl, lst: (ib, ih, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s_pad, _LANES), jnp.float32),
            pltpu.VMEM((s_pad, _LANES), jnp.float32),
            pltpu.VMEM((s_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(table.astype(jnp.int32), last.astype(jnp.int32), q, k_pages, v_pages)
    return out[:, :, :sq] if s_pad != sq else out
