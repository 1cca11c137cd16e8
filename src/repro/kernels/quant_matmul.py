"""Mixed-precision weight-stationary GEMMs (w8/w4/w2/w1 x a16, and w8a8).

Kratos' precision axis: on the FPGA, a b-bit constant-coefficient multiplier
costs ~b^2 LUTs, so area drops super-linearly with bits. On the TPU the wins
are restated as:

  * weight HBM traffic ∝ bits (sub-byte codes are bit-packed into int8 lanes
    and unpacked in-register inside the kernel — the memory roofline term
    drops linearly with bits);
  * w8a8 runs the MXU in int8 mode at 2x the bf16 MAC rate (compute term);
  * dequantization is fused: per-output-channel scales are applied once per
    output tile at accumulator flush, never materializing a float weight in
    HBM.

Packing matches core.quantize: codes packed along the reduction axis,
little-endian fields within each byte, two's complement (sign bit for 1-bit).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_compat as _compat

from repro.core import quantize as qz


def _unpack_tile(wq: jnp.ndarray, bits: int) -> jnp.ndarray:
    """int8[(bk/vpb), bn] packed -> int8[bk, bn] codes (in-kernel).

    Fields are extracted in int32: Mosaic cannot legalize shifts on 8-bit
    vectors, so each byte is widened once (its low 8 bits are the raw
    field bits) and shifted/masked as a 32-bit lane value."""
    if bits == 8:
        return wq
    vpb = qz.VALUES_PER_BYTE[bits]
    mask = (1 << bits) - 1
    sign = 1 << (bits - 1)
    pu = wq.astype(jnp.int32) & 0xFF
    fields = []
    for i in range(vpb):
        f = (pu >> (i * bits)) & mask
        if bits == 1:
            f = f * 2 - 1
        else:
            f = (f ^ sign) - sign
        fields.append(f.astype(jnp.int8))
    tile = jnp.stack(fields, axis=1)                # (bk/vpb, vpb, bn)
    return tile.reshape(wq.shape[0] * vpb, wq.shape[1])


def _wq_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_kb: int, bits: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_tile(w_ref[...], bits)
    acc_ref[...] += jnp.dot(
        x_ref[...], codes.astype(x_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(t == n_kb - 1)
    def _flush():
        # per-output-channel dequant, fused at flush time
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def quant_matmul(
    x: jnp.ndarray,              # (m, n) float
    qt: qz.QuantizedTensor,      # packed (n/vpb, p) + scale (p,)
    *,
    bm: int = 128,
    bk: int = 128,               # in *unpacked* k elements
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = x.shape
    n_full, p = qt.shape
    assert n == n_full, (x.shape, qt.shape)
    vpb = qz.VALUES_PER_BYTE[qt.bits]
    if bk % vpb:
        raise ValueError(f"bk={bk} must be divisible by values-per-byte={vpb}")
    for name, dim, b in (("n", n, bk), ("p", p, bn)):
        if dim % b:
            raise ValueError(f"{name}={dim} not divisible by its block {b}")
    # skinny-m path (decode: m = n_slots); pad rows, slice the result back.
    bm = _compat.skinny_bm(m, bm, x.dtype)
    x, m_orig = _compat.pad_rows(x, bm, "quant_matmul")
    m = x.shape[0]
    grid = (m // bm, p // bn, n // bk)
    kernel = functools.partial(_wq_kernel, n_kb=n // bk, bits=qt.bits)
    scale2d = qt.scale.reshape(1, p)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk // vpb, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, p), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="quant_matmul",
        interpret=interpret,
    )(x, qt.data, scale2d)
    return out if m == m_orig else out[:m_orig]


def _w8a8_kernel(xq_ref, xs_ref, w_ref, s_ref, o_ref, acc_ref, *, n_kb: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(t == n_kb - 1)
    def _flush():
        deq = (acc_ref[...].astype(jnp.float32)
               * xs_ref[...].astype(jnp.float32)
               * s_ref[...].astype(jnp.float32))
        o_ref[...] = deq.astype(o_ref.dtype)


def quant_matmul_w8a8(
    x: jnp.ndarray,
    qt: qz.QuantizedTensor,
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Int8 x int8 GEMM at 2x MXU rate: activations quantized per-row on the
    fly (outside the kernel, fusable), int32 accumulation, joint dequant."""
    assert qt.bits == 8
    m, n = x.shape
    _, p = qt.shape
    for name, dim, b in (("n", n, bk), ("p", p, bn)):
        if dim % b:
            raise ValueError(f"{name}={dim} not divisible by its block {b}")
    xq, xs = qz.quantize_activations_int8(x)
    # skinny-m path: pad AFTER activation quantization (an all-zero pad row
    # would otherwise hit the per-row scale computation); int8 sublane is 32.
    bm = _compat.skinny_bm(m, bm, xq.dtype)
    xq, m_orig = _compat.pad_rows(xq, bm, "quant_matmul_w8a8")
    if xq.shape[0] != m:
        xs = jnp.pad(xs, ((0, xq.shape[0] - m), (0, 0)))
    m = xq.shape[0]
    grid = (m // bm, p // bn, n // bk)
    kernel = functools.partial(_w8a8_kernel, n_kb=n // bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bm, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((m, p), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="quant_matmul_w8a8",
        interpret=interpret,
    )(xq, xs, qt.data, qt.scale.reshape(1, p))
    return out if m == m_orig else out[:m_orig]


def _bsr_wq_kernel(idx_ref, x_ref, b_ref, s_ref, o_ref, acc_ref,
                   *, nnz: int, bits: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_tile(b_ref[0, 0], bits)
    acc_ref[...] += jnp.dot(
        x_ref[...], codes.astype(x_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(t == nnz - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def bsr_quant_matmul(
    x: jnp.ndarray,            # (m, n)
    qblocks: jnp.ndarray,      # int8[n_pb, nnz, bk/vpb, bn]
    scales: jnp.ndarray,       # f32[n_pb, bn]
    indices: jnp.ndarray,      # int32[n_pb, nnz]
    bits: int,
    *,
    bm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Kratos point-3 kernel: pruning x quantization compounded.

    Skips zero blocks via scalar-prefetch indices AND streams bit-packed
    weights: weight traffic ∝ (1 - sparsity) * bits / 16 vs dense bf16.
    """
    m, n = x.shape
    n_pb, nnz, bkp, bn = qblocks.shape
    vpb = qz.VALUES_PER_BYTE[bits]
    bk = bkp * vpb
    if n % bk:
        raise ValueError(f"n={n} not divisible by block k-extent {bk}")
    # skinny-m path (decode: m = n_slots); pad rows, slice the result back.
    bm = _compat.skinny_bm(m, bm, x.dtype)
    x, m_orig = _compat.pad_rows(x, bm, "bsr_quant_matmul")
    m = x.shape[0]
    grid = (m // bm, n_pb, nnz)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t, idx: (i, idx[j, t])),
            pl.BlockSpec((1, 1, bkp, bn), lambda i, j, t, idx: (j, t, 0, 0)),
            # scales as one (1, n_pb * bn) row: a (1, bn) block over
            # (n_pb, bn) would put a 1 on the sublane axis of a multi-row
            # array, which the TPU's (8, 128) tiling refuses
            pl.BlockSpec((1, bn), lambda i, j, t, idx: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, idx: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_bsr_wq_kernel, nnz=nnz, bits=bits)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_pb * bn), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="bsr_quant_matmul",
        interpret=interpret,
    )(jnp.asarray(indices, jnp.int32), x, qblocks,
      scales.reshape(1, n_pb * bn))
    return out if m == m_orig else out[:m_orig]
