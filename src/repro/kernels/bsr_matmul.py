"""Block-sparse weight-stationary GEMM — the TPU kernel for Kratos' `gemmt`
multiply-adder tree.

The FPGA tree prunes zero-weight leaves at synthesis; here, the *grid itself*
is pruned: the kernel iterates only over the `nnz` nonzero k-blocks of each
output-column block. Zero blocks are never fetched from HBM and never touch
the MXU, so compute and weight traffic scale with (1 - sparsity) — the
paper's Fig. 5 linearity, in time instead of area.

The per-output-block k-index table rides in as a scalar-prefetch operand
(SMEM), so the x BlockSpec's index_map can look up which k-tile to stream —
the Pallas/TPU idiom for data-dependent-but-statically-shaped access.

Grid: (m/bm, n_pb, nnz), k innermost ('arbitrary') so the f32 VMEM scratch
accumulates across the pruned k-loop and is flushed once per output tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_compat as _compat


def _bsr_kernel(idx_ref, x_ref, b_ref, o_ref, acc_ref, *, nnz: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], b_ref[0, 0],
        preferred_element_type=jnp.float32)

    @pl.when(t == nnz - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bsr_matmul(
    x: jnp.ndarray,            # (m, n)
    blocks: jnp.ndarray,       # (n_pb, nnz, bk, bn)
    indices: jnp.ndarray,      # int32[n_pb, nnz]
    *,
    bm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = x.shape
    n_pb, nnz, bk, bn = blocks.shape
    # skinny-m path: decode runs at m = n_slots; pad to a sublane-aligned
    # row block instead of rejecting, and slice the pad rows off at the end.
    bm = _compat.skinny_bm(m, bm, x.dtype)
    x, m_orig = _compat.pad_rows(x, bm, "bsr_matmul")
    m = x.shape[0]
    if m % bm:
        raise ValueError(f"m={m} not divisible by bm={bm}")
    if n % bk:
        raise ValueError(f"n={n} not divisible by bk={bk}")

    grid = (m // bm, n_pb, nnz)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # x tile: the k-index is read from the prefetched table.
            pl.BlockSpec((bm, bk), lambda i, j, t, idx: (i, idx[j, t])),
            # one packed weight block (j, t).
            pl.BlockSpec((1, 1, bk, bn), lambda i, j, t, idx: (j, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, idx: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_bsr_kernel, nnz=nnz)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_pb * bn), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="bsr_matmul",
        interpret=interpret,
    )(jnp.asarray(indices, jnp.int32), x, blocks)
    return out if m == m_orig else out[:m_orig]
