"""Dense weight-stationary tiled GEMM — the TPU analogue of Kratos' `gemms`
(weight-stationary systolic array).

Like the FPGA systolic array, this datapath is *structurally dense*: zero
weights still occupy MXU cycles and HBM bandwidth, so its cost is independent
of sparsity. It exists (a) as the head-to-head baseline for the Fig. 5
reproduction (tree prunes, systolic doesn't) and (b) as the dense fast path
when sparsity == 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_compat as _compat


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_kb: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(t == n_kb - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def dense_matmul(
    x: jnp.ndarray,    # (m, n)
    w: jnp.ndarray,    # (n, p)
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = x.shape
    n2, p = w.shape
    assert n == n2, (x.shape, w.shape)
    # skinny-m path (decode: m = n_slots): adapt the row block to a
    # sublane-aligned size and zero-pad m up to it; the pad rows cost one
    # sublane of MXU work and are sliced off below.
    bm = _compat.skinny_bm(m, bm, x.dtype)
    x, m_orig = _compat.pad_rows(x, bm, "dense_matmul")
    m = x.shape[0]
    for name, dim, b in (("m", m, bm), ("n", n, bk), ("p", p, bn)):
        if dim % b:
            raise ValueError(f"{name}={dim} not divisible by its block {b}")
    grid = (m // bm, p // bn, n // bk)
    kernel = functools.partial(_mm_kernel, n_kb=n // bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, p), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="dense_matmul",
        interpret=interpret,
    )(x, w)
    return out if m == m_orig else out[:m_orig]
