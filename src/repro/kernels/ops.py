"""Jit'd public wrappers around the Pallas kernels with backend dispatch.

Backends:
  'ref'       pure-jnp oracle (XLA) — default on CPU; used by the 512-device
              dry-run (Pallas lowers to TPU-only custom calls).
  'pallas'    real Pallas lowering — the TPU target.
  'interpret' Pallas kernel body executed step-by-step on CPU — used by the
              kernel test suite to validate the TPU code path.
  'auto'      'pallas' on TPU, 'ref' elsewhere.

Skinny-m: every GEMM accepts any row count m >= 1. Decode batches are
m = n_slots (a handful of rows); the kernels adapt their row block to a
sublane-aligned size, zero-pad m up to it and slice the result back
(pallas_compat.skinny_bm / pad_rows). `SKINNY_M_EVENTS` (re-exported here)
records each padded dispatch at trace time so serving benchmarks can assert
the decode GEMMs really run the packed Pallas path at slab width.

Meshes: Mosaic kernels cannot be partitioned automatically, so under a
mesh of more than one device (`jax.set_mesh`, as ShardedBackend traces)
every kernel call is wrapped in a `shard_map` with replicated operands and
result (`_per_device`): each device runs the whole kernel on gathered
inputs. The packed weight buffers are replicated anyway
(sharding.param_pspecs), so this costs the gathers of the activations, and
it gives up tensor-parallel compute inside the packed GEMMs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quantize as qz
from repro.kernels import ref as _ref
from repro.kernels.bsr_matmul import bsr_matmul as _bsr_pallas
from repro.kernels.dense_matmul import dense_matmul as _dense_pallas
from repro.kernels.quant_matmul import (
    quant_matmul as _quant_pallas,
    quant_matmul_w8a8 as _w8a8_pallas,
    bsr_quant_matmul as _bsr_quant_pallas,
)
from repro.kernels.flash_attention import flash_attention as _fa_pallas
from repro.kernels.flash_attention import (
    paged_flash_attention as _paged_fa_pallas,
)
from repro.kernels.pallas_compat import (  # noqa: F401 (re-export)
    PAGED_ATTN_EVENTS,
    SKINNY_M_EVENTS,
)

VALID_BACKENDS = ("auto", "ref", "pallas", "interpret")


def resolve_backend(backend: str) -> str:
    if backend not in VALID_BACKENDS:
        raise ValueError(f"backend must be one of {VALID_BACKENDS}, got {backend}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def _per_device(kernel, *args):
    """Run a Pallas `kernel(*args)` whole on every device of the current
    mesh (see module docstring); a plain call off a multi-device mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel(*args)
    specs = jax.tree_util.tree_map(lambda _: P(), args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=specs, out_specs=P(),
                         check_vma=False)(*args)


def _fit_block(block: int, dim: int) -> int:
    """Largest power-of-two block <= `block` that divides `dim`."""
    c = min(block, dim)
    while c > 1 and dim % c:
        c //= 2
    return max(c, 1)


def matmul(x, w, *, backend: str = "auto", bm: int = 128, bk: int = 128,
           bn: int = 128):
    """Dense weight-stationary GEMM ('systolic' analogue)."""
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.dense_matmul_ref(x, w)
    m, n = x.shape
    p = w.shape[1]
    # bm is NOT fitted to m: the kernel's skinny-m path pads the row dim to a
    # sublane-aligned block (fitting bm to e.g. m=4 would force sub-sublane
    # tiles that the TPU cannot lay out).
    bk, bn = _fit_block(bk, n), _fit_block(bn, p)
    return _per_device(functools.partial(
        _dense_pallas, bm=bm, bk=bk, bn=bn, interpret=(b == "interpret")),
        x, w)


def bsr_matmul(x, blocks, indices, *, backend: str = "auto", bm: int = 128):
    """Block-sparse tree GEMM; FLOPs ∝ (1 - sparsity)."""
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.bsr_matmul_scan_ref(x, blocks, indices)
    return _per_device(functools.partial(
        _bsr_pallas, bm=bm, interpret=(b == "interpret")),
        x, blocks, indices)


def _fit_quant_blocks(qt, bk: int, bn: int):
    """Fit k/n blocks to the tensor (small smoke models have n < 128).

    The fitted bk is automatically a multiple of the sub-byte packing
    factor: pack_codes requires n % vpb == 0 and vpb is a power of two, so
    the largest power-of-two divisor of n is >= vpb."""
    n, p = qt.shape
    return _fit_block(bk, n), _fit_block(bn, p)


def quant_matmul(x, qt: qz.QuantizedTensor, *, backend: str = "auto",
                 bm: int = 128, bk: int = 128, bn: int = 128):
    """Weight-only quantized GEMM (w{8,4,2,1}a16)."""
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.quant_matmul_ref(x, qt)
    bk, bn = _fit_quant_blocks(qt, bk, bn)
    return _per_device(functools.partial(
        _quant_pallas, bm=bm, bk=bk, bn=bn, interpret=(b == "interpret")),
        x, qt)


def quant_matmul_w8a8(x, qt: qz.QuantizedTensor, *, backend: str = "auto",
                      bm: int = 128, bk: int = 128, bn: int = 128):
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.quant_matmul_w8a8_ref(x, qt)
    bk, bn = _fit_quant_blocks(qt, bk, bn)
    return _per_device(functools.partial(
        _w8a8_pallas, bm=bm, bk=bk, bn=bn, interpret=(b == "interpret")),
        x, qt)


def bsr_quant_matmul(x, qblocks, scales, indices, bits: int, *,
                     backend: str = "auto", bm: int = 128):
    """Sparse + quantized tree GEMM (pruning x quantization compounded)."""
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.bsr_quant_matmul_ref(x, qblocks, scales, indices, bits)
    return _per_device(functools.partial(
        _bsr_quant_pallas, bits=bits, bm=bm, interpret=(b == "interpret")),
        x, qblocks, scales, indices)


def ssm_scan(u, dt, b, c, a, *, backend: str = "auto", bd: int = 128,
             ck: int = 16):
    """Selective-scan (Mamba-1) recurrence. Returns (y, h_final)."""
    from repro.kernels import ssm_scan as _ssm
    bk = resolve_backend(backend)
    if bk == "ref":
        return _ssm.ssm_scan_ref(u, dt, b, c, a)
    return _per_device(functools.partial(
        _ssm.ssm_scan, bd=bd, ck=ck, interpret=(bk == "interpret")),
        u, dt, b, c, a)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    q_offset: int = 0, scale=None, backend: str = "auto",
                    bq: int = 128, bkv: int = 128):
    """q: (b, h, sq, d); k, v: (b, h_kv, skv, d). Returns (b, h, sq, d)."""
    b, h, sq, d = q.shape
    _, h_kv, skv, _ = k.shape
    bk = resolve_backend(backend)
    if bk == "ref":
        g = h // h_kv
        kk = jnp.repeat(k, g, axis=1) if g > 1 else k
        vv = jnp.repeat(v, g, axis=1) if g > 1 else v
        return _ref.attention_ref(q, kk, vv, causal=causal, window=window,
                                  softcap=softcap, q_offset=q_offset, scale=scale)
    out = _per_device(functools.partial(
        _fa_pallas, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, scale=scale, bq=bq, bkv=bkv,
        interpret=(bk == "interpret")),
        q.reshape(b * h, sq, d), k.reshape(b * h_kv, skv, d),
        v.reshape(b * h_kv, skv, d))
    return out.reshape(b, h, sq, d)


def paged_attention(q, k_pages, v_pages, table, last, *, window=None,
                    softcap=None, scale=None, backend: str = "auto"):
    """Page-table-native decode attention.

    q: (b, h, sq, d); k_pages/v_pages: (n_pages, h_kv, P, d) page-major
    store leaves; table: (b, pp) int32 page ids; last: (b,) int32 absolute
    position of each slot's final query token. Returns (b, h, sq, d).
    Causal by construction. Records a PAGED_ATTN_EVENTS entry at trace time
    so serving tests/benchmarks can assert the gather-free path dispatched.
    """
    bk = resolve_backend(backend)
    PAGED_ATTN_EVENTS.append((bk, q.shape[0], table.shape[1]))
    if bk == "ref":
        return _ref.paged_attention_ref(
            q, k_pages, v_pages, table, last,
            window=window, softcap=softcap, scale=scale)
    return _per_device(functools.partial(
        _paged_fa_pallas, window=window, softcap=softcap, scale=scale,
        interpret=(bk == "interpret")),
        q, k_pages, v_pages, table, last)
