"""Shared tiling helpers for the Pallas TPU kernels.

Hosts the skinny-m row-padding helpers shared by every GEMM kernel:
decode batches are m = n_slots (4-ish) rows while the kernels tile m in
MXU-sized blocks, so each kernel pads m up to a sublane-aligned block and
slices the result back (`pad_rows` / `skinny_bm`). Events are recorded in
`SKINNY_M_EVENTS` at trace time so benchmarks/tests can assert the decode
GEMMs really take this path (same idiom as serve_bench.PackedRouteCounter).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.instrument import REGISTRY

# ---------------------------------------------------------------------------
# Skinny-m support (decode GEMMs: m = n_slots << 128)
# ---------------------------------------------------------------------------

# TPU minimum second-to-minor tile extent by element width (pallas guide):
# f32 -> 8, bf16/f16 -> 16, int8/fp8 -> 32. The lane dim is always 128.
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}

# (kernel_name, m, bm) appended whenever a GEMM pads its row dim — at trace
# time, like kratos.apply_packed instrumentation. Registry-backed
# (repro.instrument.REGISTRY, stream "skinny_m"): wrap trace-and-assert
# blocks in `REGISTRY.scoped(...)` instead of hand-clearing; the historical
# name stays as an alias of the same list.
SKINNY_M_EVENTS = REGISTRY.event_list("skinny_m")

# (backend, n_slots, pages_per_slot) appended whenever the paged-attention
# decode path traces — same trace-time idiom as SKINNY_M_EVENTS. Benchmarks
# and tests assert page-table-native decode really dispatched (and that the
# gather/scatter wrap did NOT) by inspecting this alongside
# serve.paging.GATHER_EVENTS. Registry stream "paged_attn".
PAGED_ATTN_EVENTS = REGISTRY.event_list("paged_attn")


def sublane(dtype) -> int:
    """Minimum sublane multiple for `dtype` (second-to-minor tile extent)."""
    return _SUBLANE_BY_ITEMSIZE.get(jnp.dtype(dtype).itemsize, 8)


def skinny_bm(m: int, bm: int, dtype) -> int:
    """Adaptive row-block for any m >= 1.

    Policy, in order: (1) m divides bm's grid — keep the caller's bm (no
    padding, no event); (2) a sublane-aligned power-of-two block divides m
    exactly — use it (large non-divisible m keeps an exact grid, e.g. m=200
    runs bm=8 with zero pad rows); (3) otherwise pad: block = m rounded up
    to the dtype's sublane multiple, capped at bm but never below the
    sublane minimum — a 4-row f32 decode GEMM gets an 8-row block instead
    of failing the `m % 128` check (or silently building a 0-sized grid)."""
    if m % bm == 0:
        return bm
    sub = sublane(dtype)
    exact = 1
    while exact * 2 <= min(bm, m):
        exact *= 2                      # largest power of two <= min(bm, m)
    while exact > 1 and m % exact:
        exact //= 2
    if exact >= sub:
        return exact
    m_up = -(-m // sub) * sub
    return max(sub, min(bm, m_up))


def pad_rows(x: jnp.ndarray, bm: int, kernel: str) -> Tuple[jnp.ndarray, int]:
    """Zero-pad the row dim of `x` up to a multiple of `bm`.

    Returns (padded_x, original_m); callers slice the kernel output back to
    original_m rows. Records a SKINNY_M_EVENTS entry when padding happens.
    """
    m = x.shape[0]
    pad = (-m) % bm
    if pad == 0:
        return x, m
    SKINNY_M_EVENTS.append((kernel, m, bm))
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)), m
