"""Gradient compression with error feedback (the cross-pod bandwidth trick).

Two layers:

  * `ef_int8_compress` — numerics: per-tensor-block int8 quantization with
    an error-feedback accumulator (Karimireddy et al. style). Plugged into
    make_train_step(compress_fn=...); the EF state rides in the train state
    (and is checkpointed with it). Over DCN this cuts gradient bytes 4x
    vs f32 / 2x vs bf16 while EF keeps convergence (tested: a compressed
    run reaches the same loss band as an uncompressed one).

  * `cross_pod_psum_int8` — the wire pattern: a shard_map over the 'pod'
    axis that quantizes, psums the int32 codes, and dequantizes — i.e., the
    actual reduced-precision all-reduce a 1000-node deployment runs across
    its data-center interconnect. Exercised in tests on a fake multi-device
    mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ef_int8_compress(grads, state):
    """Error-feedback int8 compression of a gradient pytree.

    state: pytree of f32 residuals matching grads (or None on first step —
    use `ef_init(params)`).
    """
    if state is None:
        state = jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, e):
        gf = g.astype(jnp.float32) + e
        q, scale = _quant_int8(gf)
        deq = q.astype(jnp.float32) * scale
        return deq.astype(g.dtype), gf - deq

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_e = tdef.flatten_up_to(state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def ef_init(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def cross_pod_psum_int8(x: jnp.ndarray, mesh, axis: str = "pod") -> jnp.ndarray:
    """All-reduce `x` over `axis` in int8-on-the-wire (int32 accumulate).

    x is assumed replicated over `axis` pre-reduction is wrong — each pod
    holds its own partial sum; we quantize the partial, reduce the integer
    codes, and dequantize with the max scale.
    """
    n_axes = len(mesh.axis_names)
    spec = P(*([None] * x.ndim))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=spec, out_specs=spec, check_vma=False)
    def reduce_fn(xx):
        q, scale = _quant_int8(xx)
        # shared scale: use the max scale across pods so codes are comparable
        smax = jax.lax.pmax(scale, axis)
        q = jnp.clip(jnp.round(xx / smax), -127, 127).astype(jnp.int32)
        total = jax.lax.psum(q, axis)
        return total.astype(jnp.float32) * smax

    return reduce_fn(x)
