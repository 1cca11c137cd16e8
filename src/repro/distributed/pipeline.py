"""GPipe-style pipeline parallelism over the cross-pod ('pod') mesh axis.

At 1000+ nodes the pod-to-pod interconnect (DCN) is ~10x slower than
intra-pod ICI, so the cheapest thing to send across it is *activations of a
layer boundary*, not gradients of every parameter. This module implements the
schedule with `shard_map` + `jax.lax.ppermute`:

  * the layer stack is split into `n_stages` contiguous stages, stage s's
    parameters living only on pod s (cutting per-pod parameter + optimizer
    memory by n_stages);
  * a step runs `n_micro` microbatches; at tick t, stage s processes
    microbatch (t - s) and ppermutes its activation to stage s+1 — the
    classic pipeline diagonal with (n_stages - 1) bubble ticks;
  * backward runs the mirrored schedule (handled by jax.grad through the
    ppermutes — reverse-mode of a ppermute is the opposite ppermute).

This is exercised as an alternative to pod-as-extra-DP on a stacked-MLP tower
(tests/test_pipeline.py validates exact equivalence with the sequential
model); wiring it under the full transformer is a config flag surfaced in
EXPERIMENTS.md §Perf as a cross-pod optimization.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_params: Any,          # pytree, leaves stacked (n_stages, ...)
    x: jnp.ndarray,             # (n_micro, micro_batch, d) microbatched input
    stage_fn: Callable,         # stage_fn(params_slice, h) -> h
    mesh: Mesh,
    axis: str = "pod",
) -> jnp.ndarray:
    """Run x through n_stages pipeline stages laid out along `axis`.

    Returns (n_micro, micro_batch, d) outputs (as produced by the last stage,
    gathered back to all pods for convenience).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= n_stages, "need >= n_stages microbatches to fill the pipe"

    pspec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    in_specs = (pspec_params, P(None))          # params sharded, x replicated
    out_specs = P(None)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def run(params, xs):
        # params: leaves (1, ...) — this pod's stage; xs: (n_micro, mb, d)
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage_id = jax.lax.axis_index(axis)
        total_ticks = n_micro + n_stages - 1
        mb, d = xs.shape[1], xs.shape[2]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when valid); others use buf
            inject = jnp.where(t < n_micro, t, 0)
            h_in = jnp.where(stage_id == 0, xs[inject], buf)
            h_out = stage_fn(params, h_in)
            # last stage records its result at position (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            write = (stage_id == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            # shift activations one stage forward
            buf = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        buf0 = jnp.zeros((mb, d), xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(total_ticks))
        # broadcast the last stage's outputs to every pod
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run(stage_params, x)


def pipeline_reference(stage_params, x, stage_fn):
    """Sequential oracle: run all stages in order on each microbatch."""
    n_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

    def apply_all(h):
        for s in range(n_stages):
            ps = jax.tree_util.tree_map(lambda p: p[s], stage_params)
            h = stage_fn(ps, h)
        return h

    return jax.vmap(apply_all)(x)
