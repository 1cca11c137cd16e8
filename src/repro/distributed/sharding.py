"""Logical-axis sharding rules: DP / FSDP / TP / EP / SP over the production
mesh.

Mesh axes:
  single pod : (data=16, model=16)
  multi pod  : (pod=2, data=16, model=16) — 'pod' extends data parallelism by
               default (DCN-friendly: only gradient reduction crosses pods);
               the pipeline driver (distributed/pipeline.py) can claim it as
               a pipeline axis instead.

Parameter sharding is FSDP x TP: every 2-D projection is sharded over
('data' on its reduction-ish axis, 'model' on its parallel axis) so optimizer
state is fully sharded (ZeRO-3-equivalent); XLA inserts the per-layer
all-gathers. Rules are name-based over the parameter tree (the tree is ours,
so names are a stable contract). Stacked scan blocks get a leading None.

Activation rules (resolved by models.layers.shard):
  batch  -> ('pod', 'data')   heads/kv/ffn/vocab/expert -> 'model'
  seq    -> None (SP for saved residuals is a per-config option)
"""

from __future__ import annotations

import contextlib
import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import layers as L

# ---------------------------------------------------------------------------
# Logical activation axes
# ---------------------------------------------------------------------------

def activation_rules(mesh: Mesh, overrides: Optional[Dict] = None) -> Dict[str, Any]:
    names = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in names) or None
    model = "model" if "model" in names else None
    rules = {
        "batch": batch,
        "seq": None,
        "seq_res": model,     # SP: residual-stream / remat-carry seq sharding
        "heads": model,
        "kv_heads": model,
        "ffn": model,
        "vocab": model,
        "expert": model,
        # 2D-TP serving mode (§Perf): d_model contraction dim over 'data' so
        # weights stay resident (no per-step FSDP re-gather); off by default.
        "dm_in": None,
        # Kratos packed-block output axis (core.kratos.apply tree path)
        "out_blocks": model,
    }
    if overrides:
        rules.update(overrides)
    return rules


def _resolver_for(mesh: Mesh, overrides: Optional[Dict] = None):
    rules = activation_rules(mesh, overrides)

    def resolve(x, logical_axes):
        spec = []
        used = set()                      # a mesh axis may appear only once
        for ax, dim in zip(logical_axes, x.shape):
            mesh_ax = rules.get(ax) if ax is not None else None
            if mesh_ax is None:
                spec.append(None)
                continue
            axes = mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,)
            shards = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % shards or any(a in used for a in axes):
                spec.append(None)
            else:
                used.update(axes)
                spec.append(mesh_ax)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    return resolve


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rule_overrides: Optional[Dict] = None):
    """Install the mesh + logical resolver for model-internal constraints."""
    prev = L._LOGICAL_RESOLVER
    L.set_logical_resolver(_resolver_for(mesh, rule_overrides))
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        L.set_logical_resolver(prev)


# ---------------------------------------------------------------------------
# Parameter partition specs (name-based)
# ---------------------------------------------------------------------------

# parent-key names of column-parallel projections: out axis -> 'model'
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "wq_a", "wq_b", "wkv_a", "wkv_b",
        "in_proj", "head"}
# row-parallel: in axis -> 'model'
_ROW = {"wo", "w_down", "out_proj", "x_proj"}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(f"[{k.idx}]")
        else:
            names.append(str(k))
    return tuple(names)


def _spec_for(names: Tuple[str, ...], ndim: int, stacked: bool,
              fsdp_axis: Optional[str] = "data") -> P:
    base_ndim = ndim - (1 if stacked else 0)
    lead = (None,) if stacked else ()
    fa = fsdp_axis

    def mk(*axes):
        return P(*(lead + axes))

    nm = set(names)
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""

    # --- MoE stacked expert weights (E, d, f) / (E, f, d) ---
    if base_ndim == 3 and parent not in ("dt_proj",) and \
            leaf in ("w_gate", "w_up", "w_down"):
        if leaf == "w_down":
            return mk("model", None, fa)
        return mk("model", fa, None)
    if leaf == "emb":
        return mk("model", fa)
    if parent == "router" and leaf == "w":
        return mk(fa, "model")
    if parent == "dt_proj":
        return mk(None, "model") if base_ndim == 2 else mk("model")
    if leaf == "conv_w":
        return mk(None, "model")
    if leaf in ("conv_b", "D"):
        return mk("model")
    if leaf == "A_log":
        return mk("model", None)
    if leaf == "w" and parent in _COL:
        return mk(fa, "model")
    if leaf == "w" and parent in _ROW:
        return mk("model", fa)
    if leaf in ("scale", "bias"):                    # norms: shard last dim
        return mk(*([None] * (base_ndim - 1) + ["model"]))
    if leaf == "w" and base_ndim == 2:               # default 2-D projection
        return mk(fa, "model")
    return mk(*([None] * base_ndim))


def _sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the dim —
    uneven shardings are disallowed for jit arguments (vocab 73448 on a
    16-way axis, kv=20 heads on model=16, ...)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        shards = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(entry if shape[i] % shards == 0 else None)
    return P(*out)


def param_pspecs(params, mesh: Optional[Mesh] = None) -> Any:
    """PartitionSpec pytree matching a model parameter tree."""
    def one(path, leaf):
        names = _path_names(path)
        stacked = any(n in ("blocks", "enc_blocks") for n in names)
        spec = _spec_for(names, np.ndim(leaf), stacked)
        if mesh is not None and hasattr(leaf, "shape"):
            spec = _sanitize_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(mesh: Mesh, params) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_pspecs(params, mesh))


# ---------------------------------------------------------------------------
# Cache partition specs
# ---------------------------------------------------------------------------

def cache_pspecs(caches, mesh: Mesh, batch_size: int, *,
                 slab: bool = False) -> Any:
    """Shard KV caches: batch over ('pod','data') when divisible, else the
    cache *sequence* axis over 'data' (the long_500k single-request cell).
    The 'model' axis lands on kv-heads when divisible, otherwise on the
    cache sequence axis (e.g. kv=8 heads on a model=16 mesh — padding-free
    vs a 2x-waste uneven head sharding). d_inner (SSM) over 'model'.

    slab=True: the tree is a serving KV slab (serve.cache_pool) whose
    leading axis is `n_slots`, not a lock-step batch. Two rules change:
      * non-divisible slot counts REPLICATE instead of falling back to the
        long-context seq-over-'data' layout — every slot row is scattered at
        its own dynamic offset each micro-step, so a seq-sharded slab turns
        each per-slot write into a cross-device exchange;
      * leaves the name rules don't recognize still shard their leading
        slot axis like batch (previously they fell through to fully
        replicated as an "unknown dim").
    """
    names = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    model_n = mesh.shape["model"] if "model" in names else 1
    if dp_axes and batch_size % dp == 0:
        b_ax, seq_ax = dp_axes, None
    elif "data" in names and batch_size % mesh.shape["data"] == 0:
        b_ax, seq_ax = "data", None
    elif slab:
        b_ax, seq_ax = None, None
    else:
        b_ax, seq_ax = None, "data"

    def one(path, leaf):
        spec = _raw(path, leaf)
        if hasattr(leaf, "shape"):      # drop non-divisible entries per leaf
            spec = _sanitize_spec(spec, leaf.shape, mesh)
        return spec

    def _raw(path, leaf):
        names_ = _path_names(path)
        stacked = any(n == "blocks" for n in names_)
        lead = (None,) if stacked else ()
        leafname = names_[-1]
        nd = np.ndim(leaf) - len(lead)
        shape = leaf.shape[len(lead):] if hasattr(leaf, "shape") else ()
        if leafname in ("k", "v"):          # (B, KV, S, dh)
            kv_n, s_n = shape[1], shape[2]
            if kv_n % model_n == 0:
                return P(*(lead + (b_ax, "model", seq_ax, None)))
            # kv heads don't divide 'model'. Sharding the cache SEQ over
            # 'model' forces a per-layer cache all-gather at decode (1.5 GiB
            # x 96 layers on nemotron = the entire collective term), so:
            #   small cache -> batch-only (fully local attention);
            #   oversized cache (nemotron 2.5 TB) -> batch over 'model' (+
            #   'pod') and seq over 'data': heads stay whole, attention runs
            #   partial-softmax over 'data' with KB-scale reductions instead
            #   of GiB-scale gathers.
            if b_ax is not None:
                leaf_bytes = float(np.prod(leaf.shape)) * leaf.dtype.itemsize
                dp = int(np.prod([mesh.shape[a] for a in
                                  (b_ax if isinstance(b_ax, tuple)
                                   else (b_ax,))]))
                if leaf_bytes / dp <= (4 << 30):
                    return P(*(lead + (b_ax, None, seq_ax, None)))
                m_batch = tuple(a for a in ("pod", "model") if a in names)
                mb_n = int(np.prod([mesh.shape[a] for a in m_batch]))
                if not slab and shape[0] % mb_n == 0 \
                        and s_n % mesh.shape["data"] == 0:
                    return P(*(lead + (m_batch, None, "data", None)))
                return P(*(lead + (b_ax, None, seq_ax, None)))
            if slab:                        # never seq-shard a slot slab
                return P(*(lead + (b_ax, None, None, None)))
            m_seq = "model" if seq_ax is None else (seq_ax, "model")
            if s_n % (model_n * (1 if seq_ax is None else mesh.shape["data"])) == 0:
                return P(*(lead + (b_ax, None, m_seq, None)))
            return P(*(lead + (b_ax, None, seq_ax, None)))
        # MLA latent caches: keep seq over 'model' — the per-layer latent
        # gather is tiny (~19 MB: no head axis), while batch-only sharding
        # makes the per-head expansion run unsharded (24 GiB on minicpm3;
        # measured regression, reverted — §Perf H1 post-mortem). Serving
        # slabs (per-slot dynamic scatters) keep the seq axis whole.
        if leafname == "c_kv":              # (B, S, r) — latent, no head axis
            m_seq = None if slab else \
                ("model" if seq_ax is None else (seq_ax, "model"))
            return P(*(lead + (b_ax, m_seq, None)))
        if leafname == "k_rope":            # (B, 1, S, dr)
            m_seq = None if slab else \
                ("model" if seq_ax is None else (seq_ax, "model"))
            return P(*(lead + (b_ax, None, m_seq, None)))
        if leafname == "ssm":               # (B, di, st)
            return P(*(lead + (b_ax, "model", None)))
        if leafname == "conv":              # (B, K-1, di)
            return P(*(lead + (b_ax, None, "model")))
        if slab and nd >= 1 and shape and shape[0] == batch_size:
            # unknown slab leaf: the leading slot axis still shards like
            # batch; everything after it stays replicated.
            return P(*(lead + (b_ax,) + (None,) * (nd - 1)))
        return P(*(lead + (None,) * nd))

    return jax.tree_util.tree_map_with_path(one, caches)


def page_pspecs(caches, layout, mesh: Mesh, n_pages: int) -> list:
    """PartitionSpecs for a PAGE-MAJOR KV store (serve.paging).

    `caches` is the slab template (`T.make_caches(cfg, n_slots, cache_len)`
    shapes), `layout` a `serve.paging.PageLayout` over it. Paged leaves
    shard their PAGE axis — which sits exactly where the slab's slot axis
    sat (PageLayout.store_shapes) — the way the slab shards its slot axis
    (`batch_pspec(mesh, n_pages)` — replicated fallback when the page
    count doesn't divide the dp axes, so the donated paged decode step
    always has a legal placement); the rest of a paged leaf's spec is the
    slab rule (`cache_pspecs(slab=True)`) with the sequence entry cleared
    — kv-heads stay on 'model', the page-interior position axis is never
    sharded (every page is written at dynamic offsets, and the Pallas
    kernel's index map addresses whole pages). Resident leaves keep their
    slab spec unchanged. Returns a flat list aligned with the store's leaf
    order.
    """
    slab_specs = jax.tree_util.tree_leaves(
        cache_pspecs(caches, mesh, layout.n_slots, slab=True),
        is_leaf=lambda x: isinstance(x, P))
    page_entry = batch_pspec(mesh, n_pages)
    page_ent = tuple(page_entry)[0] if len(tuple(page_entry)) else None
    out = []
    store_shapes = layout.store_shapes(n_pages)
    for spec, slab_shape, store_shape, ls in zip(
            slab_specs, layout.slab_shapes, store_shapes, layout.specs):
        if not ls.paged:
            out.append(spec)
            continue
        ent = list(spec) + [None] * (len(slab_shape) - len(spec))
        ent[ls.batch_axis] = page_ent      # page axis replaces slot axis
        ent[-2] = None                     # page interior: never sharded
        out.append(_sanitize_spec(P(*ent), store_shape, mesh))
    return out


def batch_pspec(mesh: Mesh, batch_size: int) -> P:
    """Batch-axis spec: the combined ('pod','data') tuple when the batch
    divides the FULL mesh (so downstream reshapes can re-split it over any
    axis subset), a plain 'data' entry when it only divides the data axis,
    replicated otherwise. Multi-dp-axis meshes keep the tuple whenever the
    dp product divides — 'pod' x 'data' must shard together or not at all.

    The serving slab's per-slot vectors (steps.decode_state_pspecs) use
    this with batch_size = n_slots: the slot axis of the (K, B) token block
    and every lifecycle vector shards exactly like the slab's leading slot
    axis, and the replicated fallback keeps non-divisible slot counts legal
    as donated jit arguments (never an uneven sharding error)."""
    names = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    total = int(np.prod([mesh.shape[a] for a in names]))
    if dp_axes and batch_size % dp == 0:
        if batch_size % total == 0 or len(dp_axes) > 1 \
                or "data" not in names:
            return P(dp_axes)
        return P("data")
    if "data" in names and batch_size % mesh.shape["data"] == 0:
        return P("data")
    return P(None)
