"""Packed-model registry: load once, pack once, serve many.

`pack_model_params` walks a training parameter tree and replaces every
Kratos-able projection leaf (`{"w": ...}` dicts created by `kratos.init`)
with a `kratos.PackedLinear` — the packed serving buffers (gathered sparse
blocks, bit-packed int codes, per-channel scales). Because `kratos.apply`
dispatches `PackedLinear` leaves to `apply_packed`, the packed tree is a
drop-in for the dense one: the same `steps.make_decode_step` serves both,
but the packed tree's hot path reads (1 - sparsity) * bits/16 of the weight
bytes.

The registry keys models by `(arch, KratosSpec)` — the same trained
architecture served dense, sparse, and quantized are three distinct serving
artifacts, exactly like the paper's one-bitstream-per-(sparsity, precision)
benchmark grid.

Not packed (by design):
  * `router` / `head` / `embed` — consumed by raw einsums, not kr.apply;
  * MoE routed-expert stacks (raw (E, d, f) arrays) — dispatched per-expert
    at apply time; with a tree spec they still run the gathered-block path,
    just from dense-format storage;
  * `dt_proj` and other non-GEMM leaves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as C
from repro.core import kratos as kr
from repro.models import transformer as T

# parent-key names of projections that route through kr.apply (attention,
# MLP, MLA low-rank factors, Mamba in/x/out) — the packable surface.
PACKABLE = frozenset({
    "wq", "wk", "wv", "wo", "wq_a", "wq_b", "wkv_a", "wkv_b",
    "w_gate", "w_up", "w_down", "in_proj", "x_proj", "out_proj",
})


def _is_packable(node, name: str) -> bool:
    """The single predicate both the packer and the stats walk share."""
    return (isinstance(node, dict) and set(node) == {"w"}
            and name in PACKABLE and hasattr(node["w"], "ndim")
            and node["w"].ndim in (2, 3))


def pack_model_params(params: Dict[str, Any], spec: kr.KratosSpec,
                      ) -> Tuple[Dict[str, Any], int]:
    """Replace packable `{"w"}` leaves with PackedLinear; returns (tree, n)."""
    count = [0]

    def walk(node, name: str):
        if _is_packable(node, name):
            count[0] += 1
            return kr.pack_linear(node, spec)
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, name) for v in node]
        return node

    packed = walk(params, "")
    return packed, count[0]


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


@dataclasses.dataclass
class PackedModel:
    """A named serving artifact: config + packed parameter tree + stats.

    With `draft_spec` (serve.speculative.DraftSpec) the artifact ALSO
    carries a self-draft: the same dense weights re-packed at the draft's
    (sparsity, bits) point — optionally layer-truncated — used by the
    speculative decode path. The draft is part of the artifact identity
    (registry key + name), never a mutation of a cached target.

    With `tier_specs` (serve.qos) the artifact carries a QoS degradation
    LADDER: the same dense weights re-packed at 1-2 cheaper (sparsity,
    bits) points, full depth, same cache layout (qos.check_tier_spec), so
    an overloaded engine can swap the live decode step onto tier i without
    touching resident KV state. tier 0 is `params` itself; `tier_params[i]`
    backs engine tier i+1."""

    name: str
    cfg: T.ModelConfig
    params: Dict[str, Any]          # tree with PackedLinear leaves
    spec: kr.KratosSpec
    n_packed: int                   # projections converted to PackedLinear
    packed_bytes: int               # serving bytes of the packed projections
    dense_bytes: int                # training bytes of the same projections
    draft_spec: Any = None          # speculative.DraftSpec or None
    draft_cfg: Optional[T.ModelConfig] = None
    draft_params: Optional[Dict[str, Any]] = None
    draft_packed: int = 0           # projections packed in the draft tree
    tier_specs: Tuple = ()          # QoS ladder (DraftSpec per cheap tier)
    tier_params: Tuple = ()         # matching packed trees (same cache tree)

    @property
    def compression(self) -> float:
        return self.dense_bytes / max(1, self.packed_bytes)

    @property
    def has_draft(self) -> bool:
        return self.draft_params is not None

    @property
    def n_tiers(self) -> int:
        """Resident quality tiers: the full-quality tree plus the ladder."""
        return 1 + len(self.tier_params)

    def tier_tree(self, tier: int) -> Dict[str, Any]:
        """Packed parameter tree backing engine tier `tier` (0 = full)."""
        return self.params if tier == 0 else self.tier_params[tier - 1]

    def draft_cost_fraction(self) -> float:
        """Analytic draft/target FLOPs-per-token ratio (speculative)."""
        from repro.serve import speculative as SP
        if not self.has_draft:
            return 1.0
        return SP.draft_cost_fraction(self.cfg, self.draft_cfg)

    def check_backend(self, backend: str) -> None:
        """Raise before any compile if a packed projection of this artifact
        (target, draft or QoS tier) cannot run on `backend` — today only
        the Pallas tiling of sparsity blocks (kratos.check_pallas_blocks)."""
        if backend != "pallas":
            return
        for tree in (self.params, self.draft_params, *self.tier_params):
            for pl in _iter_packed(tree):
                kr.check_pallas_blocks(pl)

    def pspecs(self, mesh) -> Any:
        """Parameter PartitionSpec tree for serving this artifact on `mesh`
        (sharding.param_pspecs): name-rule FSDP x TP where shapes divide;
        PackedLinear buffers fall through the name rules and REPLICATE —
        the packed-kernel contract (gathered sparse blocks, bit-packed
        codes) never crosses a shard boundary. Used by ShardedBackend and
        the `launch.serve --dry-run` sharding printer."""
        from repro.distributed import sharding as SH
        return SH.param_pspecs(self.params, mesh)


class ModelRegistry:
    """Named store of packed models, keyed by (arch, KratosSpec).

    The cache key also carries (smoke, seed): a reduced smoke artifact and
    the production-config artifact of the same (arch, spec) — or two seeds
    of fresh weights — are distinct serving models."""

    def __init__(self) -> None:
        self._models: Dict[Tuple, PackedModel] = {}
        self._by_name: Dict[str, PackedModel] = {}

    def load(self, arch: str, spec: Optional[kr.KratosSpec] = None, *,
             params: Optional[Dict[str, Any]] = None, seed: int = 0,
             name: Optional[str] = None, smoke: bool = True,
             draft_spec=None, tier_specs=None) -> PackedModel:
        """Load (or return the cached) packed model for (arch, spec).

        params: trained parameter tree; freshly initialized when omitted
        (benchmarks/tests). smoke=True uses the reduced CPU config.
        draft_spec (speculative.DraftSpec): ALSO derive a self-draft
        artifact from the same dense weights — required by
        `EngineConfig.speculate`. The draft spec is part of the cache key
        AND the default name (`_spec_tag`), so a drafted and an undrafted
        artifact of the same (arch, spec) never collide in `get`.
        tier_specs (tuple of DraftSpec, cheapest LAST): also keep a QoS
        degradation ladder resident — the same dense weights packed at
        each cheaper (sparsity, bits) point, validated KV-compatible by
        `qos.check_tier_spec`. Required by `EngineConfig.qos`.
        """
        getter = C.get_smoke if smoke else C.get_config
        cfg = getter(arch)
        spec = cfg.kratos if spec is None else spec
        cfg = dataclasses.replace(cfg, kratos=spec)
        tier_specs = tuple(tier_specs or ())
        key = (arch, spec, smoke, seed, draft_spec, tier_specs)
        if key in self._models and params is None:
            return self._models[key]
        if params is None:
            params = T.init(jax.random.PRNGKey(seed), cfg)

        dense_leaves = [
            p["w"] for p in _iter_packable(params)]
        dense_bytes = sum(int(np.prod(w.shape)) * w.dtype.itemsize
                          for w in dense_leaves)
        draft = {}
        if draft_spec is not None:
            from repro.serve import speculative as SP
            dcfg, dparams, dn = SP.derive_draft(params, cfg, spec, draft_spec)
            draft = dict(draft_spec=draft_spec, draft_cfg=dcfg,
                         draft_params=dparams, draft_packed=dn)
        tier_params = ()
        if tier_specs:
            from repro.serve import qos as Q
            # pack the ladder off the DENSE tree, before the target pack
            # consumes `params` by reference (pack_model_params is pure, but
            # each tier must see the dense leaves, not PackedLinear ones)
            tier_params = tuple(
                pack_model_params(params, Q.check_tier_spec(ts)
                                  .kratos_spec(spec))[0]
                for ts in tier_specs)
        packed, n_packed = pack_model_params(params, spec)
        if n_packed == 0:
            raise ValueError(f"{arch}: no packable projections found — "
                             "packed serving would be a no-op")
        packed_bytes = sum(pl.packed_bytes for pl in _iter_packed(packed))
        default_name = (f"{arch}@{_spec_tag(spec, draft_spec, tier_specs)}"
                        + ("" if smoke else "-full")
                        + (f"#s{seed}" if seed else ""))
        model = PackedModel(
            name=name or default_name, cfg=cfg, params=packed,
            spec=spec, n_packed=n_packed, packed_bytes=packed_bytes,
            dense_bytes=dense_bytes, tier_specs=tier_specs,
            tier_params=tier_params, **draft)
        self._models[key] = model
        self._by_name[model.name] = model
        return model

    def get(self, name: str) -> PackedModel:
        if name not in self._by_name:
            raise KeyError(f"no model '{name}'; loaded: {sorted(self._by_name)}")
        return self._by_name[name]

    def names(self):
        return sorted(self._by_name)

    def __len__(self) -> int:
        return len(self._by_name)


def _spec_tag(spec: kr.KratosSpec, draft_spec=None, tier_specs=()) -> str:
    """Artifact-identity tag: every field that changes the serving buffers.

    The draft-spec fields are INCLUDED when present — a drafted artifact
    and its plain twin are different serving models and must never collide
    under one name in `Registry.get`. Same for the QoS tier ladder."""
    tag = kr.spec_tag(spec.sparsity, spec.bits, spec.act_bits, spec.impl)
    if draft_spec is not None:
        tag += f"+draft[{draft_spec.tag}]"
    if tier_specs:
        tag += "+tiers[" + ",".join(ts.tag for ts in tier_specs) + "]"
    return tag


def _iter_packable(params):
    def walk(node, name):
        if _is_packable(node, name):
            yield node
        elif isinstance(node, dict):
            for k, v in node.items():
                yield from walk(v, k)
        elif isinstance(node, list):
            for v in node:
                yield from walk(v, name)
    yield from walk(params, "")


def _iter_packed(params):
    def walk(node):
        if isinstance(node, kr.PackedLinear):
            yield node
        elif isinstance(node, dict):
            for v in node.values():
                yield from walk(v)
        elif isinstance(node, list):
            for v in node:
                yield from walk(v)
    yield from walk(params)
