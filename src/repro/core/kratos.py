"""KratosSpec / Kratos linear: the paper's contribution as a first-class,
composable JAX feature.

A `KratosSpec` attaches to any weight-stationary projection in any model
config and selects:

  * `impl='tree'`      — gathered block-sparse compute ('gemmt'): FLOPs and
                         weight traffic ∝ (1 - sparsity);
  * `impl='systolic'`  — dense compute on masked weights ('gemms'): zero
                         weights still cost full FLOPs (the paper's negative
                         control, and the dense fast path at sparsity 0);
  * `bits`             — weight precision in {8,4,2,1} (None = native bf16/f32);
                         training uses QAT fake-quant w/ straight-through
                         gradients, serving uses bit-packed kernels;
  * `act_bits=8`       — optional w8a8 (2x MXU rate on TPU);
  * `bk, bn`           — sparsity block granularity (the Table-III 'LUT size'
                         analogue, sweepable);
  * `unroll`           — 'pixelwise' | 'row' | 'full': the grid
                         parallelization degree (how much of the output is
                         produced per kernel invocation), Table I's input
                         unrolling factor.

Training params stay a dense float `w` (so optimizers/checkpoints are
oblivious); the plan is a pure function of (shape, spec) and is applied at
trace time. `pack()` converts trained params to packed serving buffers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as qz
from repro.core import sparsity as sp
from repro.instrument import scope
from repro.kernels import ops
from repro.kernels import ref as kref

UNROLL_FACTORS = ("pixelwise", "row", "full")


@dataclasses.dataclass(frozen=True)
class KratosSpec:
    sparsity: float = 0.0
    bits: Optional[int] = None
    impl: str = "tree"                # 'tree' | 'systolic'
    unroll: str = "full"
    bk: int = 128
    bn: int = 128
    act_bits: Optional[int] = None    # 8 => w8a8 serving path
    seed: int = 0

    def __post_init__(self):
        if self.impl not in ("tree", "systolic"):
            raise ValueError(f"impl must be tree|systolic, got {self.impl}")
        if self.unroll not in UNROLL_FACTORS:
            raise ValueError(f"unroll must be one of {UNROLL_FACTORS}")
        if self.bits is not None and self.bits not in qz.SUPPORTED_BITS:
            raise ValueError(f"bits must be in {qz.SUPPORTED_BITS} or None")
        if self.act_bits not in (None, 8):
            raise ValueError("act_bits must be None or 8")

    @property
    def is_identity(self) -> bool:
        """True if this spec degenerates to a plain dense matmul."""
        return self.sparsity == 0.0 and self.bits is None and self.act_bits is None

    def with_(self, **kw) -> "KratosSpec":
        return dataclasses.replace(self, **kw)


DENSE = KratosSpec()


def spec_tag(sparsity: float, bits: Optional[int], act_bits: Optional[int],
             impl: str) -> str:
    """Artifact-tag fragment shared by serve.registry._spec_tag and
    serve.speculative.DraftSpec.tag — ONE formatter, so the registry's
    no-name-collision guarantee can't drift between the two."""
    b = "bf16" if bits is None else f"w{bits}"
    if act_bits:
        b += f"a{act_bits}"
    return f"s{sparsity:g}-{b}-{impl}"


@functools.lru_cache(maxsize=4096)
def _plan_cached(n_in: int, n_out: int, bk: int, bn: int,
                 sparsity_milli: int, seed: int) -> sp.BlockSparsePlan:
    return sp.make_plan(n_in, n_out, bk=bk, bn=bn,
                        sparsity=sparsity_milli / 1000.0, seed=seed)


def plan_for(n_in: int, n_out: int, spec: KratosSpec) -> Optional[sp.BlockSparsePlan]:
    """The (deterministic, cached) block plan for a given projection.

    Returns None (= dense) when the projection's shape doesn't divide the
    block grid: an arch-wide spec touches every GEMM in the model, and the
    odd-shaped ones (MLA rope stubs, SSM x_proj, routers) simply fall off
    the sparsity grid rather than failing the whole model — the paper's
    granularity lesson: the block geometry only pays where it fits.
    """
    if spec.sparsity == 0.0 or n_in % spec.bk or n_out % spec.bn:
        return None
    return _plan_cached(n_in, n_out, spec.bk, spec.bn,
                        int(round(spec.sparsity * 1000)), spec.seed)


# ---------------------------------------------------------------------------
# Init / training apply
# ---------------------------------------------------------------------------

def init(key, n_in: int, n_out: int, spec: KratosSpec = DENSE,
         dtype=jnp.float32, init_scale: Optional[float] = None) -> Dict[str, Any]:
    """Dense float master weight; pruned blocks start (and stay) zero."""
    scale = (n_in ** -0.5) if init_scale is None else init_scale
    w = jax.random.normal(key, (n_in, n_out), dtype) * jnp.asarray(scale, dtype)
    plan = plan_for(n_in, n_out, spec)
    if plan is not None:
        w = sp.sparsify_init(w, plan)
    return {"w": w}


def _ste_quant(w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Fake-quant forward, straight-through backward."""
    return w + jax.lax.stop_gradient(qz.fake_quantize(w, bits) - w)


def apply(params: Dict[str, Any], x: jnp.ndarray, spec: KratosSpec = DENSE,
          *, backend: str = "ref", name: str = "") -> jnp.ndarray:
    """Training-time application: y = x @ kratos(w).

    x: (..., n_in) -> (..., n_out). The tree path gathers only live blocks,
    so jit/cost_analysis see (1 - sparsity) of the dense FLOPs; the systolic
    path multiplies a masked dense weight (full FLOPs) — faithful to Fig. 5.

    A `PackedLinear` leaf (serving trees built by serve.registry) dispatches
    to `apply_packed`, so the hot decode path runs on packed buffers while
    every model call site stays oblivious.

    name: the projection's name (`wq`, `w_down`, ...); its ops run under
    the `gemm` contract scope with `name` below it (repro.instrument).
    """
    if isinstance(params, PackedLinear):
        # packed buffers are only meaningful under their pack-time spec —
        # the arch-wide `spec` argument may describe a DIFFERENT tier of
        # the same weights (serve.qos tier swaps)
        return apply_packed(params.buffers, x,
                            spec if params.spec is None else params.spec,
                            params.n_in, params.n_out, backend=backend,
                            name=name)
    with scope("gemm", name):
        w = params["w"]
        n_in, n_out = w.shape
        lead = x.shape[:-1]
        xm = x.reshape(-1, n_in)
        if spec.bits is not None:
            w = _ste_quant(w, spec.bits)
        plan = plan_for(n_in, n_out, spec)
        if plan is None or spec.impl == "systolic":
            if plan is not None:  # systolic: mask, but pay dense compute
                w = w * jnp.asarray(sp.plan_mask(plan), w.dtype)
            y = ops.matmul(xm, w.astype(x.dtype), backend=backend) \
                if backend != "ref" \
                else kref.dense_matmul_ref(xm, w.astype(x.dtype))
        else:
            blocks = sp.pack_blocks(w.astype(x.dtype), plan)
            # CO-DESIGN constraint (DESIGN.md §7): the packed blocks must
            # keep the weight's tensor-parallel output sharding — without
            # this, the pack reshape/gather loses it, every device computes
            # ALL output blocks, and the sparsity saving is eaten by
            # replication. Requires the block width bn to divide the TP
            # shard width (n_out / |model|): the sparsity granularity and
            # the fabric's shard granularity are coupled — the paper's
            # LUT-size lesson reappearing as TP geometry.
            from repro.models import layers as L  # lazy: layers imports kratos
            blocks = L.shard(blocks, "out_blocks", None, None, None)
            if backend == "ref":
                y = kref.bsr_matmul_ref(xm, blocks, plan.indices)
            else:
                y = ops.bsr_matmul(xm, blocks, jnp.asarray(plan.indices),
                                   backend=backend)
        return y.reshape(*lead, n_out)


# ---------------------------------------------------------------------------
# Serving: pack + apply_packed
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedLinear:
    """A projection frozen into packed serving buffers.

    A drop-in replacement for the training-time `{"w": ...}` leaf dict:
    `apply()` dispatches it to `apply_packed`, so a whole model's parameter
    tree can be re-pointed at packed buffers (serve.registry.pack_model_params)
    without touching any model code. The logical (n_in, n_out) shape rides in
    pytree aux-data — buffers alone can't recover it (the tree path drops
    pruned k-blocks, sub-byte codes fold `VALUES_PER_BYTE` rows per byte).

    Stacked scan-block projections keep a leading layer axis on every buffer;
    `lax.scan` slices the leaves per layer while (n_in, n_out) stay static.

    `spec` is the PACK-TIME spec (serving_spec-degraded): the buffers are
    only meaningful under the plan/bit-layout it describes, so `apply`
    consults it — not the arch-wide spec of the surrounding config — when
    dispatching a PackedLinear. This is what lets a QoS tier swap
    (serve.qos) re-point a live model at a tree packed under a DIFFERENT
    (sparsity, bits) point: the spec rides in pytree aux-data, so jit
    retraces against the right plan automatically.
    """

    buffers: Dict[str, Any]
    n_in: int
    n_out: int
    spec: Optional[KratosSpec] = None

    def tree_flatten(self):
        keys = tuple(sorted(self.buffers))
        return (tuple(self.buffers[k] for k in keys),
                (keys, self.n_in, self.n_out, self.spec))

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, n_in, n_out, spec = aux
        return cls(buffers=dict(zip(keys, children)), n_in=n_in, n_out=n_out,
                   spec=spec)

    @property
    def packed_bytes(self) -> int:
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.buffers):
            total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        return total


jax.tree_util.register_pytree_node(
    PackedLinear, PackedLinear.tree_flatten, PackedLinear.tree_unflatten)


def pack_linear(params: Dict[str, Any], spec: KratosSpec) -> PackedLinear:
    """pack() a `{"w": (n_in, n_out)}` training leaf into a PackedLinear.

    A stacked `(n_layers, n_in, n_out)` weight (scan blocks) is packed per
    layer via vmap — the plan is shape-deterministic, so every layer shares
    it and the buffers stack cleanly.
    """
    w = params["w"]
    if w.ndim == 3:
        n_in, n_out = int(w.shape[1]), int(w.shape[2])
    elif w.ndim == 2:
        n_in, n_out = int(w.shape[0]), int(w.shape[1])
    else:
        raise ValueError(f"pack_linear expects a 2-D or stacked 3-D weight, "
                         f"got shape {w.shape}")
    spec = serving_spec(n_in, n_out, spec)
    if w.ndim == 3:
        buffers = jax.vmap(lambda wl: pack({"w": wl}, spec))(w)
    else:
        buffers = pack(params, spec)
    return PackedLinear(buffers=buffers, n_in=n_in, n_out=n_out, spec=spec)


# TPU vector tiles are (8, 128): a Pallas block's last dim must be a
# multiple of the 128 lanes. The block-sparse kernels stream x tiles
# (bm, bk) and weight blocks (bk, bn), so both block extents sit on lanes.
PALLAS_LANES = 128


def check_pallas_blocks(packed: PackedLinear) -> None:
    """Raise unless the block-sparse Pallas kernels can tile `packed`.

    Dense and quantized buffers fit their GEMM blocks to the projection
    (kernels.ops._fit_block); gathered sparsity blocks keep the pack-time
    (bk, bn), which the TPU refuses below the lane width. Small blocks stay
    valid for backend='ref' and 'interpret'."""
    if packed.spec is None or not {"blocks", "qblocks"} & set(packed.buffers):
        return
    bk, bn = packed.spec.bk, packed.spec.bn
    if bk % PALLAS_LANES or bn % PALLAS_LANES:
        raise ValueError(
            f"backend='pallas' needs sparsity blocks that are multiples of "
            f"{PALLAS_LANES} (the TPU lane width); this {packed.n_in}x"
            f"{packed.n_out} projection was packed with bk={bk}, bn={bn}. "
            f"Pack with bk=bn={PALLAS_LANES}, or serve with backend='ref'.")


def serving_spec(n_in: int, n_out: int, spec: KratosSpec) -> KratosSpec:
    """Degrade an arch-wide spec to what a given projection can pack.

    Sub-byte code packing folds `VALUES_PER_BYTE[bits]` reduction rows per
    byte; a projection (or sparse block) whose k-extent doesn't divide that
    keeps float weights. `apply_packed` dispatches on the buffer keys
    actually present, so pack- and apply-time decisions can't diverge.
    """
    if spec.bits is None:
        return spec
    vpb = qz.VALUES_PER_BYTE[spec.bits]
    tree = spec.impl == "tree" and plan_for(n_in, n_out, spec) is not None
    k_extent = spec.bk if tree else n_in
    if k_extent % vpb:
        spec = spec.with_(bits=None, act_bits=None)
    return spec


def pack(params: Dict[str, Any], spec: KratosSpec) -> Dict[str, Any]:
    """Convert trained dense params into packed inference buffers."""
    w = params["w"]
    n_in, n_out = w.shape
    plan = plan_for(n_in, n_out, spec)
    out: Dict[str, Any] = {}
    if plan is None or spec.impl == "systolic":
        if plan is not None:
            w = w * jnp.asarray(sp.plan_mask(plan), w.dtype)
        if spec.bits is None:
            out["w"] = w
        else:
            out["qt"] = qz.quantize(w, spec.bits)
        return out
    # tree path
    if spec.bits is None:
        out["blocks"] = sp.pack_blocks(w, plan)
    else:
        scale = qz.compute_scale(w, spec.bits)               # (n_out,)
        codes = qz.quantize_values(w, scale, spec.bits)      # int8 dense codes
        cblocks = sp.pack_blocks(codes, plan)                # (n_pb,nnz,bk,bn) i8
        n_pb, nnz, bk, bn = cblocks.shape
        vpb = qz.VALUES_PER_BYTE[spec.bits]
        packed = jax.vmap(lambda b: qz.pack_codes(b, spec.bits))(
            cblocks.reshape(n_pb * nnz, bk, bn))
        out["qblocks"] = packed.reshape(n_pb, nnz, bk // vpb, bn)
        out["qscale"] = jnp.asarray(scale, jnp.float32).reshape(n_pb, bn)
    return out


def apply_packed(packed: Dict[str, Any], x: jnp.ndarray, spec: KratosSpec,
                 n_in: int, n_out: int, *, backend: str = "ref",
                 name: str = "") -> jnp.ndarray:
    """Inference-time application on packed buffers.

    Dispatch is keyed on WHICH buffers `pack()` produced (dense 'w',
    quantized 'qt', gathered 'blocks', quantized-gathered 'qblocks'), so a
    spec degraded at pack time (serving_spec) stays consistent here.
    name: as in `apply`, the projection's name below the `gemm` scope.
    """
    with scope("gemm", name):
        lead = x.shape[:-1]
        xm = x.reshape(-1, n_in)
        plan = None
        if "blocks" in packed or "qblocks" in packed:
            plan = plan_for(n_in, n_out, spec)
        if "w" in packed:
            w = packed["w"].astype(x.dtype)
            y = kref.dense_matmul_ref(xm, w) if backend == "ref" \
                else ops.matmul(xm, w, backend=backend)
        elif "qt" in packed:
            if spec.act_bits == 8 and packed["qt"].bits == 8:
                y = ops.quant_matmul_w8a8(xm, packed["qt"], backend=backend)
            else:
                y = ops.quant_matmul(xm, packed["qt"], backend=backend)
        elif "blocks" in packed:
            if backend == "ref":
                y = kref.bsr_matmul_ref(xm, packed["blocks"], plan.indices)
            else:
                y = ops.bsr_matmul(xm, packed["blocks"],
                                   jnp.asarray(plan.indices), backend=backend)
        else:
            y = ops.bsr_quant_matmul(xm, packed["qblocks"], packed["qscale"],
                                     jnp.asarray(plan.indices), spec.bits,
                                     backend=backend)
        return y.reshape(*lead, n_out)


# ---------------------------------------------------------------------------
# Cost accounting (the 'area report' of the benchmark workflow)
# ---------------------------------------------------------------------------

def cost_report(n_in: int, n_out: int, spec: KratosSpec, m: int = 1,
                act_bytes: int = 2) -> Dict[str, float]:
    """Analytic effective cost of one application — the TPU restatement of
    the paper's ALM-utilization report.

    Returns effective MACs, weight bytes, and MXU-rate credit, relative and
    absolute. Dense bf16 reference: m*n_in*n_out MACs, 2 bytes/weight.
    """
    dense_macs = m * n_in * n_out
    plan = plan_for(n_in, n_out, spec)
    keep = 1.0 if plan is None else plan.dense_flops_fraction
    macs = dense_macs * (keep if spec.impl == "tree" else 1.0)
    wbits = 16 if spec.bits is None else spec.bits
    weight_bytes = n_in * n_out * wbits / 8.0
    if spec.impl == "tree":
        weight_bytes *= keep
    mxu_rate = 2.0 if (spec.act_bits == 8 and spec.bits == 8) else 1.0
    return {
        "dense_macs": float(dense_macs),
        "effective_macs": float(macs),
        "mac_fraction": float(macs / dense_macs),
        "weight_bytes": float(weight_bytes),
        "weight_bytes_fraction": float(weight_bytes / (2.0 * n_in * n_out)),
        "mxu_rate": mxu_rate,
        "equiv_compute_time_fraction": float(macs / dense_macs / mxu_rate),
    }
