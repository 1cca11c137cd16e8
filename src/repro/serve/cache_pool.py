"""Slab-allocated KV-cache pool with per-request slot assignment.

One `transformer.make_caches(cfg, n_slots, max_len)` slab is allocated at
engine construction and never reallocated: a request entering the engine is
assigned a free SLOT (one batch row of every cache leaf in the tree), its
prefilled batch-1 cache is written into that row, and the row is returned to
the free list when the request completes. The decode step always runs over
the whole slab — per-slot validity masks (models.attention) make the stale
rows inert, so freeing is O(1) bookkeeping with no memory traffic.

Slab layout contract (transformer.make_caches): unscanned 'prelude' entries
carry the batch axis at dim 0; scanned 'blocks' entries are layer-stacked,
so their batch axis is dim 1. `write_slot` maps over the two groups with the
right axis — the only place in the serving stack that knows this.

Donation: `write_slot` donates the slab (`donate_argnums=(0,)`), so on
backends with buffer donation (TPU/GPU) the slot install is an in-place row
write — the slab is never copied per admission. The incoming batch-1 tree is
not donated: no output has its shape, so XLA could not reuse it and would
warn. On CPU, XLA has no donation and falls back to a copy (the warning is
filtered: it is the expected, documented fallback, not a bug).

Mesh placement: constructed with `mesh=`, the pool resolves one
`NamedSharding` per cache leaf via `sharding.cache_pspecs(..., slab=True)`
(leading slot axis sharded like batch, replicated fallback), places the slab
with `device_put`, and pins the slot-install's `out_shardings` to the same
tree so donation keeps aliasing the sharded buffers (an output that changed
placement could not reuse the donated slab). `shardings` is exposed for the
execution backend to reuse as the decode step's cache in/out shardings.
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.instrument import scoped
from repro.models import transformer as T
from repro.serve.trace import NULL_TRACER


@contextlib.contextmanager
def quiet_donation():
    """Scoped suppression of the CPU no-donation warning.

    The serving hot path donates buffers (in-place on TPU/GPU); CPU has no
    donation and warns before falling back to a copy — expected, documented
    behavior, suppressed ONLY around our own donating dispatches so a user's
    broken donate_argnums elsewhere still warns. On any other backend the
    warning means a donated buffer was copied, a bug, so it stays loud."""
    if jax.default_backend() != "cpu":
        yield
        return
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class PoolExhausted(RuntimeError):
    """No free cache slot: the scheduler must hold the request in the queue."""


@scoped("kv_write")
def _write_tree(slab: Dict, single: Dict, slot) -> Dict:
    """Write a batch-1 cache tree into row `slot` of the slab (functional)."""
    pre = jax.tree_util.tree_map(
        lambda s, u: jax.lax.dynamic_update_slice_in_dim(
            s, u.astype(s.dtype), slot, axis=0),
        slab["prelude"], single["prelude"])
    blk = jax.tree_util.tree_map(
        lambda s, u: jax.lax.dynamic_update_slice_in_dim(
            s, u.astype(s.dtype), slot, axis=1),
        slab["blocks"], single["blocks"])
    return {"prelude": pre, "blocks": blk}


class CachePool:
    """Fixed-slot KV pool; slots are reused LIFO (hot rows stay hot)."""

    # class attribute: the engine re-points this at its Tracer when tracing
    # is on; the slab pool emits no page events, but sharing the attribute
    # keeps the backend surface uniform with PagedCachePool
    tracer = NULL_TRACER

    def __init__(self, cfg: T.ModelConfig, n_slots: int, max_len: int,
                 dtype=jnp.float32, *, mesh=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.dtype = dtype
        self.mesh = mesh
        self.caches = T.make_caches(cfg, n_slots, max_len, dtype)
        self._free: List[int] = list(range(n_slots - 1, -1, -1))
        self.shardings = None
        # donate the slab: the slot install updates its row in place (no
        # per-admission slab copy; see module docstring)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.distributed import sharding as SH
            pspecs = SH.cache_pspecs(self.caches, mesh, n_slots, slab=True)
            self.shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pspecs)
            self.caches = jax.device_put(self.caches, self.shardings)
            self._write = jax.jit(_write_tree, donate_argnums=(0,),
                                  out_shardings=self.shardings)
        else:
            self._write = jax.jit(_write_tree, donate_argnums=(0,))
        self._single_template = None

    @property
    def single_template(self) -> Dict:
        """Batch-1 cache tree for template-style prefills (lazy: the engine's
        donation path allocates prefill caches inside the compiled step and
        never touches this)."""
        if self._single_template is None:
            self._single_template = T.make_caches(
                self.cfg, 1, self.max_len, self.dtype)
        return self._single_template

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise PoolExhausted(
                f"all {self.n_slots} cache slots in use; admission must wait")
        return self._free.pop()

    def free(self, slot: int) -> None:
        if not (0 <= slot < self.n_slots):
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"double-free of slot {slot}")
        self._free.append(slot)

    def write_slot(self, slot: int, single: Dict) -> None:
        """Install a prefilled batch-1 cache tree into `slot` of the slab."""
        with quiet_donation():
            self.caches = self._write(self.caches, single,
                                      jnp.asarray(slot, jnp.int32))

    def bytes(self) -> int:
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(self.caches))
