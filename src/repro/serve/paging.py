"""Block-paged KV pool with radix-tree prefix reuse (serve.prefix).

The slab (serve.cache_pool) reserves one `max_len` stride per slot, so slot
count is `mem / max_len` no matter how short requests actually are, and
every admission prefills its whole prompt even when the prompt's prefix is
already resident in another slot. This pool carves the SAME preallocated
memory into fixed-size PAGES instead:

  * every cache leaf with a positional sequence axis is stored PAGE-MAJOR —
    the page axis sits exactly where the slab's slot axis sat (so the
    layer-stacked 'blocks' leaves keep their leading scan axis and shard
    page-over-data the way slots did: `page_pspecs`), and the sequence
    axis shrinks to `page_size`;
  * each slot owns an int32 row of a `(n_slots, pages_per_slot)` PAGE TABLE
    mapping logical position `p` to physical page `table[slot, p // P]`;
  * page alloc/free is O(1) free-list bookkeeping with REFCOUNTS — a page
    shared by `k` slots (and/or retained by the prefix index) frees only
    when the last reference drops;
  * decode consumes the table NATIVELY: the attention layers read K/V
    straight through the page table (kernels.ops.paged_attention — the
    Pallas kernel's BlockSpec index map translates (slot, kv-block) ->
    page id via scalar prefetch) and write new tokens with in-place
    page-indexed scatters, so no per-dispatch slab materialization exists
    (distributed.steps.make_paged_decode_step). The legacy gather/scatter
    wrap survives behind `native=False` for A/B testing; `GATHER_EVENTS`
    records every gather/scatter trace so tests can assert the hot path
    stays gather-free.

Leaf classification (PageLayout): a leaf is PAGED when its second-to-last
axis is the `cache_len` positional sequence axis — full-window attention
k/v, MLA `c_kv`/`k_rope`. Everything else is RESIDENT and keeps the
slot-major slab layout inside the same store: recurrent SSM `conv`/`ssm`
state (O(1) per slot — nothing to page), `cross` encoder caches (written
once at prefill), and circular sliding-window leaves (size W < cache_len;
their position->slot map wraps, so page identity is not position identity).

Page 0 is the reserved WRITE SINK: freed slots' table rows reset to it, so
an idle slot's garbage decode writes land in a page nobody reads (under
the slab they landed in the freed slot's own row) instead of corrupting a
page that was recycled to a live slot or retained by the prefix index.
Rows past a slot's allocated length also point at page 0; the per-slot
validity masks keep those positions inert exactly as they keep the slab's
unwritten tail inert.

Prefix reuse: `prefix_match` returns the longest PAGE-ALIGNED cached
prefix of a prompt (capped at prompt_len - 1 so at least one suffix token
remains to produce the first-sample logits); admission bumps the shared
pages' refcounts (`alloc_pages`), prefills only the suffix through the
existing s>1 decode-form block write (steps.make_suffix_prefill_step), and
publishes the request's full-prompt pages into the radix tree
(`prefix_insert`). At request FINISH the engine additionally publishes the
whole conversation — prompt + generated tokens — via
`conversation_insert`, so a multi-turn follow-up (new prompt = old
conversation + new user text) skips prefill over everything said so far,
not just the shared system prompt. Sharing needs no copy-on-write copy:
only pages with COMPLETE, final KV are ever published (full prompt pages
at admission; conversation pages up to the last token whose KV decode
actually wrote), so a sharer's first own write lands strictly past the
shared region, and speculative write-headroom pages are private by the
same argument. Under page pressure, allocation first evicts LRU
tree pages nobody else references; if that still doesn't cover the
request, `PoolExhausted` surfaces to the scheduler (the engine requeues
the admission) instead of crashing the step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.instrument import REGISTRY
from repro.models import transformer as T
from repro.serve.cache_pool import PoolExhausted, quiet_donation
from repro.serve.prefix import PrefixIndex
from repro.serve.trace import NULL_TRACER

# (op, n_paged_leaves, slab_view_bytes) appended at TRACE time whenever a
# full gather/scatter materializes the slab view — the paged analogue of
# kernels.pallas_compat.SKINNY_M_EVENTS. Native paged decode must trace
# ZERO of these; tests and serve_bench assert it. Registry-backed
# (repro.instrument.REGISTRY, stream "gather") with scoped reset; the
# historical name aliases the same list. (gather_one/scatter_one —
# admission-path slot installs — do not count: they are off the decode hot
# path by design.)
GATHER_EVENTS = REGISTRY.event_list("gather")


def prefix_supported(cfg: T.ModelConfig) -> bool:
    """Archs whose WHOLE per-request cache state is positional and paged —
    the precondition for prefix sharing to reproduce a prefill exactly.
    Recurrent state (SSM/hybrid) is not positional, circular windows
    overwrite position identity, enc-dec/vision prompts carry non-token
    conditioning the token-ID radix key cannot see."""
    return not (cfg.is_ssm or cfg.attn_period or cfg.enc_dec
                or cfg.n_img_tokens or cfg.window or cfg.frontend)


@dataclasses.dataclass(frozen=True)
class _LeafSpec:
    name: str            # dotted path, for describe()/dry-run printing
    paged: bool
    batch_axis: int      # 0 prelude leaves, 1 layer-stacked 'blocks' leaves


class PageLayout:
    """Leaf classification + gather/scatter between page store and slab.

    The store is the flat leaf list of `T.make_caches(cfg, n_slots,
    cache_len)` with every PAGED leaf re-laid out page-major: slab
    `(..., B at batch_axis, ..., cache_len, d)` becomes
    `(..., n_pages at batch_axis, ..., page_size, d)` — the page axis
    REPLACES the slot axis in place (a page belongs to whichever slots
    reference it), which keeps the layer-stacked 'blocks' leaves' leading
    scan axis where `T.forward`'s lax.scan expects it, so the native paged
    decode can hand store leaves straight to the attention layers.
    RESIDENT leaves keep the slab layout. `gather` rebuilds the exact slab
    tree (view sliced to `cache_len`, bit-identical to the slab rows on
    every valid position); `scatter` splits the view back into pages
    (zero-padding the final partial page, which is private by construction
    — see module docstring). Both are now the LEGACY path (native decode
    reads through the table instead) and trace into `GATHER_EVENTS`.
    """

    def __init__(self, cfg: T.ModelConfig, n_slots: int, cache_len: int,
                 page_size: int, dtype=jnp.float32):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.cfg, self.n_slots = cfg, n_slots
        self.cache_len, self.page_size = cache_len, page_size
        self.pp = -(-cache_len // page_size)          # pages per slot
        template = jax.eval_shape(
            lambda: T.make_caches(cfg, n_slots, cache_len, dtype))
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(template)
        self.slab_shapes = [leaf.shape for _, leaf in flat]
        self.dtypes = [leaf.dtype for _, leaf in flat]
        self.specs: List[_LeafSpec] = []
        for path, leaf in flat:
            names = [str(k.key) for k in path if hasattr(k, "key")]
            bax = 1 if names and names[0] == "blocks" else 0
            resident = any(n in ("conv", "ssm", "cross") for n in names)
            paged = (not resident and leaf.ndim >= 3
                     and leaf.shape[-2] == cache_len)
            self.specs.append(_LeafSpec(".".join(names), paged, bax))
        self.has_paged = any(s.paged for s in self.specs)

    # ------------------------------------------------------------- shapes

    def store_shapes(self, n_pages: int) -> List[Tuple[int, ...]]:
        out = []
        for shape, spec in zip(self.slab_shapes, self.specs):
            if not spec.paged:
                out.append(tuple(shape))
                continue
            shp = list(shape)
            shp[spec.batch_axis] = n_pages   # page axis replaces slot axis
            shp[-2] = self.page_size
            out.append(tuple(shp))
        return out

    def make_store(self, n_pages: int) -> List[jnp.ndarray]:
        return [jnp.zeros(s, d)
                for s, d in zip(self.store_shapes(n_pages), self.dtypes)]

    def slab_view_bytes(self) -> int:
        """Bytes of the full slab view a gather materializes (paged leaves
        only) — the per-direction cost the native path avoids."""
        return sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                   for shape, dt, spec in zip(self.slab_shapes, self.dtypes,
                                              self.specs) if spec.paged)

    # ------------------------------------------------------ gather/scatter

    def _gather_leaf(self, store_leaf, table, spec: _LeafSpec):
        bax = spec.batch_axis
        idx = (slice(None),) * bax + (table,)
        g = store_leaf[idx]                       # (..., B, pp, ..., P, d)
        g = jnp.moveaxis(g, bax + 1, -3)          # (..., B, ..., pp, P, d)
        g = g.reshape(*g.shape[:-3], g.shape[-3] * g.shape[-2], g.shape[-1])
        return jax.lax.slice_in_dim(g, 0, self.cache_len, axis=-2)

    def _scatter_leaf(self, store_leaf, table, slab_leaf, spec: _LeafSpec):
        bax = spec.batch_axis
        x = slab_leaf
        pad = self.pp * self.page_size - self.cache_len
        if pad:   # final partial page: private by construction (docstring)
            x = jnp.concatenate(
                [x, jnp.zeros((*x.shape[:-2], pad, x.shape[-1]), x.dtype)],
                axis=-2)
        x = x.reshape(*x.shape[:-2], self.pp, self.page_size, x.shape[-1])
        x = jnp.moveaxis(x, -3, bax + 1)          # (..., B, pp, ..., P, d)
        idx = (slice(None),) * bax + (table,)
        return store_leaf.at[idx].set(x.astype(store_leaf.dtype))

    def gather(self, store: List[jnp.ndarray], page_table) -> Dict:
        """Page store + (n_slots, pp) table -> the full slab cache tree.

        LEGACY path (steps' native=False A/B form): traces a GATHER_EVENTS
        entry so hot-path tests can prove native decode never calls it."""
        GATHER_EVENTS.append(("gather", sum(s.paged for s in self.specs),
                              self.slab_view_bytes()))
        out = [leaf if not spec.paged
               else self._gather_leaf(leaf, page_table, spec)
               for leaf, spec in zip(store, self.specs)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def scatter(self, store, page_table, caches) -> List[jnp.ndarray]:
        """Slab cache tree -> page store (resident leaves adopt the
        forward's functional update; paged leaves scatter into their
        pages — shared pages receive back the identical values they
        contributed, private pages the new writes). LEGACY path; traces
        a GATHER_EVENTS entry like `gather`."""
        GATHER_EVENTS.append(("scatter", sum(s.paged for s in self.specs),
                              self.slab_view_bytes()))
        leaves = jax.tree_util.tree_leaves(caches)
        return [leaf if not spec.paged
                else self._scatter_leaf(sl, page_table, leaf, spec)
                for sl, leaf, spec in zip(store, leaves, self.specs)]

    # ----------------------------------------------------- native (no copy)

    def as_tree(self, store: List[jnp.ndarray]) -> Dict:
        """Zero-cost cache-tree view of the page store for the NATIVE paged
        forward: same treedef as the slab tree (the page axis sits exactly
        where the slot axis sat), paged leaves ARE the store leaves. The
        attention layers detect the paged leaves via the `pages` operand
        and read/write them through the table."""
        return jax.tree_util.tree_unflatten(self.treedef, list(store))

    def from_tree(self, caches: Dict) -> List[jnp.ndarray]:
        """Inverse of `as_tree` (flat store leaf list, functional updates
        from the forward included)."""
        return list(jax.tree_util.tree_leaves(caches))

    def gather_one(self, store, table_row, slot) -> Dict:
        """Batch-1 view of one slot (suffix prefill / slot install)."""
        out = []
        for leaf, spec in zip(store, self.specs):
            if spec.paged:
                out.append(self._gather_leaf(leaf, table_row[None], spec))
            else:
                out.append(jax.lax.dynamic_slice_in_dim(
                    leaf, slot, 1, axis=spec.batch_axis))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def scatter_one(self, store, table_row, slot, caches):
        leaves = jax.tree_util.tree_leaves(caches)
        out = []
        for sl, leaf, spec in zip(store, leaves, self.specs):
            if spec.paged:
                out.append(self._scatter_leaf(sl, table_row[None], leaf,
                                              spec))
            else:
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    sl, leaf.astype(sl.dtype), slot, axis=spec.batch_axis))
        return out


def _install_one(layout: PageLayout):
    """(store, single, page_table, slot) -> store: slot install, jittable
    with the store donated — the paged analogue of CachePool._write."""
    def install(store, single, page_table, slot):
        row = jax.lax.dynamic_index_in_dim(page_table, slot, axis=0,
                                           keepdims=False)
        return layout.scatter_one(store, row, slot, single)
    return install


def _set_row(page_table, slot, row):
    return page_table.at[slot].set(row)


class PagedCachePool:
    """Fixed-page KV pool: refcounted pages + per-slot page tables.

    Drop-in for `CachePool` behind the execution backends (same
    alloc/free/n_free/n_active/write_slot surface) plus the paging and
    prefix-reuse surface the engine's admission path drives:
    `prefix_match` -> `alloc_pages` -> (suffix) prefill -> `prefix_insert`.
    `max_len` counts cache positions per slot INCLUDING any speculative
    write headroom, exactly like CachePool.
    """

    # re-pointed at the engine's Tracer when tracing is on (page alloc/
    # free/evict events); admission-path only, never the decode hot path
    tracer = NULL_TRACER

    def __init__(self, cfg: T.ModelConfig, n_slots: int, max_len: int,
                 dtype=jnp.float32, *, page_size: int,
                 n_pages: Optional[int] = None, prefix_cache: bool = True,
                 mesh=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.cfg, self.n_slots = cfg, n_slots
        self.max_len, self.dtype, self.mesh = max_len, dtype, mesh
        self.layout = PageLayout(cfg, n_slots, max_len, page_size, dtype)
        self.page_size, self.pp = page_size, self.layout.pp
        # +1: page 0 is the reserved write sink, never allocated
        self.n_pages = n_pages if n_pages is not None \
            else n_slots * self.pp + 1
        if self.layout.has_paged and self.n_pages < self.pp + 1:
            raise ValueError(
                f"n_pages={self.n_pages} cannot hold even one full slot "
                f"({self.pp} pages) plus the reserved sink page")
        self.store = self.layout.make_store(self.n_pages)
        self.page_table = jnp.zeros((n_slots, self.pp), jnp.int32)
        self._table = np.zeros((n_slots, self.pp), np.int32)
        self.refs = np.zeros(self.n_pages, np.int32)
        self.refs[0] = 1                       # the sink is never freeable
        self._free_pages: List[int] = list(range(self.n_pages - 1, 0, -1))
        self._slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
        self._free_slots: List[int] = list(range(n_slots - 1, -1, -1))
        self.index = PrefixIndex(page_size) \
            if (prefix_cache and self.layout.has_paged
                and prefix_supported(cfg)) else None
        self.shardings = None
        self.table_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.distributed import sharding as SH
            pspecs = SH.page_pspecs(
                jax.eval_shape(lambda: T.make_caches(cfg, n_slots, max_len,
                                                     dtype)),
                self.layout, mesh, self.n_pages)
            self.shardings = [NamedSharding(mesh, s) for s in pspecs]
            self.store = jax.device_put(self.store, self.shardings)
            slot_spec = SH.batch_pspec(mesh, n_slots)
            self.table_sharding = NamedSharding(
                mesh, P(*(tuple(slot_spec) + (None,))))
            self.page_table = jax.device_put(self.page_table,
                                             self.table_sharding)
            self._write = jax.jit(_install_one(self.layout),
                                  donate_argnums=(0,),
                                  out_shardings=self.shardings)
            self._set = jax.jit(_set_row, donate_argnums=(0,),
                                out_shardings=self.table_sharding)
        else:
            self._write = jax.jit(_install_one(self.layout),
                                  donate_argnums=(0,))
            self._set = jax.jit(_set_row, donate_argnums=(0,))

    # -------------------------------------------------------------- slots

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free_slots)

    def alloc(self) -> int:
        if not self._free_slots:
            raise PoolExhausted(
                f"all {self.n_slots} cache slots in use; admission must wait")
        return self._free_slots.pop()

    def free(self, slot: int) -> None:
        """Release a slot AND its page references. Pages retained by the
        prefix index survive (refcount >= 1); private suffix/headroom pages
        return to the free list. The slot's table row resets to the sink
        page so its stale decode writes can never touch a recycled page."""
        if not (0 <= slot < self.n_slots):
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free_slots:
            raise ValueError(f"double-free of slot {slot}")
        self.tracer.page_free(slot, len(self._slot_pages[slot]))
        for p in self._slot_pages[slot]:
            self._release(p)
        self._slot_pages[slot] = []
        self._table[slot] = 0
        with quiet_donation():
            self.page_table = self._set(
                self.page_table, jnp.asarray(slot, jnp.int32),
                jnp.zeros((self.pp,), jnp.int32))
        self._free_slots.append(slot)

    # -------------------------------------------------------------- pages

    def _retain(self, page: int) -> None:
        self.refs[page] += 1

    def _release(self, page: int) -> None:
        self.refs[page] -= 1
        assert self.refs[page] >= 0, f"refcount underflow on page {page}"
        if self.refs[page] == 0:
            self._free_pages.append(page)

    def pages_needed(self, n_positions: int) -> int:
        if not self.layout.has_paged:
            return 0
        return -(-min(n_positions, self.max_len) // self.page_size)

    @property
    def n_usable_pages(self) -> int:
        return self.n_pages - 1

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - 1 - len(self._free_pages)

    def page_stats(self) -> Tuple[int, int]:
        return self.pages_in_use, self.n_usable_pages

    def alloc_pages(self, slot: int, n_positions: int,
                    shared: Sequence[int] = ()) -> None:
        """Install a slot's page-table row: `shared` prefix pages (refcount
        bump — from `prefix_match`, which must be called in the same
        admission, before any eviction can run) followed by fresh private
        pages covering `n_positions`. Under pressure, LRU tree-only pages
        are evicted first; a request the pool still cannot hold raises
        `PoolExhausted` with every refcount restored."""
        need = self.pages_needed(n_positions)
        shared = list(shared)
        assert len(shared) <= need, (len(shared), need)
        for p in shared:
            self._retain(p)     # before eviction: a matched page is pinned
        n_new = need - len(shared)
        if n_new > len(self._free_pages) and self.index is not None:
            free_before = len(self._free_pages)
            self.index.evict(n_new - len(self._free_pages),
                             can_free=lambda p: self.refs[p] == 1,
                             release=self._release)
            freed = len(self._free_pages) - free_before
            if freed:
                self.tracer.page_evict(freed)
        if n_new > len(self._free_pages):
            for p in shared:
                self._release(p)
            raise PoolExhausted(
                f"{n_new} pages needed, {len(self._free_pages)} free "
                f"(of {self.n_usable_pages}); admission must wait")
        fresh = [self._free_pages.pop() for _ in range(n_new)]
        for p in fresh:
            self.refs[p] = 1
        self.tracer.page_alloc(slot, len(shared), n_new)
        pages = shared + fresh
        self._slot_pages[slot] = pages
        row = np.zeros((self.pp,), np.int32)
        row[:len(pages)] = pages
        self._table[slot] = row
        with quiet_donation():
            self.page_table = self._set(self.page_table,
                                        jnp.asarray(slot, jnp.int32),
                                        jnp.asarray(row))

    # ------------------------------------------------------------- prefix

    def prefix_match(self, tokens) -> Tuple[int, List[int], bool]:
        """(matched token count, shared page ids, conversation hit) for the
        longest cached page-aligned prefix — capped at len(tokens) - 1 so
        the suffix prefill always has at least one token to produce logits
        from. The third element is True when the match reached pages
        published at a request FINISH (whole-conversation reuse)."""
        if self.index is None:
            return 0, [], False
        pages, conversation = self.index.match(tokens)
        cap = max(0, (len(tokens) - 1) // self.page_size)
        pages = pages[:cap]
        return len(pages) * self.page_size, pages, conversation and \
            bool(pages)

    def prefix_insert(self, tokens, slot: int) -> int:
        """Publish the slot's FULL prompt pages (never the partial tail —
        it will receive this request's generated tokens) into the tree."""
        if self.index is None:
            return 0
        n_full = len(tokens) // self.page_size
        return self.index.insert(tokens, self._slot_pages[slot][:n_full],
                                 retain=self._retain)

    def conversation_insert(self, tokens, slot: int) -> int:
        """Publish a FINISHED request's whole conversation (prompt +
        generated tokens) so a follow-up turn skips prefill over all of it.

        Only pages with complete KV coverage publish: decode never writes
        KV for the final emitted token (it is sampled, not fed back), so
        valid KV ends at len(tokens) - 2 and the publishable page count is
        (len(tokens) - 1) // page_size. Rolled-back speculative writes and
        post-finish garbage all land at positions >= len(tokens) - 1 —
        strictly past every published page."""
        if self.index is None:
            return 0
        n_full = max(0, (len(tokens) - 1) // self.page_size)
        return self.index.insert(tokens, self._slot_pages[slot][:n_full],
                                 retain=self._retain, generated=True)

    # ------------------------------------------------------------ install

    def write_slot(self, slot: int, single: Dict) -> None:
        """Scatter a prefilled batch-1 cache view into the slot's pages
        (and its resident rows). Shared prefix pages receive back the
        values they themselves supplied to the view — a value-level no-op."""
        with quiet_donation():
            self.store = self._write(self.store, single, self.page_table,
                                     jnp.asarray(slot, jnp.int32))

    # ------------------------------------------------------ introspection

    def gather_bytes_per_dispatch(self) -> int:
        """Bytes a legacy gather+scatter dispatch would have materialized —
        what the native page-table-reading decode avoids, per dispatch.
        Static in the layout (host-computed, no device sync)."""
        if not self.layout.has_paged:
            return 0
        return 2 * self.layout.slab_view_bytes()

    def bytes(self) -> int:
        return sum(l.size * l.dtype.itemsize for l in self.store) \
            + self.page_table.size * self.page_table.dtype.itemsize

    def describe(self) -> Dict[str, Any]:
        return {
            "page_size": self.page_size,
            "pages_per_slot": self.pp,
            "n_pages": self.n_pages,
            "usable_pages": self.n_usable_pages,
            "pages_in_use": self.pages_in_use,
            "prefix_cache": self.index is not None,
            "prefix_nodes": self.index.n_nodes if self.index else 0,
            "bytes": self.bytes(),
            "paged_leaves": sum(s.paged for s in self.specs_list()),
            "resident_leaves": sum(not s.paged for s in self.specs_list()),
        }

    def specs_list(self) -> List[_LeafSpec]:
        return self.layout.specs
