"""train_step / serve_step builders (pjit-ready, microbatched, remat-aware).

The steps are pure functions over (state, batch) suitable for jax.jit with
in/out shardings from distributed.sharding. Gradient accumulation splits the
per-step batch into `grad_accum` microbatches consumed by a lax.scan — the
standard trick that bounds saved-activation memory for the 340B config.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.distributed.sharding import batch_pspec
from repro.instrument import scope
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim import adamw as O


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def make_loss_fn(cfg: T.ModelConfig, backend: str = "ref"):
    def loss_fn(params, batch):
        enc_out = None
        if cfg.enc_dec:
            enc_out = T.encode(params, batch["frames"], cfg, backend=backend)
        logits, aux, _ = T.forward(
            params, batch["tokens"], cfg, backend=backend,
            img_embeds=batch.get("img_embeds"), enc_out=enc_out)
        if cfg.n_img_tokens:
            logits = logits[:, cfg.n_img_tokens:]
        loss = T.lm_loss(logits, batch["labels"])
        return loss + aux.astype(jnp.float32), loss
    return loss_fn


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def make_train_step(cfg: T.ModelConfig, opt_cfg: O.OptimizerConfig,
                    *, grad_accum: int = 1, backend: str = "ref",
                    compress_fn: Optional[Callable] = None,
                    accum_dtype=jnp.float32):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {'params', 'opt', 'step'}; batch = {'tokens','labels',...}.
    compress_fn: optional gradient-compression hook
    (distributed.compression) applied to accumulated grads; it receives and
    returns (grads, compression_state) and state rides in `state['comp']`.
    accum_dtype: gradient-accumulation buffer dtype. f32 default; bf16
    halves the largest training temp (the grad tree) — used by the 340B
    dry-run policy, a standard memory/precision trade at that scale.
    """
    loss_fn = make_loss_fn(cfg, backend)
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    accum_dtype = jnp.dtype(accum_dtype)

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            (total, loss), grads = vg(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % grad_accum == 0, (b, grad_accum)
                mb = b // grad_accum
                # reshape (mb, ga) THEN swap: a split dim's sharding lands on
                # the major-most factor, and it must stay on the batch-row dim
                # (axis 1 after the swap), not on the microbatch index — else
                # every scan iteration gathers the full global batch.
                x = x.reshape(mb, grad_accum, *x.shape[1:]).swapaxes(0, 1)
                return L.shard(x, None, "batch", *([None] * (x.ndim - 2)))

            micro = jax.tree_util.tree_map(split, batch)
            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)

            def body(carry, mb):
                g_acc, l_acc = carry
                (tot, l), g = vg(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(accum_dtype), g_acc, g)
                return (g_acc, l_acc + l), None

            (grads, loss_sum), _ = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            loss = loss_sum / grad_accum

        new_comp = state.get("comp")
        if compress_fn is not None:
            grads, new_comp = compress_fn(grads, state.get("comp"))

        new_p, new_opt, gn = O.adamw_update(grads, state["opt"], params,
                                            opt_cfg)
        new_state = {"params": new_p, "opt": new_opt,
                     "step": state["step"] + 1}
        if new_comp is not None:
            new_state["comp"] = new_comp
        metrics = {"loss": loss, "grad_norm": gn,
                   "lr": O.warmup_cosine(opt_cfg, new_opt["count"])}
        return new_state, metrics

    return train_step


def init_train_state(key, cfg: T.ModelConfig, opt_cfg: O.OptimizerConfig):
    params = T.init(key, cfg)
    return {"params": params, "opt": O.adamw_init(params, opt_cfg),
            "step": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: T.ModelConfig, backend: str = "ref",
                      last_only: bool = True, *,
                      cache_len: Optional[int] = None,
                      cache_dtype=jnp.float32):
    """prefill(params, batch[, caches]) -> (next_token_logits, caches).

    last_only=False returns the full (B, S, vocab) logits — the serve engine
    right-pads prompts into compile-shape buckets and reads the logits column
    at the true prompt end, so it needs every position.

    cache_len: when set, the step allocates its own batch-1 cache tree of
    this length INSIDE the compiled function (zeros materialize directly on
    device) and the `caches` operand disappears — the donation-friendly form
    the serving engine uses: no host-side template is copied in per
    admission, and the returned cache buffers can be donated straight into
    the slab write (CachePool.write_slot).
    """
    # remat exists to trade recompute for backward-pass memory; inference has
    # no backward pass, and the checkpoint wrapper's conditional-update
    # plumbing forced whole-cache-stack f32 convert/select churn per layer
    # (~3.5 TB/step on nemotron decode). Always off for serving.
    cfg = dataclasses.replace(cfg, remat=False)

    def body(params, batch, caches):
        enc_out = None
        if cfg.enc_dec:
            enc_out = T.encode(params, batch["frames"], cfg, backend=backend)
        logits, _, caches = T.forward(
            params, batch["tokens"], cfg, backend=backend, caches=caches,
            img_embeds=batch.get("img_embeds"), enc_out=enc_out,
            last_only=last_only)
        return logits, caches

    if cache_len is None:
        def prefill(params, batch, caches):
            return body(params, batch, caches)
    else:
        def prefill(params, batch):
            with scope("kv_write"):
                caches = T.make_caches(cfg, 1, cache_len, cache_dtype)
            return body(params, batch, caches)
    return prefill


def make_decode_step(cfg: T.ModelConfig, backend: str = "ref", *,
                     n_steps: Optional[int] = None,
                     pages_meta: Optional[Dict[str, int]] = None,
                     ledger=None):
    """Compiled slab decode. Two forms:

    n_steps=None (legacy, lock-step launch path):
        decode(params, caches, token, index) -> (logits, caches)
    token: (B, 1) int32; index: scalar int32 count of tokens already cached
    (lock-step batch), or an int32 (B,) vector of PER-SLOT counts — the
    continuous-batching slab decode, where each cache row advances on its
    own clock (serve.engine). One compiled step serves both regimes; the
    vector form gathers/scatters per-slot cache offsets (models.attention).

    n_steps=K (device-resident loop, serve.engine):
        decode(params, caches, state) -> (tok_block, caches, state)
    runs K micro-steps in ONE dispatch via `lax.scan`, with sampling fused on
    device (T.sample_tokens — per-slot temperature, threaded jax.random key)
    and per-slot EOS / length masking, so only the (K, B) int32 `tok_block`
    ever crosses to the host. `state` is the device-resident per-slot loop
    state (see `make_decode_state`); callers donate both `caches` and
    `state`, so the KV slab updates in place instead of being copied per
    token. The rng key is split once per MICRO-step (not per dispatch),
    which makes sampled sequences identical for any K grouping of the same
    steps. Slots that finish mid-block (EOS or length) freeze their token /
    index / rng-free state; the host catches up from the synced block and
    frees them retroactively.

    pages_meta={'size': page_size, 'len': cache_len} (n_steps form only)
    builds the NATIVE PAGED variant: the returned fn takes an extra
    `page_table` operand after `caches` —
        decode(params, caches, page_table, state)
            -> (tok_block, caches, page_table, state)
    — and every forward threads pages={'table', 'size', 'len'} so the
    attention layers read/write the page-major cache leaves through the
    table (models.attention). The table is loop-invariant inside the
    dispatch (admission updates it between dispatches) and passes through
    so it stays aliased to its donated buffer.

    ledger=serve.ledger.LedgerConfig (n_steps form only) appends the
    ineffectual-work ledger as a trailing DONATED operand and return: every
    micro-step's forward runs with a LedgerProbe, the per-layer probe
    matrix accumulates in the scan carry, and the cumulative
    (n_layers, width) f32 buffer comes back for the engine to drain inside
    the dispatch's one existing host sync —
        decode(params, caches, state, ledger) ->
            (tok_block, caches, state, ledger)
    (paged form: the ledger operand stays last, after `state`).
    """
    cfg = dataclasses.replace(cfg, remat=False)   # see make_prefill_step

    if n_steps is None:
        if pages_meta is not None:
            raise ValueError("pages_meta requires the n_steps form")
        if ledger is not None:
            raise ValueError("ledger requires the n_steps form")
        def decode(params, caches, token, index):
            logits, _, caches = T.forward(
                params, token, cfg, backend=backend, caches=caches,
                index=index)
            return logits, caches
        return decode

    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")

    if ledger is not None:
        from repro.serve.ledger import LedgerProbe   # lazy: no serve<->models cycle

    def decode(params, caches, state, page_table=None, ledger_in=None):
        pages = None if page_table is None else dict(pages_meta,
                                                     table=page_table)

        def micro(carry, _):
            if ledger is not None:
                caches, st, led = carry
                probe = LedgerProbe(ledger)
            else:
                caches, st = carry
                probe = None
            out = T.forward(
                params, st["tokens"][:, None], cfg, backend=backend,
                caches=caches, index=st["index"], pages=pages, probe=probe)
            if ledger is not None:
                logits, _, caches, mat = out
                led = led + mat
            else:
                logits, _, caches = out
            with scope("sample"):
                key, sub = jax.random.split(st["key"])
                tok = T.sample_tokens(logits[:, -1], sub, st["temperature"])
                active = st["active"]
                tok = jnp.where(active, tok, st["tokens"])
                remaining = jnp.where(active, st["remaining"] - 1,
                                      st["remaining"])
                hit_eos = active & (st["eos"] >= 0) & (tok == st["eos"])
                st = {
                    "tokens": tok,
                    "index": jnp.where(active, st["index"] + 1, st["index"]),
                    "key": key,
                    "temperature": st["temperature"],
                    "eos": st["eos"],
                    "remaining": remaining,
                    "active": active & (remaining > 0) & ~hit_eos,
                    "spec_limit": st["spec_limit"],
                }
            carry = (caches, st, led) if ledger is not None else (caches, st)
            return carry, tok

        if ledger is not None:
            with scope("decode_loop"):
                (caches, state, led), tok_block = jax.lax.scan(
                    micro, (caches, state, ledger_in), None, length=n_steps)
            return tok_block, caches, state, led
        with scope("decode_loop"):
            (caches, state), tok_block = jax.lax.scan(
                micro, (caches, state), None, length=n_steps)
        return tok_block, caches, state

    if pages_meta is not None:
        if ledger is not None:
            def paged_decode(params, caches, page_table, state, ledger_in):
                tok_block, caches, state, led = decode(
                    params, caches, state, page_table, ledger_in)
                return tok_block, caches, page_table, state, led
            return paged_decode

        def paged_decode(params, caches, page_table, state):
            tok_block, caches, state = decode(params, caches, state,
                                              page_table)
            return tok_block, caches, page_table, state
        return paged_decode
    if ledger is not None:
        def ledger_decode(params, caches, state, ledger_in):
            return decode(params, caches, state, None, ledger_in)
        return ledger_decode
    return decode


def make_decode_state(n_slots: int, seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Device-resident per-slot loop state for the fused decode step.

    tokens/index: the (B,) feedback loop that never leaves the device;
    temperature/eos/remaining/active: per-slot sampling + lifecycle vectors,
    written only at admission; key: the threaded jax.random key; spec_limit:
    the per-request speculation cap (max draft tokens acceptable per
    dispatch, `Request.speculate`) — 0 opts the slot out of drafting, in
    which case the verify step degenerates to exactly one plain target
    micro-step for that slot.
    """
    return {
        "tokens": jnp.zeros((n_slots,), jnp.int32),
        "index": jnp.zeros((n_slots,), jnp.int32),
        "key": jax.random.PRNGKey(seed),
        "temperature": jnp.zeros((n_slots,), jnp.float32),
        "eos": jnp.full((n_slots,), -1, jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "active": jnp.zeros((n_slots,), bool),
        "spec_limit": jnp.zeros((n_slots,), jnp.int32),
    }


def decode_state_pspecs(mesh, n_slots: int) -> Dict[str, PartitionSpec]:
    """PartitionSpec tree matching `make_decode_state(n_slots)`.

    Every per-slot lifecycle vector is (n_slots,) and shards exactly like
    the slab's leading slot axis (sharding.batch_pspec — replicated when
    n_slots doesn't divide the dp axes, so the donated decode step always
    has a legal placement); the threaded rng key is replicated — each
    micro-step's split must agree on every device."""
    slot_spec = batch_pspec(mesh, n_slots)
    spec = {k: slot_spec for k in ("tokens", "index", "temperature", "eos",
                                   "remaining", "active", "spec_limit")}
    spec["key"] = PartitionSpec(None)
    return spec


def install_slot(state: Dict[str, jnp.ndarray], slot, token, index,
                 temperature, eos, remaining,
                 spec_limit=0) -> Dict[str, jnp.ndarray]:
    """Write one admitted request's row into the device decode state.

    Pure (jit with donated `state` by the engine): slot may be a traced
    int32. eos < 0 means no EOS; remaining <= 0 installs an inactive row
    (request finished at prefill). spec_limit: per-request speculation cap
    (0 = no drafting for this slot; ignored by the plain decode step)."""
    return {
        "tokens": state["tokens"].at[slot].set(token),
        "index": state["index"].at[slot].set(index),
        "key": state["key"],
        "temperature": state["temperature"].at[slot].set(temperature),
        "eos": state["eos"].at[slot].set(eos),
        "remaining": state["remaining"].at[slot].set(remaining),
        "active": state["active"].at[slot].set(remaining > 0),
        "spec_limit": state["spec_limit"].at[slot].set(spec_limit),
    }


# ---------------------------------------------------------------------------
# Paged decode: page-table indirection around the same fused steps
# ---------------------------------------------------------------------------

def make_paged_decode_step(cfg: T.ModelConfig, backend: str = "ref", *,
                           n_steps: int, layout, native: bool = True,
                           ledger=None):
    """Paged form of the device-resident loop (serve.paging):

        decode(params, store, page_table, state)
            -> (tok_block, store, page_table, state)

    `store` is the page-major KV store (flat leaf list), `page_table` the
    (n_slots, pages_per_slot) int32 table — BOTH donated device state, like
    the slab and the loop state today.

    native=True (default): NO gather/scatter. The store leaves pass
    straight into the forward as the cache tree (`layout.as_tree` — the
    page axis sits where the slot axis sat, so the treedef is unchanged)
    and the attention layers read/write them THROUGH the table
    (models.attention paged branches / kernels.ops.paged_attention): new-
    token writes are in-place page-indexed scatters that preserve the
    donated store's buffer aliasing, and no per-dispatch slab view ever
    materializes.

    native=False keeps the legacy wrap for A/B tests: gather each slot's
    pages into exactly the slab layout, run the unchanged fused decode,
    scatter the touched pages back (traces serve.paging.GATHER_EVENTS).
    Both forms are greedy token-identical to the slab — the native ref
    read is the same sliced-view attention program the gather produced.

    The table passes through unchanged (admission and slot release update
    it between dispatches); returning it keeps it aliased to its donated
    buffer so it stays device-resident.

    ledger=LedgerConfig appends the donated ineffectual-work ledger as a
    trailing operand/return on either form (see make_decode_step)."""
    if native:
        meta = {"size": layout.page_size, "len": layout.cache_len}
        inner = make_decode_step(cfg, backend, n_steps=n_steps,
                                 pages_meta=meta, ledger=ledger)

        if ledger is not None:
            def decode(params, store, page_table, state, ledger_in):
                caches = layout.as_tree(store)
                tok_block, caches, page_table, state, led = inner(
                    params, caches, page_table, state, ledger_in)
                return (tok_block, layout.from_tree(caches), page_table,
                        state, led)
            return decode

        def decode(params, store, page_table, state):
            caches = layout.as_tree(store)
            tok_block, caches, page_table, state = inner(
                params, caches, page_table, state)
            return tok_block, layout.from_tree(caches), page_table, state

        return decode

    inner = make_decode_step(cfg, backend, n_steps=n_steps, ledger=ledger)

    if ledger is not None:
        def decode(params, store, page_table, state, ledger_in):
            caches = layout.gather(store, page_table)
            tok_block, caches, state, led = inner(params, caches, state,
                                                  ledger_in)
            return (tok_block, layout.scatter(store, page_table, caches),
                    page_table, state, led)
        return decode

    def decode(params, store, page_table, state):
        caches = layout.gather(store, page_table)
        tok_block, caches, state = inner(params, caches, state)
        return (tok_block, layout.scatter(store, page_table, caches),
                page_table, state)

    return decode


def make_paged_speculative_decode_step(cfg: T.ModelConfig,
                                       draft_cfg: T.ModelConfig,
                                       backend: str = "ref", *,
                                       n_draft: int, layout,
                                       native: bool = True,
                                       ledger=None):
    """Paged form of the fused propose-then-verify cycle:

        spec_decode(params, draft_params, store, page_table, draft_caches,
                    state) -> (commit, n_commit, n_accept, store,
                               page_table, draft_caches, state)

    Only the TARGET store is paged (it is the memory that scales with
    prompts; the draft slab is small by construction and keeps the plain
    slab layout + slot clocks — its forwards never see `pages`). Rollback
    semantics survive paging for free: a rejected suffix is a per-slot
    index rewind that never frees a page, and the speculative write
    headroom lands in the slot's PRIVATE tail pages (prefix sharing only
    ever publishes pages with complete final KV), so a rolled-back write
    can never have touched a shared page.

    native=True: the verify forwards consume the page table directly (same
    contract as make_paged_decode_step) — the K+1-token block write is one
    page-indexed scatter per leaf. native=False keeps the legacy
    gather/scatter wrap for A/B tests.

    ledger=LedgerConfig appends the donated ineffectual-work ledger as a
    trailing operand/return on either form (see
    make_speculative_decode_step)."""
    if native:
        meta = {"size": layout.page_size, "len": layout.cache_len}
        inner = make_speculative_decode_step(cfg, draft_cfg, backend,
                                             n_draft=n_draft,
                                             pages_meta=meta, ledger=ledger)

        if ledger is not None:
            def spec_decode(params, draft_params, store, page_table,
                            draft_caches, state, ledger_in):
                caches = layout.as_tree(store)
                (commit, m, acc, caches, page_table, draft_caches, state,
                 led) = inner(params, draft_params, caches, page_table,
                              draft_caches, state, ledger_in)
                return (commit, m, acc, layout.from_tree(caches),
                        page_table, draft_caches, state, led)
            return spec_decode

        def spec_decode(params, draft_params, store, page_table,
                        draft_caches, state):
            caches = layout.as_tree(store)
            commit, m, acc, caches, page_table, draft_caches, state = inner(
                params, draft_params, caches, page_table, draft_caches,
                state)
            return (commit, m, acc, layout.from_tree(caches), page_table,
                    draft_caches, state)

        return spec_decode

    inner = make_speculative_decode_step(cfg, draft_cfg, backend,
                                         n_draft=n_draft, ledger=ledger)

    if ledger is not None:
        def spec_decode(params, draft_params, store, page_table,
                        draft_caches, state, ledger_in):
            caches = layout.gather(store, page_table)
            commit, m, acc, caches, draft_caches, state, led = inner(
                params, draft_params, caches, draft_caches, state,
                ledger_in)
            return (commit, m, acc,
                    layout.scatter(store, page_table, caches), page_table,
                    draft_caches, state, led)
        return spec_decode

    def spec_decode(params, draft_params, store, page_table, draft_caches,
                    state):
        caches = layout.gather(store, page_table)
        commit, m, acc, caches, draft_caches, state = inner(
            params, draft_params, caches, draft_caches, state)
        return (commit, m, acc, layout.scatter(store, page_table, caches),
                page_table, draft_caches, state)

    return spec_decode


def make_suffix_prefill_step(cfg: T.ModelConfig, backend: str = "ref", *,
                             layout, ledger=None):
    """Prefill ONLY the unmatched suffix of a prompt whose prefix pages are
    already resident (serve.paging prefix reuse):

        prefill(params, batch, store, page_table, slot, index)
            -> ((1, S, vocab) suffix logits, store)

    Gathers the slot's batch-1 view (the shared prefix pages supply
    positions < index), runs the suffix through the DECODE-form forward —
    the same s>1 contiguous block write the speculative verify uses
    (attention._decode_cache_write / mla_apply with a scalar `index`), so
    suffix tokens attend to the cached prefix under the standard validity
    masks — and scatters the view back: fresh suffix pages receive the new
    KV, shared prefix pages receive back the identical values they
    supplied. `index` is the matched prefix length (traced). The engine
    right-pads suffixes into pow2 buckets exactly like full prefills
    (compile O(log max_len) suffix shapes, not one per length — real
    traffic produces arbitrary suffix lengths); the FULL (1, S, vocab)
    logits come back so the caller reads the true suffix-end column, and
    the padded tail's block writes land past the shared region in the
    slot's private pages, masked by the validity clocks until decode
    overwrites them — the same contract as the slab's padded prefill
    tail.

    ledger=serve.ledger.LedgerConfig appends the donated ineffectual-work
    ledger as a trailing operand/return:
        prefill(params, batch, store, page_table, slot, index, ledger)
            -> (logits, store, ledger)."""
    cfg = dataclasses.replace(cfg, remat=False)   # see make_prefill_step

    if ledger is not None:
        from repro.serve.ledger import LedgerProbe   # lazy: no serve<->models cycle

        def prefill(params, batch, store, page_table, slot, index,
                    ledger_in):
            row = jax.lax.dynamic_index_in_dim(page_table, slot, axis=0,
                                               keepdims=False)
            caches = layout.gather_one(store, row, slot)
            probe = LedgerProbe(ledger)
            logits, _, caches, mat = T.forward(
                params, batch["tokens"], cfg, backend=backend,
                caches=caches, index=index, probe=probe)
            return (logits, layout.scatter_one(store, row, slot, caches),
                    ledger_in + mat)
        return prefill

    def prefill(params, batch, store, page_table, slot, index):
        row = jax.lax.dynamic_index_in_dim(page_table, slot, axis=0,
                                           keepdims=False)
        caches = layout.gather_one(store, row, slot)
        logits, _, caches = T.forward(
            params, batch["tokens"], cfg, backend=backend, caches=caches,
            index=index)
        return logits, layout.scatter_one(store, row, slot, caches)

    return prefill


def page_table_pspec(mesh, n_slots: int) -> PartitionSpec:
    """(n_slots, pages_per_slot) table: slot axis sharded like the slab's
    slot axis / the decode-state vectors, page entries replicated."""
    return PartitionSpec(*(tuple(batch_pspec(mesh, n_slots)) + (None,)))


# ---------------------------------------------------------------------------
# Speculative decode: fused propose-then-verify (serve.speculative)
# ---------------------------------------------------------------------------

def recurrent_cache_paths(caches) -> list:
    """Flat-leaf indices of NON-POSITIONAL cache leaves + their batch axis.

    Attention/MLA caches are positional — every write lands at a per-slot
    sequence offset, so rolling back rejected speculative tokens is a free
    index rewind (stale positions are masked, then overwritten). SSM leaves
    ('conv' tail, 'ssm' state — models.ssm.make_mamba_cache) are RECURRENT:
    the state after K tokens cannot be rewound, so the speculative step
    snapshots them per micro-step and gathers the per-slot accepted state
    back (see make_speculative_decode_step). Returns [(flat_index,
    batch_axis)] in jax tree-flatten order; batch_axis is 1 for
    layer-stacked 'blocks' leaves, 0 for 'prelude' leaves.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(caches)
    out = []
    for i, (path, _leaf) in enumerate(flat):
        names = [str(k.key) for k in path if hasattr(k, "key")]
        if "conv" in names or "ssm" in names:
            out.append((i, 1 if names and names[0] == "blocks" else 0))
    return out


def _snapshot(caches, paths):
    leaves = jax.tree_util.tree_flatten(caches)[0]
    return [leaves[i] for i, _ in paths]


def _gather_step(stacked, g, batch_axis):
    """stacked: (T, *leaf); g: (B,) int32 step index per batch row. Exact
    one-hot select along T (where + sum — one term per element, no fp
    blending) with the batch axis at `batch_axis` of the leaf."""
    t = stacked.shape[0]
    steps = jnp.arange(t).reshape((t,) + (1,) * (stacked.ndim - 1))
    gshape = [1] * stacked.ndim
    gshape[batch_axis + 1] = g.shape[0]
    mask = steps == g.reshape(gshape)
    return jnp.where(mask, stacked, 0).sum(axis=0).astype(stacked.dtype)


def _restore(caches, paths, init_leaves, step_stacks, g):
    """Replace recurrent leaves with the per-slot state at step g[b]
    (g = 0 selects the pre-dispatch state prepended from init_leaves)."""
    leaves, treedef = jax.tree_util.tree_flatten(caches)
    for (i, bax), init, snap in zip(paths, init_leaves, step_stacks):
        stacked = jnp.concatenate([init[None], snap], axis=0)
        leaves[i] = _gather_step(stacked, g, bax)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_speculative_decode_step(cfg: T.ModelConfig,
                                 draft_cfg: T.ModelConfig,
                                 backend: str = "ref", *, n_draft: int,
                                 pages_meta: Optional[Dict[str, int]] = None,
                                 ledger=None):
    """Fused propose-then-verify decode (serve.speculative):

        spec_decode(params, draft_params, caches, draft_caches, state)
            -> (commit (B, K+1), n_commit (B,), n_accept (B,),
                caches, draft_caches, state)

    pages_meta={'size', 'len'} builds the NATIVE PAGED variant (an extra
    `page_table` operand after `caches`, threaded into the TARGET forwards
    as the `pages` operand and passed through the return — see
    make_decode_step; the draft keeps its slab).

    ledger=serve.ledger.LedgerConfig appends the donated ineffectual-work
    ledger as a trailing operand/return (see make_decode_step). Only the
    TARGET verify forwards are probed — the draft's cost is accounted
    analytically (registry.draft_cost_fraction), so probing it would
    double-count work the roofline already attributes.

    ONE dispatch per cycle, everything on device:

      1. DRAFT: the cheap artifact runs K+1 micro-steps under one lax.scan
         (K proposals d_1..d_K, plus one trailing feed of d_K so the draft
         slab/state covers the fully-accepted case), sampling with the
         per-slot temperature vector and the threaded rng key.
      2. VERIFY: the target scores the whole block [t0, d_1..d_K] — ONE
         batched (B, K+1) forward with per-slot index clocks for
         positional-cache archs; for recurrent archs (SSM/hybrid, whose
         single-step recurrence cannot consume a block) a K+1-step scan of
         single-token forwards that snapshots the recurrent leaves per step.
      3. ACCEPT/REJECT per slot: greedy rows accept the longest prefix where
         the draft token equals the target argmax; temperature>0 rows use
         the standard rejection-sampling test (u < p/q) and, at the first
         rejection, sample the correction from the residual (p - q)+ — the
         committed stream is distributed exactly as the target. The run is
         clamped by the per-slot `spec_limit` (a 0 row degenerates to one
         plain target step). One bonus token from the target's column L
         always commits, so every cycle advances every live slot by
         1..K+1 tokens.
      4. ROLLBACK: rejected suffixes cost a per-slot index rewind —
         positional cache writes past the new clock are masked and later
         overwritten in place (the engine pads the slab by K positions so
         the deepest speculative write stays in bounds); recurrent leaves
         gather the per-slot state at the accepted boundary from the
         step-stacked snapshots (frozen slots gather their pre-dispatch
         state). EOS / length budgets truncate the commit on device, same
         contract as the plain multi-step loop.

    Greedy speculative output is token-identical to plain greedy decode:
    every committed draft token equals the target argmax on the committed
    prefix, and the bonus IS the target argmax — the accepted stream is the
    target's greedy stream by induction, for any draft and any K.
    """
    cfg = dataclasses.replace(cfg, remat=False)        # see make_prefill_step
    draft_cfg = dataclasses.replace(draft_cfg, remat=False)
    if n_draft < 1:
        raise ValueError(f"n_draft must be >= 1, got {n_draft}")
    k = n_draft
    recurrent = bool(cfg.is_ssm or cfg.attn_period)

    if ledger is not None:
        from repro.serve.ledger import LedgerProbe   # lazy: no serve<->models cycle

    def spec_decode(params, draft_params, caches, draft_caches, state,
                    page_table=None, ledger_in=None):
        pages = None if page_table is None else dict(pages_meta,
                                                     table=page_table)
        b = state["tokens"].shape[0]
        active = state["active"]
        idx0 = state["index"]
        temp = state["temperature"]

        # ---- 1. draft proposes (K+1 fused micro-steps) --------------------
        d_paths = recurrent_cache_paths(draft_caches)
        d_init = _snapshot(draft_caches, d_paths)

        def draft_micro(carry, _):
            dcaches, tok, idx, key = carry
            logits, _, dcaches = T.forward(
                draft_params, tok[:, None], draft_cfg, backend=backend,
                caches=dcaches, index=idx)
            key, sub = jax.random.split(key)
            nxt = T.sample_tokens(logits[:, -1], sub, temp)
            nxt = jnp.where(active, nxt, tok)
            idx = jnp.where(active, idx + 1, idx)
            return ((dcaches, nxt, idx, key),
                    (nxt, logits[:, -1], _snapshot(dcaches, d_paths)))

        (draft_caches, _, _, key), (props, dlogits, d_snaps) = jax.lax.scan(
            draft_micro, (draft_caches, state["tokens"], idx0, state["key"]),
            None, length=k + 1)
        d_block = props[:k].T                           # (B, K): d_1..d_K
        dlog = dlogits[:k].transpose(1, 0, 2)           # (B, K, vocab)

        # ---- 2. target verifies the block --------------------------------
        tok_in = jnp.concatenate([state["tokens"][:, None], d_block], axis=1)
        t_paths = recurrent_cache_paths(caches)
        t_init = _snapshot(caches, t_paths)
        led = ledger_in
        if not recurrent:
            if ledger is not None:
                probe = LedgerProbe(ledger)
                logits, _, caches, mat = T.forward(
                    params, tok_in, cfg, backend=backend, caches=caches,
                    index=idx0, pages=pages, probe=probe)
                led = led + mat
            else:
                logits, _, caches = T.forward(
                    params, tok_in, cfg, backend=backend, caches=caches,
                    index=idx0, pages=pages)
            z = logits                                  # (B, K+1, vocab)
            t_snaps = []
        else:
            def verify_micro(carry, xs):
                vcaches, vled = carry
                tok_j, j = xs
                idx_j = jnp.where(active, idx0 + j, idx0)
                if ledger is not None:
                    probe = LedgerProbe(ledger)
                    lg, _, vcaches, mat = T.forward(
                        params, tok_j[:, None], cfg, backend=backend,
                        caches=vcaches, index=idx_j, pages=pages,
                        probe=probe)
                    vled = vled + mat
                else:
                    lg, _, vcaches = T.forward(
                        params, tok_j[:, None], cfg, backend=backend,
                        caches=vcaches, index=idx_j, pages=pages)
                return ((vcaches, vled),
                        (lg[:, -1], _snapshot(vcaches, t_paths)))

            (caches, led), (zs, t_snaps) = jax.lax.scan(
                verify_micro, (caches, led),
                (tok_in.T, jnp.arange(k + 1, dtype=jnp.int32)))
            z = zs.transpose(1, 0, 2)

        # ---- 3. per-slot accept/reject ------------------------------------
        greedy = temp <= 0.0
        tgt_next = jnp.argmax(z, axis=-1).astype(jnp.int32)   # (B, K+1)
        match = d_block == tgt_next[:, :k]
        key, k_acc, k_bonus = jax.random.split(key, 3)
        safe_t = jnp.maximum(temp, 1e-6)[:, None, None]
        logp = jax.nn.log_softmax(z[:, :k].astype(jnp.float32) / safe_t, -1)
        logq = jax.nn.log_softmax(dlog.astype(jnp.float32) / safe_t, -1)
        p_d = jnp.take_along_axis(logp, d_block[..., None], axis=-1)[..., 0]
        q_d = jnp.take_along_axis(logq, d_block[..., None], axis=-1)[..., 0]
        u = jax.random.uniform(k_acc, (b, k), minval=1e-20)
        accept = jnp.where(greedy[:, None], match, jnp.log(u) < p_d - q_d)
        run = jnp.cumprod(accept.astype(jnp.int32), axis=1)
        l_run = jnp.sum(run, axis=1)
        l_acc = jnp.minimum(l_run, state["spec_limit"])       # per-slot cap

        # bonus token from the target's column L: greedy argmax; sampled
        # rows draw from the residual (p - q)+ at a TRUE rejection column,
        # from p itself when the run was clamped or fully accepted.
        z_l = jax.vmap(lambda zb, lb: zb[lb])(z, l_acc)       # (B, vocab)
        bonus_g = jnp.argmax(z_l, axis=-1).astype(jnp.int32)
        dlog_pad = jnp.concatenate([dlog, jnp.zeros_like(dlog[:, :1])], 1)
        q_l = jax.vmap(lambda qb, lb: qb[lb])(dlog_pad, l_acc)
        logp_l = jax.nn.log_softmax(z_l.astype(jnp.float32)
                                    / safe_t[:, 0], -1)
        logq_l = jax.nn.log_softmax(q_l.astype(jnp.float32)
                                    / safe_t[:, 0], -1)
        resid = jnp.log(jnp.clip(jnp.exp(logp_l) - jnp.exp(logq_l),
                                 1e-30, None))
        # the correction conditions on "an ELIGIBLE draft token was
        # rejected": at a spec_limit-clamped column the draft token could
        # never commit regardless of the accept test, so the bonus must be
        # a plain draw from p (a capped/opted-out slot is exactly one plain
        # target step), not the residual.
        use_resid = (l_acc == l_run) & (l_run < k) \
            & (l_run < state["spec_limit"])
        t_logits = jnp.where(use_resid[:, None], resid, logp_l)
        gum = jax.random.gumbel(k_bonus, z_l.shape, jnp.float32)
        bonus_t = jnp.argmax(t_logits + gum, axis=-1).astype(jnp.int32)
        bonus = jnp.where(greedy, bonus_g, bonus_t)

        # ---- 4. commit + on-device lifecycle ------------------------------
        ar = jnp.arange(k + 1)[None]
        d_pad = jnp.concatenate([d_block, jnp.zeros((b, 1), jnp.int32)], 1)
        commit = jnp.where(ar < l_acc[:, None], d_pad, 0)
        commit = jnp.where(ar == l_acc[:, None], bonus[:, None], commit)
        m_full = l_acc + 1
        is_eos = ((state["eos"][:, None] >= 0)
                  & (commit == state["eos"][:, None])
                  & (ar < m_full[:, None]))
        first_eos = jnp.min(jnp.where(is_eos, ar, k + 1), axis=1)
        m = jnp.minimum(m_full,
                        jnp.minimum(first_eos + 1, state["remaining"]))
        m = jnp.where(active, m, 0)
        hit_eos = (first_eos + 1) <= m
        remaining = state["remaining"] - m
        last = jax.vmap(lambda cb, mb: cb[jnp.maximum(mb - 1, 0)])(commit, m)
        new_state = {
            "tokens": jnp.where(active, last, state["tokens"]),
            "index": idx0 + m,               # the rollback: rewind the clock
            "key": key,
            "temperature": temp,
            "eos": state["eos"],
            "remaining": remaining,
            "active": active & (remaining > 0) & ~hit_eos,
            "spec_limit": state["spec_limit"],
        }
        # accepted = draft tokens actually COMMITTED: EOS/budget truncation
        # takes the first m commit columns, of which min(l_acc, m) are
        # drafts (the bonus commits only when m == l_acc + 1) — accepted-
        # then-truncated positions are rewound, so they must not count.
        n_accept = jnp.where(active, jnp.minimum(l_acc, m), 0)

        # ---- 5. recurrent-state rollback ----------------------------------
        if recurrent:
            # committed state = after consuming [t0, d_1..d_L] = micro-step
            # L+1 (0 = the prepended pre-dispatch state, which frozen slots
            # keep). Identical step indexing for draft and target: both fed
            # the same committed prefix.
            g = jnp.where(active, l_acc + 1, 0)
            caches = _restore(caches, t_paths, t_init, t_snaps, g)
            draft_caches = _restore(draft_caches, d_paths, d_init, d_snaps, g)

        if ledger is not None:
            return commit, m, n_accept, caches, draft_caches, new_state, led
        return commit, m, n_accept, caches, draft_caches, new_state

    if pages_meta is not None:
        if ledger is not None:
            def paged_spec_decode(params, draft_params, caches, page_table,
                                  draft_caches, state, ledger_in):
                (commit, m, acc, caches, draft_caches, state,
                 led) = spec_decode(params, draft_params, caches,
                                    draft_caches, state, page_table,
                                    ledger_in)
                return (commit, m, acc, caches, page_table, draft_caches,
                        state, led)
            return paged_spec_decode

        def paged_spec_decode(params, draft_params, caches, page_table,
                              draft_caches, state):
            commit, m, acc, caches, draft_caches, state = spec_decode(
                params, draft_params, caches, draft_caches, state,
                page_table)
            return (commit, m, acc, caches, page_table, draft_caches,
                    state)
        return paged_spec_decode
    if ledger is not None:
        def ledger_spec_decode(params, draft_params, caches, draft_caches,
                               state, ledger_in):
            return spec_decode(params, draft_params, caches, draft_caches,
                               state, None, ledger_in)
        return ledger_spec_decode
    return spec_decode
