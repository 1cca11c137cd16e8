"""Attention variants for the assigned architectures:

  * MHA / GQA (grouped KV heads)                     — all LM archs
  * sliding-window attention w/ circular KV cache    — h2o-danube, mistral (llava), gemma2 local
  * local+global alternation                          — gemma2 (via per-layer window)
  * logit soft-capping                                — gemma2
  * MLA (multi-head latent attention, compressed KV) — minicpm3, deepseek-v2-lite
  * cross-attention                                   — whisper decoder

All projections are Kratos-able. Caches:
  full window:    k/v[(B, KV, S_max, dh)] written at `index`
  sliding window: circular buffer of size W (slot = pos % W) — the cache is
                  O(W) regardless of context length, which is what makes the
                  long_500k cell feasible for SWA archs
  MLA:            compressed c_kv (B, S, r) + shared rotary key (B, S, dr):
                  O(S * (r + dr)) instead of O(S * 2 * H * dh)

Paged serving (serve.paging): the block-paged KV pool stores full-window and
MLA caches page-major behind per-slot page tables. Decode consumes the
table NATIVELY here: when the forward threads a `pages` operand
({'table': (B, pp) int32, 'size': page_size, 'len': cache_len}), the decode
branches below write new K/V with in-place page-indexed scatters
(position p lands in page table[b, p // P] at offset p % P) and read
through the table — the Pallas kernel path (kernels.ops.paged_attention)
streams pages via its BlockSpec index map; the XLA/ref path takes a sliced
contiguous view that is bit-identical to the slab rows on every valid
position, so greedy decode is token-identical to the slab. The per-slot
positional validity masks are what keep unallocated table tail entries
(the shared garbage sink page) inert, the same way they keep the slab's
unwritten tail inert. Without `pages` the slab layout contract above holds
unchanged (train / prefill / suffix-prefill slot views).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kratos as kr
from repro.instrument import scope, scoped
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    window: Optional[int] = None
    softcap: Optional[float] = None
    qk_norm: bool = False
    attn_scale: Optional[float] = None   # override 1/sqrt(dh) (gemma2)
    # MLA
    mla: bool = False
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # cross-attention (whisper decoder)
    cross: bool = False

    @property
    def q_head_dim(self) -> int:
        return (self.qk_nope_dim + self.qk_rope_dim) if self.mla else self.head_dim

    @property
    def v_dim(self) -> int:
        return self.v_head_dim if self.mla else self.head_dim

    @property
    def scale(self) -> float:
        return self.attn_scale if self.attn_scale is not None \
            else self.q_head_dim ** -0.5


# ---------------------------------------------------------------------------
# Core masked attention (positions-aware; handles circular caches)
# ---------------------------------------------------------------------------

@scoped("attn_core")
def attention_positional(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
                         softcap=None, scale=None, extra_mask=None):
    """q: (B,H,Sq,Dk); k: (B,KV,Skv,Dk); v: (B,KV,Skv,Dv); GQA via reshape.

    q_pos: (Sq,) int32 absolute positions; kv_pos: (Skv,) possibly non-monotonic
    (circular cache); extra_mask: (Skv,) bool validity.

    Continuous-batching decode passes PER-SLOT positions: any of q_pos /
    kv_pos / extra_mask may carry a leading batch axis ((B,Sq) / (B,Skv)),
    in which case the causal/window/validity mask is computed per batch row —
    each request in the slab attends under its own sequence clock.
    """
    b, h, sq, dk = q.shape
    kv, skv = k.shape[1], k.shape[2]
    scale = (dk ** -0.5) if scale is None else scale
    if kv != h:
        # broadcast k/v to full heads BEFORE the einsum: a (kv, g) split of
        # the head dim cannot shard when kv < mesh 'model' size (kv=8 heads
        # on a 16-way axis replicated a 6 GiB score tensor); the broadcast
        # keeps the head axis intact, which shards cleanly.
        g = h // kv
        k = jnp.broadcast_to(k[:, :, None], (b, kv, g, skv, dk)) \
            .reshape(b, h, skv, dk)
        v = jnp.broadcast_to(v[:, :, None], (b, kv, g, skv, v.shape[-1])) \
            .reshape(b, h, skv, v.shape[-1])
    # accumulate in kref dot-accum dtype: with f32-preferred, XLA:CPU hoists
    # a bf16->f32 convert of the (9 GiB, stacked) KV cache INSIDE the layer
    # loop (x2 per layer = TBs of churn); bf16 matches TPU MXU semantics
    # (bf16 operands stream from HBM, accumulate on-core). Softmax math is
    # still f32 (the small score tensor is upcast right after).
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=kref._DOT_ACCUM)
    s = s.astype(jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.ones((sq, skv), bool)
    qp = q_pos[..., :, None]                  # (Sq,1) or (B,Sq,1)
    kp = kv_pos[..., None, :]                 # (1,Skv) or (B,1,Skv)
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & (kp > qp - window)
    if extra_mask is not None:
        mask = mask & extra_mask[..., None, :]
    # (Sq,Skv) -> (1,1,Sq,Skv); per-slot (B,Sq,Skv) -> (B,1,Sq,Skv)
    mask = mask[None, None] if mask.ndim == 2 else mask[:, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return o


# Above this many query positions the XLA path streams over q-chunks instead
# of materializing the full (Sq, Skv) score matrix (32k+ prefill would need
# O(S^2) f32 scores = TBs; chunking bounds live memory to chunk x Skv).
CHUNKED_ATTN_THRESHOLD = 4096
CHUNK_Q = 1024


@scoped("attn_core")
def attention_chunked(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
                      softcap=None, scale=None, extra_mask=None,
                      chunk: int = CHUNK_Q):
    """Flash-style streaming attention in pure jnp (XLA path).

    Identical math to attention_positional but lax.map'd over q chunks, so
    peak live memory is (B, H, chunk, Skv) instead of (B, H, Sq, Skv). Exact
    softmax per chunk row (the full k/v is visible to every chunk).
    """
    b, h, sq, dk = q.shape
    chunk = min(chunk, sq)
    nc, rem = sq // chunk, sq % chunk
    body = sq - rem

    def one(args):
        qi, pi = args
        return attention_positional(qi, k, v, pi, kv_pos, causal=causal,
                                    window=window, softcap=softcap,
                                    scale=scale, extra_mask=extra_mask)

    qc = q[:, :, :body].reshape(b, h, nc, chunk, dk).transpose(2, 0, 1, 3, 4)
    pc = q_pos[:body].reshape(nc, chunk)
    oc = jax.lax.map(one, (qc, pc))                   # (nc, B, H, chunk, dv)
    out = oc.transpose(1, 2, 0, 3, 4).reshape(b, h, body, v.shape[-1])
    if rem:                                           # non-divisible tail
        tail = one((q[:, :, body:], q_pos[body:]))
        out = jnp.concatenate([out, tail], axis=2)
    return out


@scoped("attn_core")
def _sdpa(q, k, v, cfg: AttnConfig, *, q_pos, kv_pos, extra_mask=None,
          backend="ref", contiguous=False, q_offset=0):
    """Dispatch: flash kernel for contiguous full-seq, positional math otherwise."""
    if (backend in ("pallas", "interpret") and contiguous
            and q.shape[-1] == v.shape[-1]):
        return ops.flash_attention(
            q, k, v, causal=cfg.causal, window=cfg.window, softcap=cfg.softcap,
            q_offset=q_offset, scale=cfg.scale, backend=backend)
    if q.shape[2] > CHUNKED_ATTN_THRESHOLD:
        return attention_chunked(
            q, k, v, q_pos, kv_pos, causal=cfg.causal, window=cfg.window,
            softcap=cfg.softcap, extra_mask=extra_mask, scale=cfg.scale)
    return attention_positional(
        q, k, v, q_pos, kv_pos, causal=cfg.causal, window=cfg.window,
        softcap=cfg.softcap, extra_mask=extra_mask, scale=cfg.scale)


# ---------------------------------------------------------------------------
# Standard (GQA) attention
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: AttnConfig, spec: kr.KratosSpec = kr.DENSE,
             dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 4)
    h, kv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": kr.init(ks[0], d, h * dh, spec, dtype),
        "wk": kr.init(ks[1], d, kv * dh, spec, dtype),
        "wv": kr.init(ks[2], d, kv * dh, spec, dtype),
        "wo": kr.init(ks[3], h * dh, d, spec, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(dh, dtype)
        p["k_norm"] = L.rmsnorm_init(dh, dtype)
    return p


@scoped("rope")
def _positions_for(index, s: int) -> jnp.ndarray:
    """Absolute positions for a length-s segment starting at `index`.

    index: None (from 0) | scalar (shared decode clock) | (B,) per-slot
    clocks (continuous batching). The vector form is also a `lax.scan` carry
    in the multi-step device-resident decode (distributed.steps), so it must
    stay int32 — a weak-typed python int carry would change dtype across scan
    iterations. Returns (S,) or (B, S) int32."""
    if index is None:
        return jnp.arange(s)
    index = jnp.asarray(index, jnp.int32)
    if index.ndim == 0:
        return index + jnp.arange(s)
    return index[:, None] + jnp.arange(s)[None, :]


def _paged_leaf_view(leaf, table, cache_len: int):
    """Contiguous (B, ..., cache_len, d) view of a page-major cache leaf.

    leaf: (n_pages, ..., P, d); table: (B, pp) int32. The gathered view is
    value-identical to the slab rows on every position the validity masks
    admit (sink-page rows sit past the per-slot clocks), and slicing to
    `cache_len` makes the downstream attention math compile to exactly the
    slab program — the basis of paged/slab token-identity on the ref path.
    """
    g = leaf[table]                            # (B, pp, ..., P, d)
    g = jnp.moveaxis(g, 1, -3)                 # (B, ..., pp, P, d)
    g = g.reshape(*g.shape[:-3], g.shape[-3] * g.shape[-2], g.shape[-1])
    return jax.lax.slice_in_dim(g, 0, cache_len, axis=-2)


def _page_offsets(pages, index, b: int, s: int):
    """(page, offset, last) int32 arrays for writing s tokens at `index`.

    page/offset: (B, s) — position index[b] + j lands in page
    table[b, pos // P] at row pos % P. Positions past the slot's allocated
    footprint hit the table's sink-page tail (page 0): masked garbage, the
    paged analogue of the slab's padded-tail writes. last: (B,) absolute
    position of the final written token (the validity clock)."""
    table = pages["table"]
    psize = pages["size"]
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (b,))
    pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    page = jnp.take_along_axis(table, pos // psize, axis=1)
    return page, pos % psize, idx + (s - 1)


@scoped("attn_core")
def _split_heads(x, n, dh):
    b, s, _ = x.shape
    return x.reshape(b, s, n, dh).transpose(0, 2, 1, 3)


@scoped("attn_core")
def _merge_heads(x):
    b, h, s, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * dh)


def gqa_apply(params, x, cfg: AttnConfig, *, spec=kr.DENSE, backend="ref",
              positions=None, cache=None, index=None,
              kv_source=None, pages=None, probe=None,
              ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full-sequence (train/prefill) or single-step (decode) GQA attention.

    cache: None (train) | dict with 'k','v' (and implicit layout by size).
    index: scalar int32 — tokens already in cache (decode), or None.
    kv_source: encoder output for cross-attention (whisper).
    pages: page-table operand for NATIVE paged decode ({'table','size',
    'len'} — see module docstring); the cache leaves are then page-major
    (n_pages, KV, P, dh). Windowed layers with W < len stay resident slab
    leaves and ignore it.
    probe: serve.ledger probe (or None) — taps the merged attention output
    feeding the packed `wo` GEMM at trace time.
    """
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(kr.apply(params["wq"], x, spec, backend=backend,
                              name="wq"), h, dh)

    if cfg.cross:
        # cross-attention (whisper decoder): k/v from the encoder, cached at
        # prefill, reused verbatim every decode step.
        if cache is not None and index is not None:
            k, v, new_cache = cache["k"], cache["v"], cache
        else:
            k = _split_heads(kr.apply(params["wk"], kv_source, spec,
                                      backend=backend, name="wk"), kv, dh)
            v = _split_heads(kr.apply(params["wv"], kv_source, spec,
                                      backend=backend, name="wv"), kv, dh)
            new_cache = {"k": k, "v": v}
        skv = k.shape[2]
        with scope("attn_core"):
            o = attention_positional(
                q, k.astype(x.dtype), v.astype(x.dtype), jnp.arange(s),
                jnp.arange(skv), causal=False, softcap=cfg.softcap,
                scale=cfg.scale)
        mo = _merge_heads(o)
        if probe is not None:
            probe.tap(mo, cfg.d_model)
        y = kr.apply(params["wo"], mo, spec, backend=backend, name="wo")
        return y, new_cache

    kv_in = x if kv_source is None else kv_source
    k = _split_heads(kr.apply(params["wk"], kv_in, spec, backend=backend,
                              name="wk"), kv, dh)
    v = _split_heads(kr.apply(params["wv"], kv_in, spec, backend=backend,
                              name="wv"), kv, dh)
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)

    if positions is None:
        positions = _positions_for(index, s)
    if cfg.use_rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    q = L.shard(q, "batch", "heads", "seq", None)

    new_cache = None
    if cache is None:
        # training / encoder: contiguous self-attention over s
        o = _sdpa(q, k, v, cfg, q_pos=positions, kv_pos=positions,
                  backend=backend, contiguous=True)
    elif index is None:
        # prefill: fill cache, contiguous attention
        new_cache = _prefill_cache(cache, k, v, cfg)
        o = _sdpa(q, k, v, cfg, q_pos=positions, kv_pos=positions,
                  backend=backend, contiguous=True)
    elif pages is not None and (cfg.window is None
                                or cfg.window >= pages["len"]):
        # NATIVE paged decode: cache leaves are page-major; write the new
        # tokens straight into their pages, read through the table.
        new_cache, o = _paged_gqa_decode(q, k, v, cfg, cache, index, pages,
                                         positions, x.dtype, backend)
    else:
        # decode: write k/v at index (circular for windowed layers), attend
        new_cache, kv_pos, valid = _decode_cache_write(cache, k, v, cfg, index)
        with scope("attn_core"):        # the cache's cast for the read too
            o = attention_positional(
                q, new_cache["k"].astype(x.dtype),
                new_cache["v"].astype(x.dtype), positions, kv_pos,
                causal=cfg.causal, window=cfg.window, softcap=cfg.softcap,
                extra_mask=valid, scale=cfg.scale)
    mo = _merge_heads(o)
    if probe is not None:
        probe.tap(mo, cfg.d_model)
    y = kr.apply(params["wo"], mo, spec, backend=backend, name="wo")
    y = L.shard(y, "batch", None, "dm_in")   # see layers.mlp_apply note
    return y, new_cache


def make_gqa_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    size = min(max_len, cfg.window) if cfg.window else max_len
    shape = (batch, cfg.n_kv_heads, size, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@scoped("kv_write")
def _prefill_cache(cache, k, v, cfg: AttnConfig):
    """Fill cache from a contiguous prefill of length s (s <= cache size or,
    for windowed layers, keep the last W positions in circular layout)."""
    size = cache["k"].shape[2]
    s = k.shape[2]
    if cfg.window and s > size:
        # keep last `size` positions, placed at their circular slots
        k_tail, v_tail = k[:, :, -size:], v[:, :, -size:]
        start = s - size
        slots = (start + jnp.arange(size)) % size
        inv = jnp.argsort(slots)
        return {"k": k_tail[:, :, inv].astype(cache["k"].dtype),
                "v": v_tail[:, :, inv].astype(cache["v"].dtype)}
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }


@scoped("kv_write")
def _decode_cache_write(cache, k, v, cfg: AttnConfig, index):
    """Write s token(s) at `index`..; return (cache, kv_positions, valid).

    s > 1 is the contiguous block write: the speculative-verify block
    (distributed.steps.make_speculative_decode_step) and the prefix-reuse
    SUFFIX PREFILL (steps.make_suffix_prefill_step — a prompt whose prefix
    KV is already resident lands its unmatched suffix here, batch-1 with a
    scalar `index` = matched length). The s positions land contiguously
    from `index` and validity extends to the LAST written position
    (causality still limits what each query row of the block sees).
    Multi-token writes into a WRAPPING circular window cache are
    unsupported (dynamic_update_slice cannot wrap) — the speculative path
    refuses those archs (serve.speculative.check_supported), prefix reuse
    disables itself on them (serve.paging.prefix_supported), and the slab
    is padded so in-range writes never clamp.

    index: scalar (lock-step batch, one shared position) or (B,) per-slot
    positions (continuous batching) — the vector form writes each batch row
    at its own cache offset and returns per-row (B, size) positions/validity
    for the per-slot attention mask.
    """
    size = cache["k"].shape[2]
    last = index + (k.shape[2] - 1)      # last written position (s == 1: index)
    slot = (index % size) if cfg.window else index
    # the barrier stops XLA from sinking the f32->bf16 convert of the update
    # INTO the stack update — fused, that turns the aliased in-place write
    # into a full cache-stack copy per layer (4.6 GiB x 96 on nemotron).
    k, v = jax.lax.optimization_barrier(
        (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)))
    slots = jnp.arange(size)
    if jnp.ndim(index) == 0:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, slot, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, slot, 0))
        if cfg.window:
            # slot s holds the latest position p <= last with p % size == s
            kv_pos = last - ((last - slots) % size)
            valid = kv_pos >= 0
        else:
            kv_pos = slots
            valid = slots <= last
    else:
        write = jax.vmap(
            lambda c, u, at: jax.lax.dynamic_update_slice(c, u, (0, at, 0)))
        ck = write(cache["k"], k, slot)
        cv = write(cache["v"], v, slot)
        if cfg.window:
            kv_pos = last[:, None] - ((last[:, None] - slots[None]) % size)
            valid = kv_pos >= 0
        else:
            kv_pos = slots
            valid = slots[None] <= last[:, None]
    return {"k": ck, "v": cv}, kv_pos, valid


def _paged_gqa_decode(q, k, v, cfg: AttnConfig, cache, index, pages,
                      positions, out_dtype, backend):
    """Page-table-native decode for full-window GQA layers.

    cache['k']/cache['v']: (n_pages, KV, P, dh) page-major store leaves.
    Writes the s new tokens with one in-place page-indexed scatter per leaf
    (the donated store updates in place — no slab view ever materializes),
    then attends: the Pallas/interpret path streams pages through
    kernels.ops.paged_attention's index map; the ref path takes the sliced
    contiguous view and runs the exact slab attention program (bit-identity
    with the slab decode branch by construction)."""
    b, s = q.shape[0], q.shape[2]
    with scope("kv_write"):
        page, off, last = _page_offsets(pages, index, b, s)
        k, v = jax.lax.optimization_barrier(
            (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)))
        # advanced indices (B,s) at axes 0/2 with a sliced head axis
        # between them move to the front: the update operand is
        # (B, s, KV, dh).
        ck = cache["k"].at[page, :, off, :].set(k.transpose(0, 2, 1, 3))
        cv = cache["v"].at[page, :, off, :].set(v.transpose(0, 2, 1, 3))
    new_cache = {"k": ck, "v": cv}
    with scope("attn_core"):
        if backend in ("pallas", "interpret") \
                and q.shape[-1] == v.shape[-1]:
            o = ops.paged_attention(
                q, ck, cv, pages["table"], last, window=cfg.window,
                softcap=cfg.softcap, scale=cfg.scale, backend=backend)
            return new_cache, o.astype(q.dtype)
        ops.PAGED_ATTN_EVENTS.append(("ref", b, pages["table"].shape[1]))
        k_view = _paged_leaf_view(ck, pages["table"], pages["len"])
        v_view = _paged_leaf_view(cv, pages["table"], pages["len"])
        slots = jnp.arange(pages["len"])
        valid = slots[None] <= last[:, None]
        o = attention_positional(
            q, k_view.astype(out_dtype), v_view.astype(out_dtype),
            positions, slots, causal=cfg.causal, window=cfg.window,
            softcap=cfg.softcap, extra_mask=valid, scale=cfg.scale)
    return new_cache, o


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention) — minicpm3, deepseek-v2
# ---------------------------------------------------------------------------

def mla_init(key, cfg: AttnConfig, spec: kr.KratosSpec = kr.DENSE,
             dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    p: Dict[str, Any] = {}
    if cfg.q_lora_rank:
        p["wq_a"] = kr.init(ks[0], d, cfg.q_lora_rank, spec, dtype)
        p["q_norm"] = L.rmsnorm_init(cfg.q_lora_rank, dtype)
        p["wq_b"] = kr.init(ks[1], cfg.q_lora_rank, h * qd, spec, dtype)
    else:
        p["wq"] = kr.init(ks[0], d, h * qd, spec, dtype)
    p["wkv_a"] = kr.init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim, spec, dtype)
    p["kv_norm"] = L.rmsnorm_init(cfg.kv_lora_rank, dtype)
    p["wkv_b"] = kr.init(ks[3], cfg.kv_lora_rank,
                         h * (cfg.qk_nope_dim + cfg.v_head_dim), spec, dtype)
    p["wo"] = kr.init(ks[4], h * cfg.v_head_dim, d, spec, dtype)
    return p


def _mla_q(params, x, cfg, spec, backend):
    h = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        qa = kr.apply(params["wq_a"], x, spec, backend=backend, name="wq_a")
        q = kr.apply(params["wq_b"], L.rmsnorm(params["q_norm"], qa), spec,
                     backend=backend, name="wq_b")
    else:
        q = kr.apply(params["wq"], x, spec, backend=backend, name="wq")
    q = _split_heads(q, h, qd)
    return q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]


def _mla_expand_kv(params, c_kv, cfg, spec, backend):
    """(B, S, r) latent -> k_nope (B,H,S,nope), v (B,H,S,vd)."""
    h = cfg.n_heads
    kvb = kr.apply(params["wkv_b"], c_kv, spec, backend=backend,
                   name="wkv_b")
    kvb = _split_heads(kvb, h, cfg.qk_nope_dim + cfg.v_head_dim)
    return kvb[..., :cfg.qk_nope_dim], kvb[..., cfg.qk_nope_dim:]


def mla_apply(params, x, cfg: AttnConfig, *, spec=kr.DENSE, backend="ref",
              positions=None, cache=None, index=None,
              kv_source=None, pages=None, probe=None,
              ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    b, s, d = x.shape
    h = cfg.n_heads
    if positions is None:
        positions = _positions_for(index, s)

    q_nope, q_rope = _mla_q(params, x, cfg, spec, backend)
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    with scope("attn_core"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)

    kv_a = kr.apply(params["wkv_a"], x, spec, backend=backend, name="wkv_a")
    c_kv = L.rmsnorm(params["kv_norm"], kv_a[..., :cfg.kv_lora_rank])
    with scope("rope"):
        k_rope = kv_a[..., cfg.kv_lora_rank:][:, :, None]      # (B,S,1,dr)
        k_rope = L.apply_rope(k_rope.transpose(0, 2, 1, 3), positions,
                              cfg.rope_theta)                  # (B,1,S,dr)

    new_cache = None
    with scope("kv_write"):
        if cache is not None and index is not None and pages is not None:
            # NATIVE paged decode: append latents straight into their pages
            # (in-place page-indexed scatter), read the sliced table view —
            # value-identical to the slab rows, so the expand below compiles
            # to the exact slab program. MLA stays on the XLA view path (the
            # Pallas paged kernel is GQA-shaped: dk != dv and the latent
            # expansion happens outside the kernel).
            ops.PAGED_ATTN_EVENTS.append(("mla", b, pages["table"].shape[1]))
            c_upd, r_upd = jax.lax.optimization_barrier(
                (c_kv.astype(cache["c_kv"].dtype),
                 k_rope.astype(cache["k_rope"].dtype)))
            page, off, last = _page_offsets(pages, index, b, c_upd.shape[1])
            ck = cache["c_kv"].at[page, off, :].set(c_upd)
            cr = cache["k_rope"].at[page, :, off, :].set(
                r_upd.transpose(0, 2, 1, 3))
            new_cache = {"c_kv": ck, "k_rope": cr}
            with scope("attn_core"):
                c_all = _paged_leaf_view(ck, pages["table"], pages["len"])
                kr_all = _paged_leaf_view(cr, pages["table"], pages["len"])
            kv_pos = jnp.arange(pages["len"])
            valid = kv_pos[None] <= last[:, None]
        elif cache is not None and index is not None:
            # decode: append compressed latents, expand the whole cache
            # (naive MLA)
            # the barrier: see _decode_cache_write
            c_upd, r_upd = jax.lax.optimization_barrier(
                (c_kv.astype(cache["c_kv"].dtype),
                 k_rope.astype(cache["k_rope"].dtype)))
            # s > 1: speculative block write
            last = index + (c_upd.shape[1] - 1)
            if jnp.ndim(index) == 0:
                ck = jax.lax.dynamic_update_slice(
                    cache["c_kv"], c_upd, (0, index, 0))
                cr = jax.lax.dynamic_update_slice(
                    cache["k_rope"], r_upd, (0, 0, index, 0))
                kv_pos = jnp.arange(ck.shape[1])
                valid = kv_pos <= last
            else:                  # per-slot clocks (continuous batching)
                ck = jax.vmap(lambda c, u, at: jax.lax.dynamic_update_slice(
                    c, u, (at, 0)))(cache["c_kv"], c_upd, index)
                cr = jax.vmap(lambda c, u, at: jax.lax.dynamic_update_slice(
                    c, u, (0, at, 0)))(cache["k_rope"], r_upd, index)
                kv_pos = jnp.arange(ck.shape[1])
                valid = kv_pos[None] <= last[:, None]
            new_cache = {"c_kv": ck, "k_rope": cr}
            c_all, kr_all = ck, cr
        elif cache is not None:
            ck = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
            cr = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                (0, 0, 0, 0))
            new_cache = {"c_kv": ck, "k_rope": cr}
            c_all, kr_all = c_kv, k_rope
            kv_pos, valid = positions, None
        else:
            c_all, kr_all = c_kv, k_rope
            kv_pos, valid = positions, None

    with scope("attn_core"):
        k_nope, v = _mla_expand_kv(params, c_all.astype(x.dtype), cfg, spec,
                                   backend)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all.astype(x.dtype),
                                      (b, h, k_nope.shape[2],
                                       cfg.qk_rope_dim))],
            axis=-1)
        attn_fn = attention_chunked if s > CHUNKED_ATTN_THRESHOLD \
            else attention_positional
        o = attn_fn(
            q, k, v, positions, kv_pos, causal=cfg.causal, window=cfg.window,
            softcap=cfg.softcap, extra_mask=valid, scale=cfg.scale)
    mo = _merge_heads(o)
    if probe is not None:
        probe.tap(mo, cfg.d_model)
    y = kr.apply(params["wo"], mo, spec, backend=backend, name="wo")
    y = L.shard(y, "batch", None, "dm_in")   # see layers.mlp_apply note
    return y, new_cache


def make_mla_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, 1, max_len, cfg.qk_rope_dim), dtype),
    }


# ---------------------------------------------------------------------------
# Unified entry
# ---------------------------------------------------------------------------

def attn_init(key, cfg: AttnConfig, spec=kr.DENSE, dtype=jnp.float32) -> Dict:
    return mla_init(key, cfg, spec, dtype) if cfg.mla else gqa_init(key, cfg, spec, dtype)


def attn_apply(params, x, cfg: AttnConfig, **kw):
    fn = mla_apply if cfg.mla else gqa_apply
    return fn(params, x, cfg, **kw)


def make_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    if cfg.cross:
        return None  # built at prefill from encoder output
    return (make_mla_cache if cfg.mla else make_gqa_cache)(cfg, batch, max_len, dtype)
