"""Fused decode over the carried, layer-stacked cache against a forward
with no cache.

Each case prefills two prompts of different lengths into a two-slot cache
(slab or paged), runs one fused dispatch of `decode_chunk=4` greedy
micro-steps on the ref backend, and checks, per slot, against one
full-sequence forward over prompt plus emitted tokens:

  * every decoded position's logits agree within 1e-4 relative, and each
    greedy token is that forward's argmax;
  * the K/V rows the decode wrote equal that forward's K/V (its prefill
    form's cache) within the same tolerance, and a recurrent state (Mamba
    conv and SSM) equals that forward's final state;
  * every cache row the decode did not write keeps its value exactly.

The danube case has a sliding window smaller than the cache, so slot 0's
writes wrap round the circular cache. The jamba hybrid runs two periods of
its 8-layer pattern, so each scanned Mamba and attention slot stacks two
layers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.distributed import steps as ST
from repro.models import transformer as T
from repro.serve.cache_pool import CachePool
from repro.serve.paging import PagedCachePool

K = 4                        # micro-steps in the fused dispatch
MAX_LEN = 16
PROMPTS = [[3, 14, 15, 92, 65, 35], [89, 79, 32]]
TOL = 1e-4

CASES = [("h2o-danube-1.8b", None), ("nemotron-4-340b", 4),
         ("minicpm3-4b", None), ("jamba-v0.1-52b", None)]
IDS = ["gqa-slab", "gqa-paged", "mla", "mamba-hybrid"]


def _close(got, want, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert err <= TOL * scale, (what, err, scale)


def _slot(leaf, path, b):
    """Slot b of a cache leaf: 'blocks' leaves are layer-stacked."""
    names = [str(k.key) for k in path if hasattr(k, "key")]
    return np.take(np.asarray(leaf), b, axis=1 if names[0] == "blocks" else 0)


def _recurrent(path) -> bool:
    return any(getattr(k, "key", None) in ("conv", "ssm") for k in path)


@pytest.mark.parametrize("arch,page_size", CASES, ids=IDS)
def test_fused_decode_matches_full_forward(arch, page_size):
    cfg = C.get_smoke(arch)
    if cfg.attn_period:
        cfg = dataclasses.replace(cfg, n_layers=2 * cfg.n_layers)
    params = T.init(jax.random.PRNGKey(7), cfg)
    n = len(PROMPTS)
    if page_size:
        pool = PagedCachePool(cfg, n, MAX_LEN, jnp.float32,
                              page_size=page_size, prefix_cache=False)
    else:
        pool = CachePool(cfg, n, MAX_LEN, jnp.float32)
    prefill = jax.jit(ST.make_prefill_step(cfg, "ref", last_only=False,
                                           cache_len=MAX_LEN))
    state = ST.make_decode_state(n)
    for b, prompt in enumerate(PROMPTS):
        slot = pool.alloc()
        assert slot == b
        if page_size:
            pool.alloc_pages(slot, len(prompt) + K)
        logits, single = prefill(params, {"tokens": jnp.asarray([prompt])})
        pool.write_slot(slot, single)
        first = int(jnp.argmax(logits[0, len(prompt) - 1]))
        state = ST.install_slot(state, slot, first, len(prompt), 0.0, -1, K)

    if page_size:
        decode = jax.jit(ST.make_paged_decode_step(
            cfg, "ref", n_steps=K, layout=pool.layout))
        before = pool.layout.gather(pool.store, pool.page_table)
        toks, store, table, _ = decode(params, pool.store, pool.page_table,
                                       state)
        after = pool.layout.gather(store, table)
        pages = {"size": page_size, "len": MAX_LEN, "table": table}
    else:
        decode = jax.jit(ST.make_decode_step(cfg, "ref", n_steps=K))
        before = pool.caches
        toks, after, _ = decode(params, pool.caches, state)
        pages = None
    toks = np.asarray(toks)                                  # (K, B)

    # the decode's logits: the same micro-steps, fed the emitted tokens
    fed = jnp.asarray(np.concatenate([np.asarray(state["tokens"])[None],
                                      toks[:-1]], axis=0))    # (K, B)
    start = state["index"]

    def micro(caches, xs):
        tok, i = xs
        tree = pool.layout.as_tree(caches) if page_size else caches
        logits, _, tree = T.forward(params, tok[:, None], cfg, caches=tree,
                                    index=start + i, pages=pages)
        out = pool.layout.from_tree(tree) if page_size else tree
        return out, logits[:, -1]

    cache0 = pool.store if page_size else pool.caches
    _, dec_logits = jax.jit(lambda c: jax.lax.scan(
        micro, c, (fed, jnp.arange(K, dtype=jnp.int32))))(cache0)

    forward = jax.jit(lambda p, seq, c: T.forward(p, seq, cfg, caches=c))
    for b, prompt in enumerate(PROMPTS):
        length = len(prompt)
        seq = jnp.asarray([prompt + list(np.asarray(fed)[:, b])])
        full, _, _ = forward(params, seq, None)
        _, _, ref = forward(params, seq, T.make_caches(cfg, 1, MAX_LEN))
        for i in range(K):
            _close(dec_logits[i, b], full[0, length + i], (b, i, "logits"))
            assert toks[i, b] == int(jnp.argmax(full[0, length + i]))
        written = {(length + i) for i in range(K)}
        flat_after = jax.tree_util.tree_flatten_with_path(after)[0]
        flat_before = jax.tree_util.tree_leaves(before)
        flat_ref = jax.tree_util.tree_leaves(ref)
        for (path, a), b0, r in zip(flat_after, flat_before, flat_ref):
            a, b0 = _slot(a, path, b), _slot(b0, path, b)
            r = _slot(r, path, 0)
            if _recurrent(path):
                _close(a, r, (b, path, "state"))
                continue
            size = a.shape[-2]
            rows = sorted({p % size for p in written})
            for p in rows:
                _close(a[..., p, :], r[..., p, :], (b, path, p))
            keep = [p for p in range(size) if p not in rows]
            np.testing.assert_array_equal(a[..., keep, :],
                                          b0[..., keep, :])
