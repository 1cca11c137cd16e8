"""Plain reference of a pre-norm GQA decoder (Llama/Mistral family), in jax.numpy.

It imports nothing of the system under test. It holds:

* `make_weights`: the model's weights from a seed, in one jitted call. The
  same function makes the weights the benchmark hands to the system, so the
  reference makes its own copy and takes none from the system.
* `block_plan` and `make_weights(..., effective=True)`: a configuration's
  fine-grained sparsity and weight precision applied to those weights,
  written out anew from their definition: balanced block sparsity (each output block of
  `bn` columns keeps the same number of `bk`-row blocks, at positions drawn
  by a seeded shuffle) and symmetric per-output-channel integer codes
  (scale = max|w| / qmax, code = round(w / scale)).
* `forward`: the full causal forward pass over one sequence, layer by layer
  under `lax.scan`, at a stated dtype and matmul precision.
* `served_gaps` / `control_gaps`: how far below the reference's best logit
  each served (or control-chosen) token lies.

Layer equations (arXiv:2401.16818, a Llama/Mistral decoder): RMSNorm
(x / sqrt(mean(x^2) + eps) * g), rotary embeddings on q and k, grouped-query
attention with a causal sliding window, a SiLU-gated MLP, a final RMSNorm
and an untied output head. Rotary embeddings rotate interleaved pairs
(dims 2i, 2i+1), the system's convention; Hugging Face's rotate-half pairs
(i, i + dh/2) are the same model under a fixed permutation of the q and k
columns.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

PROJECTIONS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
QMAX = {8: 127, 4: 7}


def projection_shapes(m: Dict) -> Dict[str, tuple]:
    """(n_in, n_out) of each projection of one layer; m is the model dict."""
    d, dh = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * dh, m["n_kv_heads"] * dh
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, m["d_ff"]), "w_up": (d, m["d_ff"]),
            "w_down": (m["d_ff"], d)}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also those past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def block_plan(n_in: int, n_out: int, bk: int, bn: int, sparsity: float,
               plan_seed: int = 0) -> np.ndarray:
    """Kept k-block indices, int32[n_out // bn, nnz], each row sorted.

    nnz = round((1 - sparsity) * n_in / bk), at least 1; row j keeps the
    first nnz entries of a permutation drawn from
    SeedSequence([plan_seed, n_in, n_out, bk, bn])."""
    n_kb, n_pb = n_in // bk, n_out // bn
    nnz = max(1, min(n_kb, int(round((1.0 - sparsity) * n_kb))))
    rng = np.random.default_rng(
        np.random.SeedSequence([plan_seed, n_in, n_out, bk, bn]))
    return np.stack([np.sort(rng.permutation(n_kb)[:nnz])
                     for _ in range(n_pb)]).astype(np.int32)


def block_mask(plan: np.ndarray, n_kb: int) -> np.ndarray:
    """bool[n_kb, n_pb]: which (k-block, output-block) pairs are kept."""
    keep = np.zeros((n_kb, plan.shape[0]), bool)
    for j, rows in enumerate(plan):
        keep[rows, j] = True
    return keep


def _masks(m: Dict, kratos: Dict) -> Dict[str, np.ndarray]:
    if not kratos.get("sparsity"):
        return {}
    bk, bn = kratos["bk"], kratos["bn"]
    out = {}
    for name, (n_in, n_out) in projection_shapes(m).items():
        if n_in % bk or n_out % bn:
            continue
        out[name] = block_mask(
            block_plan(n_in, n_out, bk, bn, kratos["sparsity"]), n_in // bk)
    return out


def _apply_mask(w, keep, bk, bn):
    n_l, n_in, n_out = w.shape
    wb = w.reshape(n_l, n_in // bk, bk, n_out // bn, bn)
    return (wb * keep[None, :, None, :, None]).reshape(n_l, n_in, n_out)


def _quantize(w, bits):
    """Per-output-column symmetric codes times their scale."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / QMAX[bits] + 1e-12
    return jnp.clip(jnp.round(w / scale), -QMAX[bits], QMAX[bits]) * scale


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, m_items, kratos_items, effective):
    m, kratos = dict(m_items), dict(kratos_items)
    d, v, n_l = m["d_model"], m["vocab"], m["n_layers"]
    ks = iter(jax.random.split(key, 16))
    normal = jax.random.normal
    w = {
        "embed": normal(next(ks), (v, d), jnp.float32),
        "final_norm": 1.0 + 0.1 * normal(next(ks), (d,), jnp.float32),
        "head": normal(next(ks), (d, v), jnp.float32) * d ** -0.5,
        "layers": {
            "attn_norm": 1.0 + 0.1 * normal(next(ks), (n_l, d), jnp.float32),
            "mlp_norm": 1.0 + 0.1 * normal(next(ks), (n_l, d), jnp.float32),
        },
    }
    masks = _masks(m, kratos)
    bits = kratos.get("bits")
    for name, (n_in, n_out) in projection_shapes(m).items():
        x = normal(next(ks), (n_l, n_in, n_out), jnp.float32) * n_in ** -0.5
        if name in masks:
            x = _apply_mask(x, jnp.asarray(masks[name]), kratos["bk"],
                            kratos["bn"])
        if effective and bits is not None:
            x = _quantize(x, bits)
        w["layers"][name] = x
    return w


def make_weights(seed: int, model: Dict, kratos: Optional[Dict] = None, *,
                 effective: bool = False) -> Dict:
    """Float32 weights from `seed`, made on the device in one jitted call.

    Projections are N(0, 1/n_in), the embedding N(0, 1), the head
    N(0, 1/d_model), norm gains 1 + N(0, 0.01). With a sparse `kratos`
    spec the pruned blocks are zero. effective=True also rounds the
    projections to the spec's integer codes: the weights the configuration
    serves."""
    kratos = kratos or {}
    return _make(seed_key(seed), tuple(sorted(model.items())),
                 tuple(sorted(kratos.items())), effective)


def _rms(x, g, eps):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.reshape(x.shape).astype(x.dtype)


def forward(w: Dict, tokens, model: Dict, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST):
    """Logits (S, vocab) in float32 for one sequence of token ids (S,)."""
    m = model
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta, window = m["norm_eps"], m["rope_theta"], m.get("window")
    s = tokens.shape[0]
    dot = functools.partial(jnp.dot, precision=precision)
    pos = jnp.arange(s)
    allowed = pos[None, :] <= pos[:, None]
    if window:
        allowed &= pos[None, :] > pos[:, None] - window
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    x = w["embed"][tokens].astype(dtype)

    def layer(x, lw):
        lw = cast(lw)
        a = _rms(x, lw["attn_norm"], eps)
        q = _rope(dot(a, lw["wq"]).reshape(s, h, dh), theta)
        k = _rope(dot(a, lw["wk"]).reshape(s, kv, dh), theta)
        v = dot(a, lw["wv"]).reshape(s, kv, dh)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=precision)
        sc = jnp.where(allowed[None], sc.astype(jnp.float32) * dh ** -0.5,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(dtype)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=precision)
        x = x + dot(o.reshape(s, h * dh), lw["wo"]).astype(dtype)
        f = _rms(x, lw["mlp_norm"], eps)
        g = jax.nn.silu(dot(f, lw["w_gate"]).astype(jnp.float32))
        u = dot(f, lw["w_up"]).astype(jnp.float32)
        x = x + dot((g * u).astype(dtype), lw["w_down"]).astype(dtype)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, w["final_norm"].astype(dtype), eps)
    return dot(x, w["head"].astype(dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(2,))
def served_gaps(w, tokens, model_items):
    """gap[t] = max(logits[t]) - logits[t, tokens[t + 1]]; the last is 0."""
    logits = forward(w, tokens, dict(model_items))
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    gap = logits.max(-1) - jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
    return gap.at[-1].set(0.0)


@functools.partial(jax.jit, static_argnums=(2,))
def control_gaps(w, tokens, model_items):
    """Per position: how far below the reference's best logit lies the
    token that the same forward in bfloat16 (weights, activations and
    matmuls) puts first."""
    m = dict(model_items)
    ref = forward(w, tokens, m)
    low = forward(w, tokens, m, dtype=jnp.bfloat16,
                  precision=jax.lax.Precision.DEFAULT)
    pick = jnp.argmax(low, axis=-1)
    return ref.max(-1) - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
