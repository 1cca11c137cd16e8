"""Work a configuration requires, counted from shapes and its Kratos spec.

The counts depend on the configuration only, never on which kernel runs
it: a projection of (n_in, n_out) applied to m rows needs 2 * m * nnz
operations, where nnz is its stored nonzero weights, and reads those
weights at `bits` (float32 where the spec keeps floats), their per-column
scales, the kept block indices, the m * n_in activations in and writes the
m * n_out activations out (float32).
"""

from __future__ import annotations

from typing import Dict

ACT_BYTES = 4        # float32 activations
FLOAT_W_BYTES = 4    # float32 weights where the spec keeps floats


def projection(n_in: int, n_out: int, m: int, kratos: Dict) -> Dict[str, float]:
    """{'flops', 'bytes', 'nnz'} of one projection on m rows."""
    nnz, idx_bytes = n_in * n_out, 0
    sparsity = kratos.get("sparsity") or 0.0
    bk, bn = kratos.get("bk", 128), kratos.get("bn", 128)
    if sparsity and n_in % bk == 0 and n_out % bn == 0:
        n_kb, n_pb = n_in // bk, n_out // bn
        kept = max(1, min(n_kb, int(round((1.0 - sparsity) * n_kb))))
        nnz = n_pb * kept * bk * bn
        idx_bytes = 4 * n_pb * kept
    bits = kratos.get("bits")
    if bits is None:
        w_bytes, scale_bytes = nnz * FLOAT_W_BYTES, 0
    else:
        w_bytes, scale_bytes = nnz * bits / 8, 4 * n_out
    act = ACT_BYTES * m * (n_in + n_out)
    return {"flops": 2.0 * m * nnz, "nnz": float(nnz),
            "bytes": float(w_bytes + scale_bytes + idx_bytes + act)}


def least_time_s(work: Dict[str, float], peaks: Dict) -> float:
    """The roofline's least time: the larger of operations over the peak
    rate and bytes over the memory bandwidth."""
    return max(work["flops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes"])


def layer_projections(model: Dict) -> Dict[str, tuple]:
    d, dh = model["d_model"], model["head_dim"]
    q, kv = model["n_heads"] * dh, model["n_kv_heads"] * dh
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, model["d_ff"]), "w_up": (d, model["d_ff"]),
            "w_down": (model["d_ff"], d)}


def decode_gemms(model: Dict, kratos: Dict, m: int) -> Dict[str, float]:
    """Summed work of every packed projection of one decode micro-step
    over m slot rows."""
    tot = {"flops": 0.0, "bytes": 0.0, "nnz": 0.0}
    for n_in, n_out in layer_projections(model).values():
        w = projection(n_in, n_out, m, kratos)
        for k in tot:
            tot[k] += w[k] * model["n_layers"]
    return tot


def decode_gemm_least_s(model: Dict, kratos: Dict, m: int, peaks: Dict
                        ) -> float:
    """Least time of the packed projections of one decode micro-step:
    each projection's own roofline bound, summed."""
    return model["n_layers"] * sum(
        least_time_s(projection(n_in, n_out, m, kratos), peaks)
        for n_in, n_out in layer_projections(model).values())


def model_flops_per_token(model: Dict, kratos: Dict) -> float:
    """2 x stored nonzero weights a token multiplies: every projection at
    its spec plus the float output head (the embedding is a gather)."""
    nnz = decode_gemms(model, kratos, 1)["nnz"]
    return 2.0 * (nnz + model["d_model"] * model["vocab"])


def attention_flops(model: Dict, context: int) -> float:
    """Scores and weighted sum of one token over `context` cached
    positions, over all layers."""
    return 4.0 * model["n_heads"] * model["head_dim"] * context \
        * model["n_layers"]
