"""Work a configuration requires, counted from shapes and its Kratos spec.

The counts depend on the configuration only, never on which kernel runs
it: a projection of (n_in, n_out) applied to m rows needs 2 * m * nnz
operations, where nnz is its stored nonzero weights, and reads those
weights at `bits` (float32 where the spec keeps floats), their per-column
scales, the kept block indices, the m * n_in activations in and writes the
m * n_out activations out (float32). Which projections a decode step
applies, on how many rows and how often, is the architecture's: its
adapter (`bench/adapters/<reference>.py`, `system.adapter`) lists them in
`decode_projections(model, m)`.
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


def decode_gemm_least_s(adapter, model: Dict, kratos: Dict, m: int,
                        peaks: Dict) -> float:
    """Least time of the packed projections of one decode micro-step:
    each projection's own roofline bound times its applications, summed.
    Bounds of projections applied equally often are summed first."""
    least: Dict[int, list] = {}
    for n_in, n_out, rows, times in adapter.decode_projections(model, m):
        least.setdefault(times, []).append(
            least_time_s(projection(n_in, n_out, rows, kratos), peaks))
    return sum(times * sum(t) for times, t in least.items())


def model_flops_per_token(adapter, model: Dict, kratos: Dict) -> float:
    """Operations of one token: the packed projections of a decode
    micro-step on one row, at their spec, plus the float output head (the
    embedding is a gather)."""
    return sum(projection(n_in, n_out, rows, kratos)["flops"] * times
               for n_in, n_out, rows, times
               in adapter.decode_projections(model, 1)) \
        + 2.0 * model["d_model"] * model["vocab"]
