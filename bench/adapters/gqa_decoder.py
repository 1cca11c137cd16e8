"""The program's side of the GQA decoder reference (`bench/reference/gqa_decoder.py`).

The one place that knows both layouts: how the reference's weight dict
maps onto the program's parameter tree, and which projections one decode
micro-step applies, for the work counts of `bench/workcount.py`. The
harness finds it by the configuration's `reference` name.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from bench.reference.gqa_decoder import projection_shapes


def program_tree(w: Dict, model: Dict) -> Dict:
    """The program's parameter tree (one scanned block slot, no prelude)
    over the reference's weight dict; shares every buffer."""
    lw = w["layers"]
    return {
        "embed": {"emb": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "head": {"w": w["head"]},
        "prelude": [],
        "blocks": [{
            "pre_norm": {"scale": lw["attn_norm"]},
            "mixer": {k: {"w": lw[k]} for k in ("wq", "wk", "wv", "wo")},
            "ffn_norm": {"scale": lw["mlp_norm"]},
            "ffn": {k: {"w": lw[k]} for k in ("w_gate", "w_up", "w_down")},
        }],
    }


def decode_projections(model: Dict, m: int) -> List[Tuple[int, int, int, int]]:
    """(n_in, n_out, rows, times) of each projection one decode micro-step
    applies: every projection of a layer on the m slot rows, once in each
    layer."""
    return [(n_in, n_out, m, model["n_layers"])
            for n_in, n_out in projection_shapes(model).values()]


def attention_flops(model: Dict, context: int) -> float:
    """Scores and weighted sum of one token over `context` cached
    positions, over all layers."""
    return 4.0 * model["n_heads"] * model["head_dim"] * context \
        * model["n_layers"]
