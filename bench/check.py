"""The comparison that decides `correct`: served tokens against the plain reference.

Once the window has closed and the system's state is freed, a sample of
the requests the timed path finished, drawn from the seed and always with
the longest of them in it, is run through the reference: one full forward
pass over each prompt followed by its served tokens. For every served
token the reference's best logit at that position minus the served
token's logit is its gap; greedy decoding that agrees with the reference
reads 0, and rounding in the system reads small gaps at near-ties. The
widest gap is compared with the configuration's limit.
"""

from __future__ import annotations

import importlib
from typing import Dict, List, Sequence

import numpy as np

MIN_TOKENS = 768        # served tokens compared per run, at least ...
MAX_REQUESTS = 32       # ... unless this many requests are reached first
MIN_BUCKET = 256


def reference(config: Dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def sample(recs: Sequence, seed: int) -> List:
    """Finished requests to compare: the one with the most served tokens,
    then others in an order drawn from the seed."""
    done = [r for r in recs if r.done]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (done[i].n_out, len(done[i].prompt)))
    order = np.random.default_rng(np.random.SeedSequence([seed, 7])) \
        .permutation(len(done))
    picked, tokens = [done[longest]], done[longest].n_out
    for i in order:
        if tokens >= MIN_TOKENS or len(picked) >= MAX_REQUESTS:
            break
        if i != longest:
            picked.append(done[i])
            tokens += done[i].n_out
    return picked


def padded(tokens: np.ndarray, max_len: int) -> np.ndarray:
    """Right-pad with zeros to a power of two (at least MIN_BUCKET), so
    the reference compiles a few shapes; causal attention keeps the pad
    from touching earlier positions."""
    n = MIN_BUCKET
    while n < len(tokens):
        n *= 2
    out = np.zeros(min(n, max(max_len, len(tokens))), np.int32)
    out[:len(tokens)] = tokens
    return out


def gaps(config: Dict, seed: int, picked: Sequence,
         served: Sequence[Sequence[int]], control: bool = False
         ) -> np.ndarray:
    """The gap of every served token of `picked`, in order. control=True
    instead reads, at the same positions, the gap of the token that the
    reference computed in bfloat16 puts first."""
    import jax.numpy as jnp
    ref = reference(config)
    model = config["model"]
    w = ref.make_weights(seed, model, config["kratos"], effective=True)
    items = tuple(sorted(model.items()))
    fn = ref.control_gaps if control else ref.served_gaps
    out = []
    for rec, toks in zip(picked, served):
        p = len(rec.prompt)
        seq = np.concatenate([np.asarray(rec.prompt, np.int32),
                              np.asarray(toks, np.int32)])
        g = np.asarray(fn(w, jnp.asarray(
            padded(seq, config["engine"]["max_len"])), items))
        out.append(g[p - 1:p - 1 + len(toks)])
    del w
    return np.concatenate(out) if out else np.zeros(0)


def readings(g: np.ndarray) -> Dict[str, float]:
    """The numbers a set of gaps can be judged by: the widest gap, the
    mean gap, and the share of tokens that are not the reference's best."""
    if not g.size:          # nothing compared: no number (null in JSON)
        return {"max_logit_gap": None, "mean_logit_gap": None,
                "flip_share": None}
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "flip_share": float((g > 0).mean())}


def verdict(config: Dict, read: Dict[str, float], ok: bool = True) -> Dict:
    """{'correct', 'checks'}: every number the configuration limits,
    beside its limit. ok=False (nothing to compare, a token outside the
    vocabulary) is not correct whatever the numbers read."""
    checks = {k: {"value": read[k], "limit": v["limit"]}
              for k, v in config["check"].items()}
    return {"correct": ok and all(c["value"] is not None
                                  and c["value"] <= c["limit"]
                                  for c in checks.values()),
            "checks": checks}


def judge(config: Dict, seed: int, recs: Sequence, control: bool = False
          ) -> Dict:
    """{'correct', 'checks': {name: {'value', 'limit'}}, 'compared',
    'readings': {'program': ...}}. With control=True also 'control': the
    same verdict for the token the reference computed in bfloat16 puts
    first at each compared position, and its readings."""
    picked = sample(recs, seed)
    served = [r.served for r in picked]
    vocab = config["model"]["vocab"]
    ok = bool(picked) and all(0 <= t < vocab for s in served for t in s)
    g = gaps(config, seed, picked, served) if ok else np.zeros(0)
    read = readings(g)
    out = verdict(config, read, ok)
    out["compared"] = {"requests": len(picked), "tokens": int(g.size)}
    out["readings"] = {"program": read}
    if control and ok:
        c = readings(gaps(config, seed, picked, served, control=True))
        out["control"] = verdict(config, c)
        out["readings"]["control"] = c
    return out
