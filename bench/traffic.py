"""The one traffic generator: reads a mix's parameters from `traffic/<name>.json`.

A mix file states the loop and the length distributions; nothing in it is
code, so a new mix is a new data file. Two loops exist:

* ``open``: Poisson arrivals at ``rate_rps``. ``round(rate_rps * seconds)``
  requests are due inside the window; their gaps are the stratified
  quantiles of the exponential distribution, scaled to fill the window and
  put in an order drawn from the seed.
* ``closed``: ``clients_per_slot * n_slots`` clients, each sending its next
  request as soon as its previous one completes. Requests come from blocks
  of ``block`` stratified draws, each block in its own seeded order.

Every seed gets the same multiset of lengths and gaps in another order
(stratified quantiles ``(j + 0.5) / n`` of each distribution), so a seed
changes the order of the work and never its amount. Token ids are drawn
from the seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
_LOOPS = ("open", "closed")


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of a mix: when it is due (open loop) and its sizes."""

    due_s: float            # seconds after the window opens (open loop)
    prompt: np.ndarray      # int32 token ids
    n_out: int              # tokens to generate


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    mix = json.loads((directory / f"{name}.json").read_text())
    if mix.get("loop") not in _LOOPS:
        raise ValueError(f"traffic {name}: loop must be one of {_LOOPS}")
    return mix


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as ints.

    dist: {"dist": "lognormal", "median", "sigma", "min", "max"} |
    {"dist": "loguniform", "min", "max"} | {"dist": "uniform", "min", "max"}.
    Lognormal values are clipped to [min, max]; the other two lie inside it.
    """
    u = (np.arange(n) + 0.5) / n
    kind, lo, hi = dist["dist"], dist["min"], dist["max"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(v), lo, hi).astype(np.int64)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def _block(mix: Dict, n: int, vocab: int, seed: int, stream: int
           ) -> List[Req]:
    """n requests whose lengths are the n stratified quantiles, in an order
    drawn from (seed, stream); due times are left at 0."""
    rng = _rng(seed, stream)
    p_len = rng.permutation(quantiles(mix["prompt"], n))
    o_len = rng.permutation(quantiles(mix["output"], n))
    return [Req(0.0, rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(p_len, o_len)]


def open_requests(mix: Dict, seconds: float, vocab: int, seed: int
                  ) -> List[Req]:
    """The open loop's requests, sorted by due time; all are due inside
    the window, the last one at its close."""
    n = max(1, round(mix["rate_rps"] * seconds))
    reqs = _block(mix, n, vocab, seed, 0)
    u = (np.arange(n) + 0.5) / n
    gaps = _rng(seed, 1).permutation(-np.log1p(-u))
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    return [dataclasses.replace(r, due_s=float(t)) for r, t in zip(reqs, due)]


def closed_requests(mix: Dict, vocab: int, seed: int) -> Iterator[Req]:
    """The closed loop's endless request stream, block by block."""
    k = 0
    while True:
        yield from _block(mix, mix["block"], vocab, seed, 2 + k)
        k += 1


def n_clients(mix: Dict, n_slots: int) -> int:
    return mix["clients_per_slot"] * n_slots


def length_bound(mix: Dict) -> int:
    """The most cache positions one request of the mix can need."""
    return mix["prompt"]["max"] + mix["output"]["max"]


def prompt_lengths(mix: Dict, seconds: float) -> np.ndarray:
    """Every prompt length the mix can send (for the warm-up's buckets)."""
    if mix["loop"] == "open":
        return quantiles(mix["prompt"], max(1, round(mix["rate_rps"] * seconds)))
    return quantiles(mix["prompt"], mix["block"])
