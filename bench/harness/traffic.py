"""One generator for every traffic mix: a data file of distributions in,
request specs and (for an open loop) their due times out.

Every seed gets the same multiset of sizes and gaps, in another order: the
sizes are the distribution's quantiles at (j + 0.5) / n, and the seed only
shuffles them and draws the token ids. So two seeds ask for the same work,
and a run's spread is the system's, not the draw's.

Traffic file (`bench/traffic/<name>.json`):

    {"arrivals": "poisson" | "closed",
     "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                    "min": 32, "max": 768},
     "output_len": {"dist": "uniform", "min": 256, "max": 512}}

Cell file (`bench/cells/<workload>.json`) `load`: `{"rate_per_s": r}` for
"poisson", `{"clients": c}` for "closed".
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any

import numpy as np

_NORMAL = statistics.NormalDist()


def length_quantile(dist: dict[str, Any], q: float) -> int:
    """The `q` quantile (0 < q < 1) of a length distribution, as a whole
    number of tokens within [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * _NORMAL.inv_cdf(q))
        return int(min(max(round(x), lo), hi))
    if kind == "uniform":
        return min(lo + int(q * (hi - lo + 1)), hi)
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


@dataclasses.dataclass
class Plan:
    """What a run sends: request specs in the order they go out, and for an
    open loop the second (from the window's start) each is due."""

    specs: list[dict[str, Any]]
    due_s: list[float] | None      # None for a closed loop
    clients: int | None            # None for an open loop


def _specs(traffic, n, rng, vocab, block):
    """`n` greedy request specs; each block of `block` consecutive specs
    holds the same multiset of prompt and output lengths."""
    qs = stratified(block)
    p_set = [length_quantile(traffic["prompt_len"], q) for q in qs]
    o_set = [length_quantile(traffic["output_len"], q) for q in qs]
    specs = []
    while len(specs) < n:
        for p, o in zip(rng.permutation(p_set), rng.permutation(o_set)):
            prompt = rng.integers(1, vocab, size=int(p)).tolist()
            specs.append({"prompt": prompt, "max_tokens": int(o)})
    return specs[:n]


def plan(traffic: dict[str, Any], load: dict[str, Any], *, seed: int,
         seconds: float, vocab: int, engine: dict[str, Any]) -> Plan:
    check_fits(traffic, engine)
    rng = np.random.default_rng(seed)
    kind = traffic["arrivals"]
    if kind == "poisson":
        n = max(1, round(load["rate_per_s"] * seconds))
        gaps = -np.log1p(-stratified(n)) / load["rate_per_s"]
        gaps = rng.permutation(gaps)
        # n arrivals in [0, seconds): the first at 0, the gaps' shape kept
        t = np.concatenate([[0.0], np.cumsum(gaps[1:])])
        due = (t * seconds / (t[-1] + gaps[0])).tolist()
        return Plan(_specs(traffic, n, rng, vocab, n), due, None)
    if kind == "closed":
        clients = int(load["clients"])
        # more than a window can finish: every client has its next request
        n = clients * int(load.get("requests_per_client", 16))
        return Plan(_specs(traffic, n, rng, vocab, clients), None, clients)
    raise ValueError(f"unknown arrivals {kind!r}")


def check_fits(traffic: dict[str, Any], engine: dict[str, Any]) -> None:
    """Refuse a mix that the cell's engine would truncate: the longest
    prompt, chunk-padded, and the longest prompt with its longest answer
    must fit `max_seq`."""
    chunk, max_seq = engine["prefill_chunk"], engine["max_seq"]
    p_max = int(traffic["prompt_len"]["max"])
    o_max = int(traffic["output_len"]["max"])
    if -(-p_max // chunk) * chunk > max_seq or p_max + o_max - 1 > max_seq:
        raise ValueError(f"prompts up to {p_max} and answers up to {o_max} "
                         f"tokens do not fit max_seq={max_seq} "
                         f"(prefill_chunk={chunk})")
