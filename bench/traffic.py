"""One generator for every traffic mix.

A mix is a JSON file of parameters (``bench/traffic/<name>.json``):

  arrivals  ``{"kind": "poisson", "rate_per_s": r}``: open loop, requests
            due on a schedule whatever the server does; or
            ``{"kind": "closed", "outstanding_per_slot": k}``: closed loop,
            ``k * slots`` requests outstanding, each replaced when it ends
  prompt,   ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
  output      "max": b}`` or ``{"dist": "uniform", "min": a, "max": b}``,
            in tokens; ``"scale": "max_len"`` reads ``min``/``max`` as
            shares of the configuration's ``max_len``
  ramp_s    seconds of traffic before the measured window opens
  head_start  optional, closed loop: ``{"per_slot": k}``.  The first
            ``k * slots`` requests arrive part-served, as requests of a
            loop that has run for a while: each has a share of its output,
            drawn uniform over [0, 1), already served, as random tokens
            folded into its prompt, and asks for the rest.  The ramp then
            lasts at least until each of them has its first token
  block     requests per stratified block (below)
  check     ``{"requests": n}``: finished requests the reference re-runs

Sizes are drawn by stratified sampling: each block of ``block`` requests
takes its prompt lengths, output lengths and gaps between arrivals at the
quantiles ``(i + 0.5) / block`` of their distributions, in an order
shuffled by the seed.  Every seed therefore offers the same work in
another order, and runs with different seeds differ by ordering alone.
The head-start requests form a block of their own, whose prompt lengths,
output lengths and served shares are paired by a fixed rule, so that every
seed starts from the same set of part-served requests.  Prompt token ids
are uniform over the configuration's real vocabulary.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np

_MASK64 = (1 << 64) - 1


@dataclasses.dataclass
class Draw:
    index: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    gap_s: float            # time since the previous arrival (open loop)


def _ppf(spec: dict, max_len: int):
    """Quantile function of a length distribution, as integer tokens."""
    scale = max_len if spec.get("scale") == "max_len" else 1
    lo, hi = spec["min"] * scale, spec["max"] * scale
    lo, hi = int(round(lo)), int(round(hi))
    if spec["dist"] == "uniform":
        return lambda u: np.minimum(lo + np.floor(u * (hi - lo + 1)),
                                    hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        mu, sigma = math.log(spec["median"] * scale), spec["sigma"]
        return lambda u: np.clip(np.round(np.exp(
            mu + sigma * np.array([nd.inv_cdf(x) for x in u]))),
            lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def seeded_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of a run's seed (seeds of any size)."""
    return np.random.default_rng([seed & _MASK64, *stream])


def head_start_count(traffic: dict, slots: int) -> int:
    """How many requests of the mix arrive part-served."""
    hs = traffic.get("head_start")
    return int(hs["per_slot"] * slots) if hs else 0


def _head_start(seed: int, n: int, vocab: int, prompt_ppf,
                output_ppf) -> Iterator[Draw]:
    u = (np.arange(n) + 0.5) / n
    fixed = seeded_rng(0, 3)      # the pairing, the same for every seed
    plens = prompt_ppf(u[fixed.permutation(n)])
    outs = output_ppf(u)
    shares = u[fixed.permutation(n)] - 0.5 / n
    order = seeded_rng(seed, 3).permutation(n)
    for index, i in enumerate(order):
        served = int(shares[i] * outs[i])
        ids = seeded_rng(seed, 1, index).integers(
            0, vocab, int(plens[i]) + served, dtype=np.int32)
        yield Draw(index=index, prompt=ids, max_new=int(outs[i]) - served,
                   gap_s=0.0)


def requests(traffic: dict, seed: int, *, vocab: int, max_len: int,
             slots: int = 0) -> Iterator[Draw]:
    """The mix's requests in arrival order, without end; ``slots`` sizes
    the head start."""
    block = int(traffic["block"])
    prompt_ppf = _ppf(traffic["prompt"], max_len)
    output_ppf = _ppf(traffic["output"], max_len)
    arr = traffic["arrivals"]
    rate = float(arr["rate_per_s"]) if arr["kind"] == "poisson" else None
    u = (np.arange(block) + 0.5) / block
    index = head_start_count(traffic, slots)
    if index:
        yield from _head_start(seed, index, vocab, prompt_ppf,
                               output_ppf)
    for b in range(1 << 62):
        rng = seeded_rng(seed, 0, b)
        plens = prompt_ppf(rng.permutation(u))
        outs = output_ppf(rng.permutation(u))
        gaps = (-np.log1p(-rng.permutation(u)) / rate if rate is not None
                else np.zeros(block))
        for i in range(block):
            ids = seeded_rng(seed, 1, index).integers(
                0, vocab, int(plens[i]), dtype=np.int32)
            yield Draw(index=index, prompt=ids, max_new=int(outs[i]),
                       gap_s=float(gaps[i]))
            index += 1
