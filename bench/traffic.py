"""One generator for every traffic mix in ``bench/traffic/<mix>.json``.

A mix file states the arrival process, the length distributions of prompts
and outputs, and the serving settings chosen for that traffic. The cell's
file (``bench/cells/<cell>.json``) states the measured knee, and the mix's
``rate_share_of_knee`` sets the offered rate from it.

Every seed gets the same work at the same times: the count of requests is
``rate * seconds``, and the prompt lengths, output lengths and gaps between
arrivals are the quantiles of their distributions at evenly spaced
probabilities, put in an order drawn from the mix's ``schedule_seed``. The
run's seed draws the prompt tokens. With a few requests
to a window, as here, the order of sizes and gaps sets how long the window
drains, so a seed that reordered them would change the work measured.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np
from statistics import NormalDist


@dataclasses.dataclass(frozen=True)
class Planned:
    rid: str
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n quantiles of a clipped distribution at probabilities (i+.5)/n."""
    u = (np.arange(n) + 0.5) / n
    if dist["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _gaps(arrival: dict, rate: float, n: int) -> np.ndarray:
    """n gaps between arrivals, mean 1/rate, before shuffling."""
    if arrival["kind"] != "poisson":
        raise ValueError(f"unknown arrival process {arrival['kind']!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)  # exponential quantiles
    return g / g.mean() / rate


def offered_rate(mix: dict, cell: dict) -> float:
    """Requests per second the cell offers."""
    return float(mix["rate_share_of_knee"]) * float(cell["knee_req_s"])


def n_requests(mix: dict, cell: dict, seconds: float) -> int:
    return max(1, int(math.floor(offered_rate(mix, cell) * seconds)))


def generate(mix: dict, cell: dict, vocab: int, seed: int,
             seconds: float) -> List[Planned]:
    """The requests of one run: arrivals in [0, seconds), the first at 0."""
    rate = offered_rate(mix, cell)
    return plan(mix, rate, n_requests(mix, cell, seconds), vocab, seed,
                seconds)


def plan(mix: dict, rate: float, n: int, vocab: int, seed: int,
         seconds: float) -> List[Planned]:
    """``n`` requests of the mix offered at ``rate`` (all at 0 when
    ``rate`` is infinite), inside [0, seconds)."""
    order = np.random.default_rng([int(mix["schedule_seed"]), 3])
    prompts = order.permutation(_quantiles(mix["prompt_tokens"], n))
    outputs = order.permutation(_quantiles(mix["output_tokens"], n))
    if math.isinf(rate):
        t = np.zeros(n)
    else:
        gaps = order.permutation(_gaps(mix["arrival"], rate, n))
        # the first request is due at 0; the n-1 gaps that follow are
        # scaled to end inside the window
        t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        if n > 1 and t[-1] >= seconds:
            t *= (seconds * (n - 1) / n) / t[-1]
    rng = np.random.default_rng([int(seed), 3])
    return [Planned(f"r{i}", rng.integers(0, vocab, int(prompts[i]),
                                          dtype=np.int64).astype(np.int32),
                    int(outputs[i]), float(t[i])) for i in range(n)]


def max_context(mix: dict) -> int:
    """Positions the longest request can hold."""
    return int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])


def shapes(mix: dict) -> dict:
    """Every padded prompt length and decode view (in blocks) the mix can
    reach with its serving settings: the shapes set-up warms and no
    others."""
    s = mix["serving"]
    bs, vb = int(s["block_size"]), int(s["view_bucket"])
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    prefill = sorted({-(-n // bs) * bs
                      for n in range(int(p["min"]), int(p["max"]) + 1)})
    # a decode step at position pos covers pos+1 positions; the first is at
    # the prompt's length, the last at prompt + outputs - 2
    lo = -(-(int(p["min"]) + 1) // bs)
    hi = -(-(int(p["max"]) + int(o["max"]) - 1) // bs)
    views = sorted({-(-b // vb) * vb for b in range(lo, hi + 1)})
    return {"prefill_tokens": prefill, "view_blocks": views}
