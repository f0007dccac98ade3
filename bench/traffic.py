"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) states the arrivals, the prompt and
output lengths and how prompt tokens are drawn:

- ``arrival``: ``{"kind": "closed", "clients": n}`` (each client sends its
  next request when its last one retires) or ``{"kind": "poisson",
  "rate_per_s": r}`` (an open loop on a schedule).
- ``prompt_len`` / ``output_len``: ``{"kind": "fixed", "value": n}`` or
  ``{"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.
- ``tokens``: ``{"kind": "iid"}`` (uniform over the vocabulary) or
  ``{"kind": "runs", "min_run": a, "max_run": b}`` (one token repeated for
  a run length drawn uniformly from ``a..b``).
- ``set_size``: how many requests one set holds (closed loop; the clients
  take them in order and start again at the first), so that a window
  serves about the same lengths whatever the seed; an open loop sizes its
  set to the window (``rate * seconds``).
- ``warm_s``: seconds of the same traffic served before the window opens.

Every seed gets the same multiset of lengths and inter-arrival gaps: they
are the distribution's quantiles at evenly spaced probabilities.  The seed
only chooses their order and the token ids, so two seeds ask for the same
work in a different order.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as generated: the token array and its budget."""
    prompt: np.ndarray          # (Lp,) int32
    max_new: int
    due: Optional[float] = None  # seconds after the window opens (open loop)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any non-negative
    whole number, of any size)."""
    return np.random.default_rng([stream, seed])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at (i + 0.5) / n."""
    if dist["kind"] == "fixed":
        return np.full((n,), int(dist["value"]), np.int64)
    if dist["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    nd = NormalDist(math.log(dist["median"]), dist["sigma"])
    vals = [math.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean ``1 / rate``, as the
    quantiles at (i + 0.5) / n."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def make_tokens(tok: dict, n: int, vocab: int,
                rng: np.random.Generator) -> np.ndarray:
    if tok["kind"] == "iid":
        return rng.integers(0, vocab, n).astype(np.int32)
    if tok["kind"] != "runs":
        raise ValueError(f"unknown token kind {tok['kind']!r}")
    out = np.empty((n,), np.int32)
    i = 0
    while i < n:
        run = int(rng.integers(tok["min_run"], tok["max_run"] + 1))
        out[i:i + run] = rng.integers(0, vocab)
        i += run
    return out


def max_prompt(mix: dict) -> int:
    d = mix["prompt_len"]
    return int(d["value"] if d["kind"] == "fixed" else d["max"])


def max_output(mix: dict) -> int:
    d = mix["output_len"]
    return int(d["value"] if d["kind"] == "fixed" else d["max"])


def request_set(mix: dict, seed: int, vocab: int, *, stream: int,
                seconds: Optional[float] = None) -> List[Spec]:
    """One set of requests, in the order the seed gives them.

    An open loop's set covers ``seconds``: its due times start at 0 and its
    gaps add up to about ``seconds``.  A closed loop's set holds
    ``set_size`` requests, handed out in order.
    """
    arrival = mix["arrival"]
    if arrival["kind"] == "poisson":
        if seconds is None:
            raise ValueError("an open loop needs the window's length")
        n = max(1, int(round(arrival["rate_per_s"] * seconds)))
    elif arrival["kind"] == "closed":
        n = int(mix["set_size"])
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    rng = rng_for(seed, stream)
    lens = rng.permutation(quantiles(mix["prompt_len"], n))
    outs = rng.permutation(quantiles(mix["output_len"], n))
    dues = None
    if arrival["kind"] == "poisson":
        gaps = rng.permutation(exp_gaps(arrival["rate_per_s"], n))
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    specs = []
    for i in range(n):
        specs.append(Spec(prompt=make_tokens(mix["tokens"],
                                                    int(lens[i]), vocab, rng),
                          max_new=int(outs[i]),
                          due=None if dues is None else float(dues[i])))
    return specs
