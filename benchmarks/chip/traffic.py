"""One seeded generator for every traffic mix.

A mix is a JSON file ``traffic/<name>.json`` of parameters:

``requests``       how many requests.
``arrivals``       ``{"kind": "at_start"}``: every request due at 0, a
                   backlog.
``prompt_tokens``, ``output_tokens``
                   ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
                   (clipped) or ``{"dist": "uniform", "min", "max"}``.

Other keys (``source``, ``assumed``) document the mix and are not read.

Every seed gets the same sizes in the same order, and its own token ids.
The sizes are the distribution's quantiles at (i + 1/2) / n, in the
bit-reversed order of their ranks (the prompts' ranks run the other way),
so every run of 2^k consecutive requests from the first spreads over the
whole distribution.  A window serves only the first requests when the
decode is slow: a shuffle by the seed would make one run's window hold a
short answer that finishes inside it and another's not, and so change the
work the metrics are taken over.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Item:
    prompt: np.ndarray  # (n,) int32
    max_new: int


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a length distribution, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def spread_order(n: int) -> np.ndarray:
    """0..n-1 in bit-reversed order (ranks past n skipped): every prefix
    of 2^k entries holds one rank from each of 2^k equal strata."""
    bits = max(1, (n - 1).bit_length())
    rev = [int(format(i, f"0{bits}b")[::-1], 2) for i in range(1 << bits)]
    return np.array([r for r in rev if r < n], np.int64)


def generate(mix: dict, seed: int, vocab: int) -> list:
    """The mix's requests for this seed, all due at the window's start."""
    if mix["arrivals"]["kind"] != "at_start":
        raise ValueError(f"unknown arrivals {mix['arrivals']['kind']!r}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7a11]))
    n = int(mix["requests"])
    order = spread_order(n)
    plen = quantiles(mix["prompt_tokens"], n)[order[::-1]]
    olen = quantiles(mix["output_tokens"], n)[order]
    return [Item(rng.integers(1, vocab, size=int(p)).astype(np.int32), int(o))
            for p, o in zip(plen, olen)]


def describe(mix: dict) -> str:
    q = [quantiles(mix[k], 1001) for k in ("prompt_tokens", "output_tokens")]
    return (f"{mix['requests']} requests, all due at 0, prompt median {int(np.median(q[0]))} "
            f"[{q[0].min()}, {q[0].max()}], output median {int(np.median(q[1]))} "
            f"[{q[1].min()}, {q[1].max()}]")
