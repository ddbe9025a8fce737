"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) states the loop, the arrival rate or
the number of outstanding requests, and the distributions of prompt and
output lengths.  Every seed gets the SAME multiset of lengths and
inter-arrival gaps, drawn at evenly spaced quantiles of the stated
distributions; the seed only shuffles their order and draws the token ids.
So two seeds offer the same work in another order, and run-to-run spread
measures the system, not the luck of the draw.

Open loop (``"loop": "open"``): ``round(rate * seconds)`` requests, due at
``t = 0`` and then after each gap, the gaps scaled to sum to the window, so
every request is due inside it.  Closed loop (``"loop": "closed"``):
``outstanding`` requests are due at ``t = 0``; the harness issues the next
one from a cycled pool of ``pool`` lengths whenever one completes.
"""
from __future__ import annotations

import json
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Request:
    """One request as offered: when it is due (s from the window start; the
    closed loop overwrites it at issue) and what it asks for."""

    rid: int
    due: float
    prompt: np.ndarray  # int32 token ids
    n_new: int
    client: int = 0


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _norm_ppf(q: np.ndarray) -> np.ndarray:
    inv = statistics.NormalDist().inv_cdf
    return np.asarray([inv(float(x)) for x in q])


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``, clipped to
    [min, max] and rounded: a lognormal (``median``, ``sigma``) or a
    uniform integer range."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * _norm_ppf(q))
    elif dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def exponential_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` gaps at the quantiles of an exponential, scaled to sum to
    ``seconds`` (a Poisson process's gaps, drawn evenly)."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (seconds / g.sum())


def make_requests(mix: dict, seed: int, seconds: float,
                  vocab_size: int) -> List[Request]:
    """The requests of one run.  Open loop: every request due in the
    window, in due order.  Closed loop: the cycled pool, all due at 0 (the
    harness re-stamps each at issue)."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
        gaps = rng.permutation(exponential_gaps(n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif mix["loop"] == "closed":
        n = int(mix["pool"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    p_len = rng.permutation(quantile_lengths(mix["prompt_tokens"], n))
    o_len = rng.permutation(quantile_lengths(mix["output_tokens"], n))
    # ids 0 and 1 are reserved (pad / bos), as in the program's own traffic
    return [Request(rid=i, due=float(due[i]),
                    prompt=rng.integers(2, vocab_size, size=int(p_len[i]),
                                        dtype=np.int64).astype(np.int32),
                    n_new=int(o_len[i]))
            for i in range(n)]
