"""Reduction from per-request records to the end-to-end metrics.

Times are seconds on the host clock from the window's start.  The WAN is
laid on this timeline and never slept (servers that share a host cannot
feel it): per eq. (1), a request's first token is later by the prefill
round trips of every hop of its route, and every later token by the token
round trips.  Slot occupancy therefore ignores WAN time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Record:
    """One request as the harness saw it."""

    rid: int
    due: float
    prompt_len: int
    n_new: int
    route: Tuple[int, ...] = ()
    seen: Optional[float] = None  # when the loop picked the arrival up
    granted: Optional[float] = None  # when the engine granted its slots
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)  # the served tokens
    failed: Optional[str] = None
    prompt: Optional[np.ndarray] = None
    # the logits behind each served token, kept for a sampled request only:
    # device rows while the window runs, (n_new, vocab) float32 after it
    logits: Optional[object] = None

    @property
    def done(self) -> bool:
        return self.failed is None and len(self.token_times) >= self.n_new


def wan_delays(wan: dict, route: Sequence[int], prompt_len: int
               ) -> Tuple[float, float]:
    """(prefill, per-token) WAN seconds along ``route``: each hop is a
    client-to-server round trip plus the serialisation of the hidden
    states at ``bandwidth_bps`` (the scattered-scenario arithmetic)."""
    ser = 2 * 8 * float(wan["embed_bytes"]) / float(wan["bandwidth_bps"])
    prop = sum(float(wan["rtt_s"][j]) for j in route)
    return prop + ser * prompt_len * len(route), prop + ser * len(route)


def ttfts(records: Sequence[Record], wan: dict) -> np.ndarray:
    """First-token delivery minus due time, WAN included, for every request
    that delivered a first token."""
    out = [r.token_times[0] - r.due + wan_delays(wan, r.route, r.prompt_len)[0]
           for r in records if r.token_times]
    return np.asarray(out, float)


def inter_token_gaps(records: Sequence[Record], wan: dict) -> np.ndarray:
    """Every gap between consecutive tokens of every request, WAN
    included."""
    out = []
    for r in records:
        if len(r.token_times) > 1:
            tok = wan_delays(wan, r.route, r.prompt_len)[1]
            out.append(np.diff(np.asarray(r.token_times)) + tok)
    return np.concatenate(out) if out else np.zeros(0)


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    """numpy's linear-interpolation percentile; None on no samples."""
    return float(np.percentile(values, q)) if len(values) else None


def tokens_in_window(records: Sequence[Record], seconds: float) -> int:
    return sum(1 for r in records for t in r.token_times if 0.0 <= t < seconds)


def end_to_end(names: Sequence[str], records: Sequence[Record], wan: dict,
               seconds: float) -> dict:
    """The cell's end-to-end metrics other than ``setup_s``, in ms or
    tokens/s."""
    out = {}
    for name in names:
        if name == "ttft_p90_ms":
            v = percentile(ttfts(records, wan), 90)
            out[name] = (None if v is None else v * 1e3, "ms")
        elif name == "itl_p99_ms":
            v = percentile(inter_token_gaps(records, wan), 99)
            out[name] = (None if v is None else v * 1e3, "ms")
        elif name == "tokens_per_s":
            out[name] = (tokens_in_window(records, seconds) / seconds,
                         "tokens/s")
        elif name != "setup_s":
            raise ValueError(f"no reduction for end-to-end metric {name!r}")
    return out
