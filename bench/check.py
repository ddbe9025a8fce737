"""Whether the timed path computed the right logits and served the right
tokens.

Before the window, a sample of the run's requests is drawn from the seed:
the one with most tokens to serve, then others, until the sample holds
``MIN_SAMPLE_TOKENS`` served tokens and ``MIN_SAMPLE_REQUESTS`` requests
(so that more than one cache row is checked).  While the window runs, the
loop keeps the logits the program computed for each of their served
tokens (``Session.last_logits``, read after the round that produced the
token).  After the window has closed and the program's state is freed,
the configuration's plain reference runs once over each sampled prompt
followed by the tokens the program served, in float32 at the highest
matmul precision, on weights it makes again from the seed.  Two numbers
are read; each that the configuration file gives a limit is compared:

- ``logit_err_max``: at each served position, the root mean square over
  the vocabulary of the program's logits minus the reference's, in units
  of the reference row's standard deviation; the largest over the sample.
  It sees a path computed in a lower precision than the configuration
  states, a cache that is not written, a hop or a row left out.
- ``served_gap_max``: how far a served token's reference logit lies below
  the reference's best, in the same units; the largest over the sample.
  Every served token was greedy, so it sees a token altered after its
  logits were computed.

The control (:func:`check_control`, read on the chip by
``bench/control.py``) puts the reference computed in a lower precision in
the program's place and goes through the same :func:`verdict`.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.stats import Record

# served tokens and requests the sample gathers at the least
MIN_SAMPLE_TOKENS = 400
MIN_SAMPLE_REQUESTS = 3
NUMBERS = ("logit_err_max", "served_gap_max")


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def choose(requests: Sequence, seed: int,
           min_tokens: int = MIN_SAMPLE_TOKENS,
           min_requests: int = MIN_SAMPLE_REQUESTS) -> List[int]:
    """The rids of the sample, drawn before the window: the request with
    most tokens to serve, then others in an order drawn from the seed.
    (A closed loop's first offers carry the rids of its pool.)"""
    longest = max(requests, key=lambda r: (r.n_new, len(r.prompt), -r.rid))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    out, n = [longest.rid], longest.n_new
    for i in rng.permutation(len(requests)):
        if n >= min_tokens and len(out) >= min_requests:
            break
        r = requests[int(i)]
        if r.rid != longest.rid:
            out.append(r.rid)
            n += r.n_new
    return out


def sampled(records: Sequence[Record], rids: Sequence[int]) -> List[Record]:
    """The sampled requests that finished, in sample order."""
    by_rid = {r.rid: r for r in records if r.done}
    return [by_rid[i] for i in rids if i in by_rid]


def padded(rec: Record, seq_pad: int, rows_pad: int):
    """(tokens, rows, served) of one request, padded to fixed shapes so the
    reference compiles once per run: tokens to ``seq_pad`` (causal, so the
    tail changes no row read), rows to ``rows_pad`` (repeating the last)."""
    served = np.asarray(rec.tokens[: rec.n_new], np.int64)
    toks = np.concatenate([np.asarray(rec.prompt, np.int64), served[:-1]])
    rows = rec.prompt_len - 1 + np.arange(len(served))
    toks = np.pad(toks, (0, seq_pad - len(toks)))
    rows = np.pad(rows, (0, rows_pad - len(rows)), mode="edge")
    return toks, rows, served


def reference_logits(ref, m: dict, params, recs: Sequence[Record],
                     seq_pad: int, rows_pad: int, lowp=None
                     ) -> List[np.ndarray]:
    """The reference's logits at each served token's position, per
    request."""
    out = []
    for rec in recs:
        toks, rows, served = padded(rec, seq_pad, rows_pad)
        out.append(ref.logits_at(m, params, toks, rows, lowp=lowp)[
            : len(served), : m["vocab_size"]])
    return out


def served_gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Per position: how far the chosen token's reference logit lies below
    the reference's best, in units of that row's standard deviation over
    the vocabulary."""
    best = ref_logits.max(axis=1)
    at = ref_logits[np.arange(len(chosen)), chosen]
    return (best - at) / ref_logits.std(axis=1)


def logit_errors(ref_logits: np.ndarray, got: np.ndarray) -> np.ndarray:
    """Per position: the root mean square of ``got`` minus the reference
    over the vocabulary, in units of the reference row's standard
    deviation."""
    d = got.astype(np.float64) - ref_logits
    return np.sqrt(np.mean(d * d, axis=1)) / ref_logits.std(axis=1)


def readings(exact: Sequence[np.ndarray], got: Sequence[np.ndarray],
             chosen: Sequence[np.ndarray]) -> Dict[str, float]:
    """The numbers compared, over every position of every sampled request:
    ``exact`` the reference's logits, ``got`` those of the path under test
    and ``chosen`` the tokens it served.  A request whose logits were not
    all kept reads infinitely far off."""
    err, gap = 0.0, 0.0
    for ex, g, ch in zip(exact, got, chosen):
        gap = max(gap, float(served_gaps(ex, ch).max()))
        if g is None or g.shape != ex.shape:
            err = float("inf")
        else:
            err = max(err, float(logit_errors(ex, g).max()))
    return {"logit_err_max": err, "served_gap_max": gap}


def verdict(values: Optional[Dict[str, float]], limits: dict,
            what: str = "") -> Tuple[bool, dict, List[str]]:
    """(correct, checks, lines): each number the configuration gives a
    limit beside it; a number with no limit yet (none has been set from
    chip readings) is printed as a reading only.  No numbers (nothing
    sampled finished) is not correct."""
    checks, lines = {}, []
    for name in NUMBERS:
        v = None if values is None else values[name]
        if name not in limits:
            lines.insert(0, f"reading {name} {v!r} (no limit set){what}")
            continue
        limit = float(limits[name])
        checks[name] = {"value": v, "limit": limit}
        lines.append(f"check {name} {v!r} limit {limit!r}{what}")
    correct = values is not None and all(
        c["value"] <= c["limit"] for c in checks.values())
    return correct, checks, lines


def check_run(ref, m: dict, seed: int, recs: Sequence[Record],
              seq_pad: int, rows_pad: int, limits: dict):
    """:func:`verdict` on the program's own logits and served tokens."""
    if not recs:
        return verdict(None, limits, " (no sampled request finished)")
    exact = reference_logits(ref, m, ref.make_params(m, seed), recs,
                             seq_pad, rows_pad)
    values = readings(exact, [r.logits for r in recs],
                      [np.asarray(r.tokens[: r.n_new]) for r in recs])
    return verdict(values, limits,
                   f" (over {sum(r.n_new for r in recs)} served tokens of "
                   f"{len(recs)} requests)")


def check_control(ref, m: dict, seed: int, recs: Sequence[Record],
                  seq_pad: int, rows_pad: int, limits: dict, kind: str):
    """:func:`verdict` with the control in the program's place: the
    reference with every matrix and its input activations rounded to
    ``kind`` ("int8" or "fp8"), its logits at each position of the same
    prompts and served tokens standing for the program's and its own best
    token for the served one.  Returns (correct, checks, per-position
    logit errors)."""
    exact = reference_logits(ref, m, ref.make_params(m, seed), recs,
                             seq_pad, rows_pad)
    low = reference_logits(ref, m, ref.quantized_params(m, seed, kind),
                           recs, seq_pad, rows_pad, lowp=kind)
    values = readings(exact, low, [lo.argmax(axis=1) for lo in low])
    correct, checks, _ = verdict(values, limits)
    errs = np.concatenate([logit_errors(ex, lo) for ex, lo in zip(exact, low)])
    return correct, checks, errs

