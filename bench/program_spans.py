"""The program's own spans (``repro.tracing``) over a traced run's stretch.

The program records its spans while the profiler runs, on
``time.perf_counter()``, the clock of the loop (``bench.loop``): a span's
loop second is ``t - run.loop.t0`` and its second in the device trace's
window is that less ``run.window[0]``.  So the anchors that put the trace
on the loop's clock put the program's spans on it too.

Where the program has no tracer, or recorded no span in the stretch,
every function here returns None.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

OUTSIDE = "outside"  # idle under no program span


def in_stretch(run) -> Optional[list]:
    """The program's spans that began and ended inside the stretch, with
    ``start`` and ``end`` in window seconds."""
    try:
        from repro import tracing
    except ImportError:
        return None
    lo, hi = run.window
    t0 = run.loop.t0
    out = [s._replace(start=s.start - t0 - lo, end=s.end - t0 - lo)
           for s in tracing.spans()
           if lo <= s.start - t0 and s.end - t0 <= hi]
    return out or None


def _gap_chains(run, spans) -> Optional[List[Tuple[float, list]]]:
    """Each device idle gap in the stretch as (seconds over the number of
    chips, the ``spans`` open at its middle, innermost first)."""
    t = run.trace
    if t is None or not t.devices or spans is None:
        return None
    spans = sorted(spans, key=lambda s: s.start)
    starts = [s.start for s in spans]
    by_id = {s.id: s for s in spans}
    out = []
    for d in t.devices:
        iv = t.busy_intervals(d)
        edges = np.concatenate([[0.0], iv.ravel(), [t.window_s]])
        for a, b in edges.reshape(-1, 2):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            # spans nest, so every span open at `mid` is the last one begun
            # before it or an ancestor of that one
            i = bisect.bisect_right(starts, mid) - 1
            s = spans[i] if i >= 0 else None
            while s is not None and s.end < mid:
                s = by_id.get(s.parent)
            chain = []
            while s is not None:
                chain.append(s)
                s = by_id.get(s.parent)
            out.append(((b - a) / len(t.devices), chain))
    return out


def idle_by_program_span(run, k: Optional[int] = None) -> Optional[List]:
    """Device idle seconds in the stretch, summed by the innermost program
    span open at the middle of each gap (``outside`` where none was),
    averaged over the chips; the ``k`` largest (all where ``k`` is
    None)."""
    gaps = _gap_chains(run, in_stretch(run))
    if gaps is None:
        return None
    tot: Dict[str, float] = defaultdict(float)
    for sec, chain in gaps:
        tot[chain[0].name if chain else OUTSIDE] += sec
    return [[n, v] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_under(run, name: str) -> Optional[Tuple[float, int]]:
    """(device idle seconds whose gap's middle falls inside a program span
    ``name``, averaged over the chips; the number of such spans in the
    stretch)."""
    spans = in_stretch(run)
    gaps = _gap_chains(run, spans)
    n = sum(1 for s in spans or () if s.name == name)
    if gaps is None or not n:
        return None
    return (sum(sec for sec, chain in gaps
                if any(s.name == name for s in chain)), n)


def count_ratio(run, name: str, num: str, den: str) -> Optional[float]:
    """Sum of attribute ``num`` over sum of ``den`` across the program
    spans ``name`` in the stretch, in percent."""
    spans = [s for s in in_stretch(run) or () if s.name == name]
    total = sum(s.attrs[den] for s in spans)
    if not total:
        return None
    return sum(s.attrs[num] for s in spans) / total * 100.0
