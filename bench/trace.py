"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Modules`` line has one event per program run (``jit_hop(<hash>)``,
``jit_step(...)``, ...) and whose ``XLA Ops`` line has one event per
operation, on the trace's own clock.  The host's events do not come back
in v5e traces of this harness, so the trace is put on the loop's clock by
an anchor: a tiny program (``bench_anchor``) that the harness runs, and
waits for, once as the traced stretch opens and once as it closes.  The
window is the device time between the two anchors.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HOST_SPANS = ("admit", "try_admit", "prefill_round", "decode_round",
              "retire", "generator_idle")
ANCHOR = "bench_anchor"  # XLA calls its program jit_bench_anchor
_SHAPE = re.compile(r"=\s*\(?([a-z0-9]+\[[0-9,]*\])")


def module_base(name: str) -> str:
    """``jit_hop(1762...)`` -> ``jit_hop``."""
    return name.split("(", 1)[0]


def op_label(name: str) -> str:
    """``%fusion.133 = (bf16[4,16]{...}, ...) fusion(...)`` ->
    ``fusion.133 bf16[4,16]``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = _SHAPE.search(name)
    return f"{head} {m.group(1)}" if m else head


@dataclass
class DeviceTrace:
    """Events of the traced window, in seconds from its start."""

    window_s: float
    modules: Dict[str, List[Tuple[str, float, float]]]  # device -> events
    ops: Dict[str, List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]]
    devices: List[str] = field(default_factory=list)
    # the window on the loop's clock, and how far the second anchor fell
    # from where the first one put it (seconds)
    stretch: Tuple[float, float] = (0.0, 0.0)
    skew_s: float = 0.0

    def busy_intervals(self, device: str) -> np.ndarray:
        """Merged (start, end) intervals in which a program ran."""
        iv = sorted((s, e) for _, s, e in self.modules.get(device, []))
        out: List[List[float]] = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return np.asarray(out, float).reshape(-1, 2)

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the chips."""
        per = [float(np.sum(iv[:, 1] - iv[:, 0])) for iv in
               (self.busy_intervals(d) for d in self.devices)]
        return float(np.mean(per)) if per else 0.0

    def module_s(self, base: str) -> float:
        """Device seconds of every run of the program ``base``, summed over
        the chips."""
        return sum(e - s for d in self.devices
                   for n, s, e in self.modules.get(d, [])
                   if module_base(n) == base)

    def module_count(self, base: str) -> int:
        return sum(1 for d in self.devices
                   for n, _, _ in self.modules.get(d, [])
                   if module_base(n) == base)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operations that took most device time, each named by
        its program and its HLO name and shape."""
        by_name: Dict[Tuple[str, str], float] = defaultdict(float)
        for d in self.devices:
            mods = sorted(self.modules.get(d, []), key=lambda t: t[1])
            starts = [m[1] for m in mods]
            for name, s, e in self.ops.get(d, []):
                i = bisect.bisect_right(starts, s) - 1
                prog = (module_base(mods[i][0])
                        if i >= 0 and mods[i][2] >= s else "?")
                by_name[prog, name] += e - s
        tot: Dict[str, float] = defaultdict(float)
        for (prog, name), v in by_name.items():
            tot[f"{prog}/{op_label(name)}"] += v
        return [[n, v] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_by_host_span(self, k: Optional[int] = 10) -> List[List]:
        """Device idle seconds in the window, summed by the host span open
        at the middle of each gap (``loop`` where none was), averaged over
        the chips; the ``k`` largest (all where ``k`` is None)."""
        spans = sorted(self.host, key=lambda t: t[1])
        starts = [s for _, s, _ in spans]
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            iv = self.busy_intervals(d)
            edges = np.concatenate([[0.0], iv.ravel(), [self.window_s]])
            for s, e in edges.reshape(-1, 2):
                if e <= s:
                    continue
                mid = 0.5 * (s + e)
                # the benchmark's spans do not nest: the one open at `mid`
                # is the last to start before it, if it has not ended
                i = bisect.bisect_right(starts, mid) - 1
                name = spans[i][0] if i >= 0 and spans[i][2] >= mid else \
                    "loop"
                tot[name] += (e - s) / len(self.devices)
        return [[n, v] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def align(dev_mod: Dict[str, list], anchors) -> Tuple[Tuple[float, float],
                                                     float, float]:
    """(window, origin, skew): the window is the device time between the
    end of the first anchor program and the start of the last, in trace
    ns; ``origin`` is the trace ns of the loop's second 0, which puts the
    first anchor's middle at the middle of its host bracket; ``skew`` is
    how many seconds the last anchor's middle lies after its own bracket's
    middle on that footing.  ``anchors`` are the host brackets
    ((a0, a1), (b0, b1)), in loop seconds, of the two anchor runs (from
    dispatch to the return of the wait)."""
    evs = sorted((s, e) for v in dev_mod.values() for n, s, e in v
                 if module_base(n) == f"jit_{ANCHOR}")
    if len(evs) < 2:
        raise RuntimeError(f"the trace holds {len(evs)} runs of the anchor "
                           f"program jit_{ANCHOR}; two are needed")
    (as_, ae), (bs, be) = evs[0], evs[-1]
    (a0, a1), (b0, b1) = anchors
    origin = 0.5 * (as_ + ae) - 0.5 * (a0 + a1) * 1e9
    skew = (0.5 * (bs + be) - origin) * 1e-9 - 0.5 * (b0 + b1)
    return (ae, bs), origin, skew


def load(path: str, spans, anchors) -> DeviceTrace:
    """Read one ``.xplane.pb``: its device programs and operations between
    the anchors (:func:`align`), and the loop's own host ``spans`` (name,
    start, end in loop seconds) moved onto the trace's clock, to which
    idle time is attributed."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev_mod: Dict[str, list] = {}
    dev_ops: Dict[str, list] = {}
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev_mod[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                           for ev in line.events]
                elif line.name == "XLA Ops":
                    dev_ops[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                           for ev in line.events]
    return on_loop_clock(dev_mod, dev_ops, spans, anchors)


def on_loop_clock(dev_mod, dev_ops, spans, anchors) -> DeviceTrace:
    """The device events between the anchors, with the loop's host spans
    put on the trace's clock."""
    window, origin, skew = align(dev_mod, anchors)
    host = [(n, origin + s * 1e9, origin + e * 1e9)
            for n, s, e in spans if n in HOST_SPANS]
    dt = clip(window, host, dev_mod, dev_ops)
    dt.stretch = ((window[0] - origin) * 1e-9, (window[1] - origin) * 1e-9)
    dt.skew_s = skew
    return dt


def clip(window: Tuple[float, float], host, dev_mod, dev_ops) -> DeviceTrace:
    """Events in ns on the trace's clock -> seconds in the window."""
    w0, w1 = window

    def cut(evs: Sequence[Tuple[str, float, float]]):
        return [(n, (max(s, w0) - w0) * 1e-9, (min(e, w1) - w0) * 1e-9)
                for n, s, e in evs if e > w0 and s < w1]

    devices = sorted(d for d, evs in dev_mod.items() if cut(evs))
    return DeviceTrace(window_s=(w1 - w0) * 1e-9,
                       modules={d: cut(v) for d, v in dev_mod.items()},
                       ops={d: cut(v) for d, v in dev_ops.items()},
                       host=cut(host), devices=devices)
