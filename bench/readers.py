"""What a per-layer metric's reader is given, and how it is found.

The reader of metric ``<name>`` is ``bench/metrics/<name>.py``; a metric
named ``<base>.<variant>`` (the same quantity in cells that move another
end-to-end metric) is read by ``bench/metrics/<base>.py`` unless it has a
file of its own.  A reader defines ``read(run) -> float | None`` and returns
None where it finds nothing to read; the metric is then left out.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List, Optional, Tuple

from bench.loop import LoopResult
from bench.trace import DeviceTrace

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


@dataclass
class RunView:
    """A traced run, seen over its traced stretch: stopping the profiler
    stalls the loop for seconds, so nothing after the stretch is read."""

    loop: LoopResult
    window: Tuple[float, float]  # the traced stretch, in window seconds
    model: dict
    deployment: dict
    peaks: Optional[dict]
    trace: Optional[DeviceTrace] = None
    # the configuration's reference module (``bench/references/``), which
    # counts its architecture's costs: ``round_needs``, ``token_model_flops``
    arch: Optional[ModuleType] = None
    # the configuration's ``wan``: the round trips laid on each token
    wan: Optional[dict] = None

    def spans(self, name: str) -> List[Tuple[float, float]]:
        """(start, end) of the host spans ``name`` that ran in the
        stretch."""
        lo, hi = self.window
        return [(s, e) for n, s, e in self.loop.spans
                if n == name and lo <= s and e <= hi]

    def rounds(self) -> List[List[int]]:
        """Positions decoded by each round begun in the stretch."""
        lo, hi = self.window
        return [p for s, p in self.loop.rounds if lo <= s < hi]


def reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    if not path.exists():
        path = METRICS_DIR / f"{name.split('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
