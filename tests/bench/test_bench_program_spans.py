"""The readers of the program's own spans (``bench.program_spans`` and the
four metrics on it), on a hand-made traced run with known answers: idle
gaps put down to the innermost program span open at their middle, idle
per round, and row-layer ratios."""
import sys

import pytest

from bench import program_spans as PS
from bench import readers
from bench import trace as T
from bench.loop import LoopResult
from repro import tracing
from repro.tracing import Span

MS = 1_000_000  # ns
T0, LO = 100.0, 1.0  # the loop's perf_counter origin; the stretch's start


def _span(i, parent, name, a_ms, b_ms, **attrs):
    """A program span from window ms ``a_ms`` to ``b_ms``."""
    return Span(i, parent, name, T0 + LO + a_ms * 1e-3, T0 + LO + b_ms * 1e-3,
                attrs)


SPANS = [
    _span(0, None, "decode_round", -5, 3),  # begun before the stretch
    _span(1, None, "decode_round", 5, 35),
    _span(2, 1, "round.prepare", 6, 12),
    _span(3, 1, "round.hop", 12, 30, row_layers_useful=6,
          row_layers_computed=16),
    _span(4, 1, "round.sync", 31, 34),
    _span(5, None, "prefill_round", 40, 85),
    _span(6, 5, "prefill.hop", 41, 50, tok_layers_useful=30,
          tok_layers_computed=400),
    _span(7, 6, "prefill.stage", 41.5, 46),
    _span(8, 5, "prefill.finalize", 86, 88),
    _span(9, None, "decode_round", 92, 99),
    _span(10, 9, "round.hop", 93, 98, row_layers_useful=2,
          row_layers_computed=16),
]


def _run(spans, monkeypatch):
    monkeypatch.setattr(tracing, "spans", lambda: list(spans))
    # chip 0 idles 10-20, 30-60 and 70-90 ms; chip 1 never idles
    mods = {"/device:TPU:0": [("jit_hop(1)", 0, 10 * MS),
                              ("jit_hop(1)", 20 * MS, 30 * MS),
                              ("jit_step(2)", 60 * MS, 70 * MS),
                              ("jit_hop(1)", 90 * MS, 100 * MS)],
            "/device:TPU:1": [("jit_hop(1)", 0, 100 * MS)]}
    dt = T.clip((0, 100 * MS), [], mods, {d: [] for d in mods})
    loop = LoopResult(records=[], spans=[], rounds=[], prefills=[], t0=T0)
    return readers.RunView(loop=loop, window=(LO, LO + 0.1), model={},
                           deployment={}, peaks=None, trace=dt)


def test_idle_goes_to_the_innermost_program_span(monkeypatch):
    run = _run(SPANS, monkeypatch)
    # gap middles: 15 ms in round.hop, 45 in prefill.stage, 80 in
    # prefill_round alone (its hop has ended, finalize not begun); halved
    # over the two chips
    assert dict(PS.idle_by_program_span(run)) == pytest.approx(
        {"round.hop": 0.005, "prefill.stage": 0.015, "prefill_round": 0.010})
    assert PS.idle_under(run, "decode_round") == pytest.approx((0.005, 2))
    assert PS.idle_under(run, "prefill_round") == pytest.approx((0.025, 1))


@pytest.mark.parametrize("name,value", [
    ("round_idle_ms", 2.5), ("round_idle_ms.batch", 2.5),
    ("prefill_idle_ms", 25.0), ("prefill_idle_ms.batch", 25.0),
    ("round_row_util", 25.0), ("round_row_util.batch", 25.0),
    ("prefill_row_util", 7.5), ("prefill_row_util.batch", 7.5)])
def test_metrics_read_known_values(monkeypatch, name, value):
    assert readers.reader(name)(_run(SPANS, monkeypatch)) == \
        pytest.approx(value)


def test_a_gap_outside_every_program_span(monkeypatch):
    run = _run([s for s in SPANS if s.id not in (5, 6, 7, 8)], monkeypatch)
    idle = dict(PS.idle_by_program_span(run))
    assert idle[PS.OUTSIDE] == pytest.approx(0.025)
    assert readers.reader("prefill_idle_ms")(run) is None


@pytest.mark.parametrize("name", ["round_idle_ms", "prefill_idle_ms",
                                  "round_row_util", "prefill_row_util"])
def test_nothing_to_read_without_program_spans(monkeypatch, name):
    # no span recorded (no profiler session reached the program)
    assert readers.reader(name)(_run([], monkeypatch)) is None
    # a program without the tracer, as before it existed
    monkeypatch.delattr(sys.modules["repro"], "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert readers.reader(name)(_run(SPANS, monkeypatch)) is None
