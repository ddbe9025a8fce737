"""The reduction from a profiler trace to device metrics, on a hand-made
trace with known answers and on three decode rounds recorded from a TPU
v5e (``data/v5e_decode_rounds.json``: OLMo-1B, four servers on one chip,
16 sessions decoding)."""
import json
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data" / "v5e_decode_rounds.json"
DEV = "/device:TPU:0"


def test_synthetic_busy_idle_and_attribution():
    ms = 1_000_000  # ns
    mods = {DEV: [("jit_hop(1)", 0, 10 * ms), ("jit_hop(2)", 5 * ms, 20 * ms),
                  ("jit_tail(3)", 30 * ms, 40 * ms),
                  ("jit_hop(4)", 45 * ms, 70 * ms)]}  # crosses the window end
    host = [("decode_round", 0, 25 * ms), ("retire", 25 * ms, 28 * ms)]
    dt = T.clip((0, 50 * ms), host, mods, {DEV: []})
    assert dt.devices == [DEV]
    assert dt.window_s == pytest.approx(0.050)
    assert dt.busy_s() == pytest.approx(0.020 + 0.010 + 0.005)
    assert dt.module_s("jit_hop") == pytest.approx(0.010 + 0.015 + 0.005)
    assert dt.module_count("jit_hop") == 3
    idle = dict(dt.idle_by_host_span())
    # gap 20-30 ms: middle at 25 ms, where "retire" began; gap 40-45: none
    assert idle == pytest.approx({"retire": 0.010, "loop": 0.005})


def test_op_and_module_names():
    assert T.module_base("jit_hop(17628723615789005104)") == "jit_hop"
    assert T.op_label("%fusion.133 = (bf16[4,16,1024,16,128]{4,3,2,1,0}, "
                      "bf16[4]) fusion(...)") == \
        "fusion.133 bf16[4,16,1024,16,128]"
    assert T.op_label("%while.15 = (s32[]{:T(128)}, bf16[16,1,2048]) while") \
        == "while.15 s32[]"


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(DATA.read_text())
    return T.clip(tuple(d["window_ns"]), d["host"], d["modules"], d["ops"])


def test_recorded_v5e_rounds(recorded):
    dt = recorded
    assert dt.devices == [DEV]
    # the window spans three 35 ms decode rounds of four hops each
    assert 0.10 < dt.window_s < 0.12
    assert dt.module_count("jit_hop") == 12
    hop_s = dt.module_s("jit_hop")
    assert 0.05 < hop_s < dt.busy_s() <= dt.window_s
    idle = dt.idle_by_host_span()
    assert sum(v for _, v in idle) == pytest.approx(dt.window_s - dt.busy_s())
    assert {n for n, _ in idle} <= {"decode_round", "loop"}
    ops = dt.top_ops()
    assert 1 <= len(ops) <= 10 and ops == sorted(ops, key=lambda t: -t[1])
    assert ops[0][0].startswith("jit_hop/")


def test_anchors_put_a_trace_without_host_events_on_the_loop_clock():
    # the trace's clock runs 7.3 s ahead of the loop's; in loop ms: anchor
    # A runs 100.05-100.15 (host bracket 100.0-100.2), a hop 101-111 and
    # another 120-130, anchor B 130.15-130.25 (bracket 130.1-130.3)
    def ns(t_ms):
        return int(round((t_ms * 1e-3 + 7.3) * 1e9))

    anchor = f"jit_{T.ANCHOR}(5)"
    mods = {DEV: [(anchor, ns(100.05), ns(100.15)),
                  ("jit_hop(1)", ns(101), ns(111)),
                  ("jit_hop(2)", ns(120), ns(130)),
                  (anchor, ns(130.15), ns(130.25))]}
    spans = [("decode_round", 0.1005, 0.112), ("retire", 0.112, 0.118),
             ("decode_round", 0.119, 0.1305), ("loop_setup", 0.1, 0.2)]
    dt = T.on_loop_clock(mods, {DEV: []}, spans,
                         [(0.1, 0.1002), (0.1301, 0.1303)])
    assert dt.stretch == pytest.approx((0.10015, 0.13015), abs=1e-9)
    assert dt.window_s == pytest.approx(0.030, abs=1e-9)
    assert dt.skew_s == pytest.approx(0.0, abs=1e-9)
    # the anchors lie outside the window; the hops are what ran in it
    assert dt.module_count(f"jit_{T.ANCHOR}") == 0
    assert dt.busy_s() == pytest.approx(0.020, abs=1e-9)
    idle = dict(dt.idle_by_host_span(k=None))
    # 100.15-101 in the first decode_round, 111-120 (middle 115.5) in
    # retire, 130-130.15 in the second decode_round
    assert idle == pytest.approx({"decode_round": 0.00100, "retire": 0.009},
                                 abs=1e-9)


def test_a_trace_without_anchors_is_refused():
    with pytest.raises(RuntimeError, match="anchor"):
        T.on_loop_clock({DEV: [("jit_hop(1)", 0, 10)]}, {DEV: []}, [],
                        [(0.0, 0.1), (1.0, 1.1)])
