"""End-to-end reductions: percentiles over all requests and all gaps, and
the WAN laid along each request's route."""
import numpy as np
import pytest

from bench import readers, stats
from bench.loop import LoopResult
from bench.stats import Record

WAN = {"rtt_s": [0.010, 0.020, 0.040], "bandwidth_bps": 1e9,
       "embed_bytes": 4096}
SER = 2 * 8 * 4096 / 1e9


def rec(rid, due, times, route=(0, 1, 2), prompt=100):
    return Record(rid=rid, due=due, prompt_len=prompt, n_new=len(times),
                  route=route, token_times=list(times),
                  tokens=list(range(len(times))))


def test_wan_is_summed_along_the_route():
    pre, tok = stats.wan_delays(WAN, (0, 2), 100)
    assert pre == pytest.approx(0.050 + 2 * SER * 100)
    assert tok == pytest.approx(0.050 + 2 * SER)
    r = rec(0, 1.0, [1.5, 1.6, 1.8], route=(0, 2))
    assert stats.ttfts([r], WAN)[0] == pytest.approx(0.5 + pre)
    np.testing.assert_allclose(stats.inter_token_gaps([r], WAN),
                               [0.1 + tok, 0.2 + tok])
    # another route pays its own round trips
    r1 = rec(1, 1.0, [1.5], route=(1,))
    assert stats.ttfts([r1], WAN)[0] == pytest.approx(0.5 + 0.020
                                                      + SER * 100)


def test_percentiles_take_every_request_and_every_gap():
    nowan = {"rtt_s": [0.0] * 3, "bandwidth_bps": 1e30, "embed_bytes": 1}
    # one long request with small gaps, many short ones with one big gap
    recs = [rec(0, 0.0, 0.1 + 0.01 * np.arange(100))]
    recs += [rec(i, 0.0, [0.2 * i, 0.2 * i + 0.5]) for i in range(1, 20)]
    ttft = stats.ttfts(recs, nowan)
    assert len(ttft) == 20
    assert stats.percentile(ttft, 90) == pytest.approx(
        np.percentile([0.1] + [0.2 * i for i in range(1, 20)], 90))
    gaps = stats.inter_token_gaps(recs, nowan)
    assert len(gaps) == 99 + 19  # pooled, not averaged per request
    assert stats.percentile(gaps, 99) == pytest.approx(
        np.percentile(np.r_[np.full(99, 0.01), np.full(19, 0.5)], 99))
    out = stats.end_to_end(["ttft_p90_ms", "itl_p99_ms", "setup_s"], recs,
                           nowan, 10.0)
    assert set(out) == {"ttft_p90_ms", "itl_p99_ms"}
    assert out["ttft_p90_ms"][0] == pytest.approx(
        stats.percentile(ttft, 90) * 1e3)


def test_tokens_per_s_counts_the_window_only():
    recs = [rec(0, 0.0, [0.5, 1.0, 9.9, 10.0, 10.5])]
    assert stats.tokens_in_window(recs, 10.0) == 3
    assert stats.end_to_end(["tokens_per_s"], recs, WAN, 10.0)[
        "tokens_per_s"][0] == pytest.approx(0.3)
    assert stats.percentile(np.zeros(0), 90) is None


def _view(recs, window, wan=WAN):
    loop = LoopResult(records=recs, spans=[], rounds=[], prefills=[])
    return readers.RunView(loop=loop, window=window, model={},
                           deployment={}, peaks=None, wan=wan)


def test_itl_per_layer_reads_the_end_to_end_tail_inside_the_stretch():
    read = readers.reader("itl_p99_ms.chat")
    recs = [rec(0, 0.0, 0.1 + 0.02 * np.arange(200))]
    recs += [rec(i, 0.0, [0.3 * i, 0.3 * i + 0.4], route=(1,))
             for i in range(1, 12)]
    # a stretch holding every token reads what the window's tail reads
    assert read(_view(recs, (0.0, 10.0))) == pytest.approx(
        stats.end_to_end(["itl_p99_ms"], recs, WAN, 10.0)["itl_p99_ms"][0])
    # a gap counts only where both its tokens lie in the stretch
    inside = [r for r in recs if r.rid == 0 or 1.0 <= 0.3 * r.rid
              and 0.3 * r.rid + 0.4 <= 3.0]
    ts = np.asarray(recs[0].token_times)
    want = np.r_[np.full(((ts >= 1.0) & (ts <= 3.0)).sum() - 1,
                         0.02 + stats.wan_delays(WAN, (0, 1, 2), 100)[1]),
                 np.full(len(inside) - 1,
                         0.4 + stats.wan_delays(WAN, (1,), 100)[1])]
    assert read(_view(recs, (1.0, 3.0))) == pytest.approx(
        np.percentile(want, 99) * 1e3)


def test_itl_per_layer_reads_nothing_without_a_gap_in_the_stretch():
    read = readers.reader("itl_p99_ms.chat")
    recs = [rec(0, 0.0, [0.5, 1.5]), rec(1, 0.0, [2.0])]
    assert read(_view(recs, (1.0, 3.0))) is None
    assert read(_view(recs, (0.0, 3.0), wan=None)) is None
