"""The traffic generator: one seed gives the same requests in any process;
seeds differ only in order and token ids, never in the work offered."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 12345  # wider than 32 signed bits, as the driver's are


def digest(mix_name, seed, seconds):
    reqs = traffic.make_requests(traffic.load_mix(mix_name), seed, seconds,
                                 50304)
    h = hashlib.sha256()
    for r in reqs:
        h.update(np.float64(r.due).tobytes())
        h.update(np.int64(r.n_new).tobytes())
        h.update(r.prompt.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("mix", ["chat", "batch", "rag"])
def test_same_seed_same_requests_across_processes(mix):
    code = ("import sys; sys.path.insert(0, sys.argv[1]);"
            "from tests.bench.test_bench_traffic import digest;"
            f"print(digest({mix!r}, {SEED}, 40.0))")
    outs = set()
    for hashseed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hashseed)
        outs.add(subprocess.run(
            [sys.executable, "-c", code, str(ROOT)], env=env, cwd=ROOT,
            capture_output=True, text=True, check=True).stdout.strip())
    assert outs == {digest(mix, SEED, 40.0)}


def test_open_loop_offers_the_same_work_for_every_seed():
    mix = traffic.load_mix("chat")
    a = traffic.make_requests(mix, SEED, 40.0, 50304)
    b = traffic.make_requests(mix, 7, 40.0, 50304)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 40.0)
    for reqs in (a, b):
        due = np.array([r.due for r in reqs])
        assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 40.0
    gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs]), 9))
    # the multisets of lengths agree; the order and the ids do not
    for key in (lambda r: len(r.prompt), lambda r: r.n_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2  # each drops one gap
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi and r.prompt.min() >= 2 for r in a)


def test_lengths_follow_the_stated_distributions():
    ln = traffic.quantile_lengths({"dist": "lognormal", "median": 256,
                                   "sigma": 0.8, "min": 16, "max": 768},
                                  1001)
    assert np.median(ln) == 256 and ln.min() >= 16 and ln.max() == 768
    un = traffic.quantile_lengths({"dist": "uniform", "min": 8, "max": 48},
                                  410)
    assert sorted(set(un)) == list(range(8, 49))
    assert np.bincount(un)[8:].min() == np.bincount(un)[8:].max() == 10


def test_closed_loop_pool():
    mix = traffic.load_mix("batch")
    reqs = traffic.make_requests(mix, SEED, 40.0, 31360)
    assert len(reqs) == mix["pool"] and all(r.due == 0.0 for r in reqs)
    assert max(r.prompt.max() for r in reqs) < 31360
