"""The check that decides ``correct``, driven end to end at a size the
CPU holds: a sound run passes; the control (the reference with weights and
activations in int8, and in float8 e4m3) fails through the same verdict;
and so does the served path with each fault planted underneath it."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import bench.run as R
from bench import check, traffic
from tests.bench.tiny import CELL, ROOT, tiny

SEED = 2 ** 31 + 99


def run():
    bench, cfg, mix = tiny()
    res, lines, lr, recs = R.run_cell(bench, CELL, SEED, 1.0, False,
                                      cfg=cfg, mix=mix, log=lambda s: None)
    return res, lines, cfg, mix, recs


def test_sound_run_is_correct_and_control_is_not():
    res, lines, cfg, mix, recs = run()
    assert res["correct"] and res["failed"] == 0
    assert set(res["checks"]) == set(check.NUMBERS)
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    assert list(res)[-1] == "checks" and lines[-1].startswith("check ")
    # the logits compared are the timed path's own, on several rows
    assert len(recs) >= check.MIN_SAMPLE_REQUESTS
    for r in recs:
        assert r.logits.shape == (r.n_new, cfg["model"]["vocab_size"])
        assert list(r.logits.argmax(axis=1)) == r.tokens[: r.n_new]
    # the control in the program's place, judged by the same verdict
    ref, m = check.reference_module(cfg), cfg["model"]
    for kind in ("int8", "fp8"):
        correct, checks, _ = check.check_control(ref, m, SEED, recs, 96, 32,
                                                 cfg["check"], kind)
        assert not correct, (kind, checks)


def test_a_number_without_a_limit_is_read_not_compared():
    correct, checks, lines = check.verdict(
        {"logit_err_max": 9.0, "served_gap_max": 0.1},
        {"served_gap_max": 0.2})
    assert correct and list(checks) == ["served_gap_max"]
    assert lines[0].startswith("reading logit_err_max 9.0")
    assert not check.verdict(None, {"served_gap_max": 0.2})[0]


@pytest.mark.parametrize("mix", ["chat", "batch", "rag"])
def test_sample_is_drawn_before_the_window(mix):
    m = traffic.load_mix(mix)
    reqs = traffic.make_requests(m, 2 ** 33 + 5, 51.0, 1000)
    rids = check.choose(reqs, 2 ** 33 + 5)
    assert rids == check.choose(reqs, 2 ** 33 + 5) != check.choose(reqs, 7)
    assert reqs[rids[0]].n_new == max(r.n_new for r in reqs)
    assert len(set(rids)) == len(rids) >= check.MIN_SAMPLE_REQUESTS
    assert sum(reqs[i].n_new for i in rids) >= check.MIN_SAMPLE_TOKENS


def _state_unchanged(E, monkeypatch):
    orig = E.BlockServer.round_rows

    def rr(self, *a, **k):
        keep = jax.tree.map(jnp.copy, self.pool.tree)
        out = orig(self, *a, **k)
        self.pool.tree = keep
        return out

    monkeypatch.setattr(E.BlockServer, "round_rows", rr)


def _hop_left_out(E, monkeypatch):
    orig = E.BlockServer.round_rows

    def rr(self, h, *a, **k):
        return h if self.sid == 1 else orig(self, h, *a, **k)

    monkeypatch.setattr(E.BlockServer, "round_rows", rr)


def _half_the_rows_left_out(E, monkeypatch):
    orig = E.BlockServer.round_rows

    def rr(self, h, pos, encl, sor, ros, layer_active, **k):
        even = jnp.arange(layer_active.shape[1]) % 2 == 0
        return orig(self, h, pos, encl, sor, ros, layer_active & even[None],
                    **k)

    monkeypatch.setattr(E.BlockServer, "round_rows", rr)


def _token_altered(E, monkeypatch):
    orig = E.make_round_tail

    def make(cfg):
        tail = orig(cfg)

        def altered(*a):
            toks, logits = tail(*a)
            return toks.at[0].set((toks[0] + 1) % cfg.vocab_size), logits

        return altered

    monkeypatch.setattr(E, "make_round_tail", make)


@pytest.mark.parametrize("fault", [_state_unchanged, _hop_left_out,
                                   _half_the_rows_left_out, _token_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_fault_in_the_served_path_is_not_correct(monkeypatch, fault):
    from repro.serving import engine as E

    fault(E, monkeypatch)
    res, _, _, _, _ = run()
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "olmo1b.chat", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "TPU" in p.stderr
