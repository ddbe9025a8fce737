"""A deployment on several chips: each server computes on a chip of its
own, serves the logits of the same deployment on one chip, and a run
whose exchange between chips is left out is not correct.  The four-chip
cases run in a child process with four virtual CPU devices; one chip
passes the program no device groups; the hop-gap reader on a hand-made
trace of two chips."""
import json
import os
import subprocess
import sys

import pytest

from bench import deploy, readers
from bench import trace as T
from bench.loop import LoopResult
from tests.bench.tiny import CELL, ROOT, tiny

MS = 1_000_000  # ns

_CHILD = r"""
import json

import jax
import numpy as np

import bench.run as R
from bench import check, deploy
from repro.core import shortest_path_route
from repro.serving import engine as E
from tests.bench.tiny import CELL, tiny

SEED = 2 ** 32 + 17


def cell(chips):
    bench, cfg, mix = tiny()
    cfg["deployment"].update(servers=4, blocks_per_server=1, chips=chips)
    return bench, cfg, mix


def served_logits(chips):
    # the same prompts through prefill and seven decode rounds, stepped
    # by hand so that both deployments run the same rounds
    bench, cfg, mix = cell(chips)
    ref = check.reference_module(cfg)
    params = ref.make_params(cfg["model"], SEED)
    system, _ = deploy.build(cfg, mix, params)
    route, _ = shortest_path_route(system.problem, system.alive_placement(),
                                   0)
    rng = np.random.default_rng(3)
    sids = [system.create_session(rng.integers(2, 512, size=L), 0, route, 8)
            for L in (9, 23, 40)]
    assert system.try_admit_sessions(sids) == sids
    while system.has_pending_prefill():
        system.prefill_round()
    out = []
    for _ in range(7):
        system.decode_round()
        out.append(np.stack([np.asarray(system.sessions[s].last_logits,
                                        np.float32) for s in sids]))
    return deploy.server_devices(system), np.stack(out)


def exchange_left_out():
    # hidden rows that arrive from another chip are not moved: the next
    # server computes on zeros in their place
    orig = E.BlockServer._local

    def local(self, h, *rest):
        moved = orig(self, h, *rest)
        if self._inputs_at is None or h.devices() <= self._inputs_at.device_set:
            return moved
        return (moved[0] * 0,) + tuple(moved[1:])

    E.BlockServer._local = local


homes, four = served_logits(4)
_, one = served_logits(1)
bench, cfg, mix = cell(4)
sound = R.run_cell(bench, CELL, SEED, 1.0, False, cfg=cfg, mix=mix,
                   log=lambda s: None)[0]
exchange_left_out()
fault = R.run_cell(bench, CELL, SEED, 1.0, False, cfg=cfg, mix=mix,
                   log=lambda s: None)[0]
print(json.dumps({
    "devices": len(jax.devices()),
    "homes": {str(k): v for k, v in homes.items()},
    "logit_diff_max": float(np.max(np.abs(four - one))),
    "sound": {"correct": sound["correct"], "checks": sound["checks"]},
    "fault": {"correct": fault["correct"], "checks": fault["checks"]}}))
"""


@pytest.fixture(scope="module")
def four_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", _CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_server_computes_on_a_chip_of_its_own(four_chips):
    assert four_chips["devices"] == 4
    assert four_chips["homes"] == {"0": [0], "1": [1], "2": [2], "3": [3]}


def test_four_chips_serve_the_logits_of_one(four_chips):
    # each server runs the same programs on one device either way and the
    # hidden rows are copied between chips, never reduced or recast, so
    # the logits agree bit for bit (as chip_smoke.py finds on a v5e)
    assert four_chips["logit_diff_max"] == 0.0


def test_a_sound_run_on_four_chips_is_correct(four_chips):
    assert four_chips["sound"]["correct"], four_chips["sound"]


def test_the_exchange_between_chips_left_out_is_not_correct(four_chips):
    got = four_chips["fault"]
    assert not got["correct"]
    assert any(c["value"] > c["limit"] for c in got["checks"].values())


def test_one_chip_passes_no_device_groups():
    bench, cfg, mix = tiny()
    assert cfg["deployment"]["chips"] == 1
    assert deploy.device_groups(cfg["deployment"]) is None
    from bench import check

    ref = check.reference_module(cfg)
    system, _ = deploy.build(cfg, mix, ref.make_params(cfg["model"], 5))
    assert system.device_groups == {}
    import jax

    d0 = jax.devices()[0].id
    assert deploy.server_devices(system) == {0: [d0], 1: [d0]}


@pytest.mark.parametrize("servers", [2, 8])
def test_servers_other_than_the_chips_raise(servers):
    dep = {"chips": 4, "servers": servers}
    with pytest.raises(ValueError, match=f"4 chips.*{servers} servers"):
        deploy.device_groups(dep)


def _hops_run(rounds, hops):
    """A traced run of two chips over 100 ms: decode rounds at window ms
    ``rounds`` and ``jit_hop`` runs ``(chip, start ms, end ms)``, beside a
    prefill program that no round holds."""
    mods = {"/device:TPU:0": [("jit_step(2)", 85 * MS, 95 * MS)],
            "/device:TPU:1": []}
    for chip, a, b in hops:
        mods[f"/device:TPU:{chip}"].append(("jit_hop(1)", a * MS, b * MS))
    dt = T.clip((0, 100 * MS), [], mods, {d: [] for d in mods})
    lo = 1.0  # the stretch's start, in loop seconds
    spans = [("decode_round", lo + a * 1e-3, lo + b * 1e-3)
             for a, b in rounds]
    loop = LoopResult(records=[], spans=spans, rounds=[], prefills=[])
    return readers.RunView(loop=loop, window=(lo, lo + 0.1), model={},
                           deployment={}, peaks=None, trace=dt)


def test_hop_gap_reads_the_gaps_between_hops_across_chips():
    read = readers.reader("hop_gap_ms")
    # round 1: hops on chips 0, 1, 0 with gaps of 2 and 3 ms; round 2:
    # chips 1, 0 with a gap of 1.5 ms; the prefill run is in no round
    run = _hops_run([(0, 30), (40, 80)],
                    [(0, 5, 10), (1, 12, 20), (0, 23, 27),
                     (1, 45, 50), (0, 51.5, 60)])
    assert read(run) == pytest.approx((5.0 + 1.5) / 2)


def test_hop_gap_reads_nothing_where_a_round_has_one_hop():
    read = readers.reader("hop_gap_ms")
    run = _hops_run([(0, 30), (40, 80)], [(0, 5, 10), (1, 45, 50)])
    assert read(run) is None
    run.trace = None
    assert read(run) is None
