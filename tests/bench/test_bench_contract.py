"""BENCHMARK.json against the files the harness finds by name."""
import dataclasses
import json
import re

import pytest

from bench import deploy, readers, traffic
from repro.configs.base import ModelConfig
from tests.bench.tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_every_cell_finds_its_files_and_metrics():
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert NAME.fullmatch(w["name"]) and w["config"] in configs
        cfg = deploy.load_config(w["config"])
        assert cfg["name"] == w["config"]
        assert (ROOT / configs[w["config"]]["file"]).exists()
        mix = traffic.load_mix(w["traffic"])
        l_in, l_out = deploy.mix_bounds(mix)
        assert l_in + l_out <= cfg["deployment"]["max_seq_len"]
        reported = [e for e in BENCH["end_to_end"]
                    if w["name"] in e.get("workloads", [w["name"]])]
        assert "setup_s" in {e["name"] for e in reported} and len(reported) > 1
    for e in BENCH["per_layer"]:
        assert callable(readers.reader(e["name"]))
        assert set(e["workloads"]) <= cells
        moved = e2e[e["moves"]]
        assert set(e["workloads"]) <= set(moved.get("workloads", cells))
    assert BENCH["command"] == ["python3", "bench/run.py"]


def test_every_model_key_is_a_program_field_or_reference_only():
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    for c in BENCH["configs"]:
        model = json.loads((ROOT / c["file"]).read_text())["model"]
        assert set(model) <= fields | set(deploy.REFERENCE_ONLY), c["name"]
        deploy.model_config(deploy.load_config(c["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_reports_a_per_layer_metric_of_each_it_moves(cell):
    reported = {e["name"] for e in BENCH["end_to_end"]
                if cell in e.get("workloads", [cell])}
    per_layer = [e for e in BENCH["per_layer"]
                 if cell in e.get("workloads", [cell])]
    assert per_layer and {e["moves"] for e in per_layer} <= reported
    # every end-to-end metric but the set-up time has a layer to move it
    assert {e["moves"] for e in per_layer} == reported - {"setup_s"}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_asks_for_the_chips_its_deployment_states(cell):
    dep = deploy.load_config(cell["config"])["deployment"]
    assert cell["chips"] == dep["chips"]
