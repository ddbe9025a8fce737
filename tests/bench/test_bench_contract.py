"""BENCHMARK.json against the files the harness finds by name."""
import json
import re

from bench import deploy, readers, traffic
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
