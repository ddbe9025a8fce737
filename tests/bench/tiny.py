"""A cell cut to a size a test run holds on the CPU: an OLMo-shaped model
of four blocks at width 128 over two servers, bfloat16 as served, and a
one-second open-loop window."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.chat"


def tiny(config="olmo1b"):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    cfg["model"].update(n_layers=4, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=256, vocab_size=512)
    cfg["deployment"].update(servers=2, blocks_per_server=2, rows=4,
                             max_seq_len=96)
    cfg["wan"]["embed_bytes"] = 256
    # at this size on the CPU, over seeds 5, 77, 2**31 + 99 and
    # 123456789012: the program read logit_err_max 0.024-0.033 and
    # served_gap_max 0.026-0.062, the int8 control 0.052-0.066 and
    # 0.022-0.100, the fp8 control 0.245-0.268 and 0.50-0.63
    cfg["check"] = {"logit_err_max": 0.042, "served_gap_max": 0.3}
    mix = {"loop": "open", "rate_per_s": 12.0,
           "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                             "min": 8, "max": 64},
           "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                             "min": 4, "max": 32}}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": CELL, "config": config,
                           "traffic": "chat", "chips": 1}]
    for e in bench["end_to_end"] + bench["per_layer"]:
        e.pop("workloads", None)
    return bench, cfg, mix
