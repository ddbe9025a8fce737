"""Find the highest rate a cell's open-loop traffic sustains: run the cell
at each offered rate in one process and report, per rate, the requests
served, the tail latencies, the queue wait and how long the backlog took
to drain after the window closed.

    python bench/sweep.py --workload olmo1b.chat --seed 1 --seconds 30 \
        --rates 1 1.5 2 2.5 3

A rate is sustained when the queue wait stays bounded and the drain is
short; the cell's mix is then set at about four fifths of the highest such
rate.  The benchmark's own runs never sweep.  Writes one JSON line per rate
to ``chiprun_out/sweep_<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = R.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    R.require_chips(int(cell["chips"]))
    R.use_compile_cache()
    import numpy as np

    from bench import deploy, stats, traffic

    cfg = deploy.load_config(cell["config"])
    base = traffic.load_mix(cell["traffic"])
    out = R.ROOT / "chiprun_out" / f"sweep_{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    for rate in args.rates:
        mix = dict(base, rate_per_s=rate)
        res, _, lr, _ = R.run_cell(bench, args.workload, args.seed,
                                   args.seconds, False, cfg=cfg, mix=mix,
                                   log=lambda s: print(s, file=sys.stderr))
        waits = [r.granted - r.due for r in lr.records
                 if r.granted is not None]
        last = max((r.token_times[-1] for r in lr.records if r.token_times),
                   default=0.0)
        row = {"rate": rate, "attempted": res["attempted"],
               "failed": res["failed"], "correct": res["correct"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "ttft_p50_ms": stats.percentile(
                   stats.ttfts(lr.records, cfg["wan"]), 50) * 1e3,
               "queue_wait_p90_ms": float(np.percentile(waits, 90)) * 1e3,
               "queue_wait_max_ms": float(max(waits)) * 1e3,
               "drain_s": last - args.seconds,
               "checks": res["checks"]}
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
