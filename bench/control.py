"""The control of a cell's check, read on the chip: the program's readings
and the control's on the same requests, seed by seed, in one process.

    python bench/control.py --workload olmo1b.chat --seconds 20 \
        --seeds 101 102 103

For each seed the cell runs as the benchmark runs it (a shorter window at
the cell's own load) and is checked as ``bench/run.py`` checks it.  On
the same sampled requests the control then takes the program's place:
the plain reference with every matrix and every activation entering one
rounded to int8, and again to float8 e4m3 (one scale per output channel,
one per token), the precisions below the configuration's bfloat16.  At
each position of the same prompts and served tokens its logits stand for
the program's and the token it puts first for the served one, and the
same ``bench.check.verdict`` judges them (``check_control``): a sound
limit passes the program and fails the control.  Writes one JSON line per seed to
``chiprun_out/control_<workload>.jsonl``.  The benchmark's own runs never
run it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402


def _spread(errs) -> dict:
    """Per-position logit errors, read whether or not a limit is set."""
    import numpy as np

    return {"max": float(np.max(errs)), "median": float(np.median(errs))}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = R.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    R.require_chips(int(cell["chips"]))
    R.use_compile_cache()
    import numpy as np

    from bench import check, deploy, traffic

    cfg = deploy.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    out = R.ROOT / "chiprun_out" / f"control_{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    ref, m = check.reference_module(cfg), cfg["model"]
    l_in, l_out = deploy.mix_bounds(mix)
    for seed in args.seeds:
        res, _, _, recs = R.run_cell(bench, args.workload, seed,
                                     args.seconds, False,
                                     log=lambda s: print(s, file=sys.stderr))
        gc.collect()
        exact = check.reference_logits(ref, m, ref.make_params(m, seed),
                                       recs, l_in + l_out, l_out)
        prog = np.concatenate([check.logit_errors(ex, r.logits)
                               for ex, r in zip(exact, recs)])
        row = {"seed": seed, "correct": res["correct"],
               "program": res["checks"], "control": {},
               "logit_err": {"program": _spread(prog)},
               "served_tokens": sum(r.n_new for r in recs),
               "requests": len(recs), "attempted": res["attempted"],
               "failed": res["failed"]}
        for kind in ("int8", "fp8"):
            c, ch, errs = check.check_control(ref, m, seed, recs,
                                              l_in + l_out, l_out,
                                              cfg["check"], kind)
            row["control"][kind] = {"correct": c, "checks": ch}
            row["logit_err"][kind] = _spread(errs)
            gc.collect()
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
