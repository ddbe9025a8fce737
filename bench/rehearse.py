"""Compile one server's pooled steps of a configuration for a described
(not attached) TPU v5e at the real widths, and print what XLA says they
hold in memory: the round step at the round width, and the prefill step
at the largest prompt bucket the cell's traffic reaches, over all rows.
Nothing runs; no chip is needed.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload bloom176b.batch
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import check, deploy, traffic
    from repro.models.blocks import stack_block_kinds
    from repro.models.model import block_param_range
    from repro.serving.kv_cache import (bucket_for, default_prefill_buckets,
                                        kind_runs, make_pool_prefill_step,
                                        make_pool_round_step,
                                        new_state_pool_tree)

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = deploy.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    mc = deploy.model_config(cfg)
    ref, m, dep = check.reference_module(cfg), cfg["model"], cfg["deployment"]
    k, rows = dep["blocks_per_server"], dep["rows"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    # the first server's blocks, in per-kind runs as the engine holds them
    full = jax.eval_shape(lambda: ref.make_params(m, 0))
    kinds = stack_block_kinds(mc)[:k]
    runs = kind_runs(kinds)
    params = place(tuple(jax.eval_shape(
        lambda p, kind=kind, lo=lo, hi=hi: block_param_range(
            p, mc, kind, lo, hi), full) for kind, lo, hi in runs))
    shared = place(full.get("shared"))
    pool = place(jax.eval_shape(lambda: tuple(
        new_state_pool_tree(mc, kind, hi - lo, rows, dep["max_seq_len"])
        for kind, lo, hi in runs)))
    d = mc.d_model
    mask, lids = spec((k, rows), jnp.bool_), spec((k,), jnp.int32)
    dummy = spec((1, 1, 1), jnp.float32)
    bucket = bucket_for(default_prefill_buckets(dep["max_seq_len"]),
                        int(mix["prompt_tokens"]["max"]))
    i32, act = jnp.int32, jnp.dtype(mc.act_dtype)
    steps = {
        "round": make_pool_round_step(mc, kinds, "xla").lower(
            params, shared, pool, spec((rows, 1, d), act),
            spec((rows,), i32), dummy, spec((rows,), i32),
            spec((rows,), i32), spec((rows,), i32), mask, lids),
        f"prefill@{bucket}": make_pool_prefill_step(mc, kinds, "xla").lower(
            params, shared, pool, spec((rows, bucket, d), act), dummy,
            dummy, mask, lids, 0, "all"),
    }
    l_in, l_out = deploy.mix_bounds(mix)
    steps["reference"] = ref.lowered_reference(m, place(full), l_in + l_out,
                                               l_out, chip)
    for name, lowered in steps.items():
        ma = lowered.compile().memory_analysis()
        b = {f: int(getattr(ma, f"{f}_size_in_bytes"))
             for f in ("argument", "output", "temp", "alias")}
        b["peak"] = b["argument"] + b["output"] + b["temp"] - b["alias"]
        print(json.dumps({"workload": args.workload, "step": name,
                          "bytes": b, "GB": {f: round(v / 1e9, 3)
                                             for f, v in b.items()}}),
              flush=True)


if __name__ == "__main__":
    main()
