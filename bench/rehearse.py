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

    from bench import deploy, traffic
    from bench.references import dense_decoder as ref
    from repro.serving.kv_cache import (bucket_for, default_prefill_buckets,
                                        make_pool_prefill_step,
                                        make_pool_round_step,
                                        new_state_pool_tree)

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = deploy.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    mc = deploy.model_config(cfg)
    dep = cfg["deployment"]
    k, rows = dep["blocks_per_server"], dep["rows"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    full = jax.eval_shape(lambda: ref.make_params(cfg["model"], 0))
    blocks = full["segments"]["blocks"]
    params = place((jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (k,) + x.shape[1:], x.dtype), blocks),))
    pool = place(jax.eval_shape(lambda: (new_state_pool_tree(
        mc, "decoder", k, rows, dep["max_seq_len"]),)))
    kinds = ("decoder",) * k
    d = mc.d_model
    mask, lids = spec((k, rows), jnp.bool_), spec((k,), jnp.int32)
    dummy = spec((1, 1, 1), jnp.float32)
    bucket = bucket_for(default_prefill_buckets(dep["max_seq_len"]),
                        int(mix["prompt_tokens"]["max"]))
    i32, act = jnp.int32, jnp.dtype(mc.act_dtype)
    steps = {
        "round": make_pool_round_step(mc, kinds, "xla").lower(
            params, None, pool, spec((rows, 1, d), act), spec((rows,), i32),
            dummy, spec((rows,), i32), spec((rows,), i32),
            spec((rows,), i32), mask, lids),
        f"prefill@{bucket}": make_pool_prefill_step(mc, kinds, "xla").lower(
            params, None, pool, spec((rows, bucket, d), act), dummy, dummy,
            mask, lids, 0, "all"),
    }
    l_in, l_out = deploy.mix_bounds(mix)
    ref_params = place(full)
    flat = ref.flat_leaves(ref_params, cfg["model"])
    m = cfg["model"]
    per_layer = {k_: v for k_, v in flat.items()
                 if k_ not in ("tok", "final_scale", "final_bias")}
    with jax.default_matmul_precision("highest"):
        steps["reference"] = ref._logits_jit.lower(
            m["norm_kind"], m["norm_eps"], m["pos_kind"],
            float(m.get("rope_theta", 0.0)), m["mlp"], None, flat["tok"],
            flat.get("final_scale"), flat.get("final_bias"), per_layer,
            spec((l_in + l_out,), i32), spec((l_out,), i32))
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
