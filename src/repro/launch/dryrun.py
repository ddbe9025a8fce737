"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the fake-device flag before ANY other import (jax locks the device
count at first init).  Merged into — not overwriting — any XLA_FLAGS the
user already exported (repro.launch.xla_flags is stdlib-only)."""
import os

from repro.launch.xla_flags import force_host_device_count

force_host_device_count(os.environ, 512)

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS, SHAPES_BY_NAME, ModelConfig, ShapeSpec, get_config)
from repro.launch import costs as C  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import (  # noqa: E402
    batch_specs, cache_shardings, cache_specs, make_ctx)
from repro.models.layers import ShardingCtx  # noqa: E402
from repro.models.model import (  # noqa: E402
    decode_step, init_params_shapes, make_decode_body, make_full_body,
    prefill, stack_plan)
from repro.training.train_step import (  # noqa: E402
    TrainHParams, make_optimizer_for, make_train_step)


# The production chip the fake-CPU lowering models: its roofline peaks
# (launch.costs.PEAKS) price every cell.
TARGET_DEVICE_KIND = "TPU v5 lite"

# ---------------------------------------------------------------------------
# Spec plumbing
# ---------------------------------------------------------------------------


def _with_shardings(shape_tree, sharding_tree):
    return jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        shape_tree, sharding_tree)


def param_specs(cfg: ModelConfig, sh: ShardingCtx):
    shapes, axes = init_params_shapes(cfg)
    shardings = sh.param_shardings(axes)
    return _with_shardings(shapes, shardings), axes, shardings


def _slice_leading(tree):
    """SDS tree with the leading (scan) axis removed."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree)


def _slice_axes(axes_tree):
    return jax.tree.map(lambda a: a[1:], axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def input_specs(arch: str, shape_name: str, mesh) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    sh = make_ctx(cfg, mesh, shape)
    out: Dict = {"cfg": cfg, "shape": shape, "sh": sh}
    pspecs, axes, pshard = param_specs(cfg, sh)
    out["params"] = pspecs
    out["param_axes"] = axes
    out["param_shardings"] = pshard
    if shape.kind in ("train", "prefill"):
        out["batch"] = batch_specs(cfg, shape, sh)
    if shape.kind == "decode":
        out["caches"] = cache_specs(cfg, shape, sh, enc_len=shape.seq_len)
        out["tokens"] = jax.ShapeDtypeStruct(
            (shape.global_batch,), jnp.int32,
            sharding=sh.named_sharding("batch"))
    return out


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               with_corrections: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    sh = make_ctx(cfg, mesh, shape)
    spec = input_specs(arch, shape_name, mesh)
    t0 = time.time()

    if shape.kind == "train":
        hp = TrainHParams(remat=True, grad_accum=1)
        opt = make_optimizer_for(cfg, hp)
        step_fn = make_train_step(cfg, sh, opt, hp)
        opt_shapes = jax.eval_shape(opt.init, spec["params"])
        opt_shardings = _opt_shardings(opt, spec["param_shardings"],
                                       opt_shapes, mesh)
        state_sds = {
            "params": spec["params"],
            "opt": _with_shardings(opt_shapes, opt_shardings),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        lowered = jax.jit(step_fn, donate_argnums=0).lower(
            state_sds, spec["batch"])
    elif shape.kind == "prefill":
        def fn(params, batch):
            return prefill(params, cfg, sh, batch, cache_len=shape.seq_len)

        out_shapes = jax.eval_shape(fn, spec["params"], spec["batch"])
        logits_ns = sh.named_sharding("batch", "vocab")
        cache_ns = cache_shardings(cfg, sh, out_shapes[1])
        lowered = jax.jit(fn, out_shardings=(logits_ns, cache_ns)).lower(
            spec["params"], spec["batch"])
    else:  # decode
        pos = shape.seq_len - 1

        def fn(params, caches, tokens):
            return decode_step(params, cfg, sh, caches, tokens, pos)

        cache_ns = jax.tree.map(lambda s: s.sharding, spec["caches"])
        logits_ns = sh.named_sharding("batch", "vocab")
        lowered = jax.jit(fn, donate_argnums=1,
                          out_shardings=(logits_ns, cache_ns)).lower(
            spec["params"], spec["caches"], spec["tokens"])

    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = C.memory_summary(compiled)
    raw = C.summarize_compiled(compiled)

    corrected = C.CostSummary()
    corrected.scaled_add(raw, 1.0)
    seg_costs = {}
    if with_corrections:
        for seg in stack_plan(cfg):
            k = seg.n - 1
            if k <= 0:
                continue
            if shape.kind == "decode" and seg.kind == "enc":
                continue  # encoder does no decode-time work
            fwd, bwd = _segment_body_costs(cfg, sh, shape, spec, seg,
                                           train=(shape.kind == "train"))
            corrected.scaled_add(fwd, float(k))
            seg_costs[seg.name] = {"n": seg.n, "fwd": fwd.to_dict()}
            if bwd is not None:
                corrected.scaled_add(bwd, float(k))
                seg_costs[seg.name]["bwd"] = bwd.to_dict()

    # analytic HBM-traffic floor: everything the step necessarily touches
    # once per device (params + opt state + caches = args; outputs), plus the
    # remat stash (written fwd, read bwd) for training.
    stash = 0.0
    if shape.kind == "train":
        n_data = n_chips // 16  # data axes product (model axis is 16)
        stash = (shape.global_batch / n_data) * shape.seq_len \
            * cfg.d_model * 2 * cfg.n_layers
        seq_rule = sh.rules.get("seq_act")
        if seq_rule is not None:
            stash /= 16
    mem_floor = (mem["argument_size_in_bytes"]
                 + mem["output_size_in_bytes"] + 2.0 * stash)
    terms = C.roofline_terms(corrected, n_chips, mem_floor_bytes=mem_floor,
                             device_kind=TARGET_DEVICE_KIND)
    model_flops = _model_flops_per_device(cfg, shape, n_chips)
    # TPU-peak estimate: true-dtype args + half of the f32-inflated temps
    peak_tpu_est = (mem["argument_size_in_bytes"]
                    + mem["output_size_in_bytes"]
                    + mem["temp_size_in_bytes"] / 2.0
                    - mem["alias_size_in_bytes"])
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "compile_seconds": round(compile_s, 2),
        "memory": mem,
        "raw_cost": raw.to_dict(),
        "corrected_cost": corrected.to_dict(),
        "segments": seg_costs,
        "roofline": terms,
        "model_flops_per_device": model_flops,
        "useful_flops_ratio": (model_flops / corrected.flops
                               if corrected.flops else 0.0),
        "fits_hbm_16g_raw": bool(mem["peak_hbm_bytes"] < 16e9),
        "peak_hbm_tpu_est_bytes": peak_tpu_est,
        "fits_hbm_16g_tpu_est": bool(peak_tpu_est < 16e9),
    }
    del compiled, lowered
    gc.collect()
    return result


def _opt_shardings(opt, param_shardings, opt_shapes, mesh):
    """Optimizer-state shardings: adamw m/v mirror params; scalars/factored
    stats fall back to replication (they are comparatively small)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    if opt.name == "adamw":
        return {"m": param_shardings, "v": param_shardings}
    return jax.tree.map(lambda s: rep, opt_shapes)


def _model_flops_per_device(cfg: ModelConfig, shape: ShapeSpec,
                            n_chips: int) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:
        total = 2.0 * n_active * shape.global_batch
    return total / n_chips


# ---------------------------------------------------------------------------
# Segment-body lowering for the exact scan-cost correction
# ---------------------------------------------------------------------------


def _segment_body_costs(cfg, sh: ShardingCtx, shape: ShapeSpec, spec, seg,
                        train: bool):
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.param_dtype)
    h_sds = jax.ShapeDtypeStruct(
        (B, 1 if shape.kind == "decode" else S, cfg.d_model), dt,
        sharding=sh.named_sharding("batch", "seq_act" if train else None,
                                   None))
    p_slice = _with_shardings(
        _slice_leading(spec["params"]["segments"][seg.name]),
        sh.param_shardings(
            _slice_axes(spec["param_axes"]["segments"][seg.name])))
    idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
    aux_sds = {"moe_aux_loss": jax.ShapeDtypeStruct((), jnp.float32),
               "moe_drop_frac": jax.ShapeDtypeStruct((), jnp.float32)}
    emb_sds = h_sds  # emb0 for zamba mega
    shared_sds = spec["params"].get("shared")

    if shape.kind == "decode":
        pos = shape.seq_len - 1
        # keep per-leaf cache shardings on the sliced (per-layer) specs —
        # lowering the body with unsharded caches would overcount per-device
        # bytes by the full sharding factor
        from repro.launch.sharding import cache_axes_for

        def _slice_cache_spec(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else None
            axes = cache_axes_for(name, leaf.ndim, sh.rules)[1:]
            return jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype,
                                        sharding=sh.named_sharding(*axes))

        cache_slice = jax.tree_util.tree_map_with_path(
            _slice_cache_spec, spec["caches"][seg.name])

        def fwd_fn(p, c, h, emb0, shared):
            body = make_decode_body(seg, cfg, sh, pos, emb0=emb0,
                                    shared_params=shared)
            xs = (p, c, jnp.int32(0)) if seg.kind == "decoder" else (p, c)
            return body(h, xs)

        args = (p_slice, cache_slice, h_sds, emb_sds, shared_sds)
        fwd = _lower_cost(fwd_fn, args)
        return fwd, None

    positions_sds = jax.ShapeDtypeStruct((S,), jnp.int32)
    collect = shape.kind == "prefill"

    def fwd_fn(p, h, aux, positions, emb0, enc_h, shared):
        body = make_full_body(seg, cfg, sh, positions, emb0=emb0,
                              enc_h=enc_h, collect_caches=collect,
                              shared_params=shared)
        if seg.kind == "decoder":
            return body((h, aux), (p, jnp.int32(0)))
        return body(h, (p, None))

    args = (p_slice, h_sds, aux_sds, positions_sds, emb_sds, h_sds,
            shared_sds)
    fwd = _lower_cost(fwd_fn, args)
    bwd = None
    if train:
        body = None

        def loss_like(p, h, aux, positions, emb0, enc_h, shared):
            out = fwd_fn(p, h, aux, positions, emb0, enc_h, shared)
            carry = out[0] if seg.kind == "decoder" else out[0]
            return carry

        remat_fn = jax.checkpoint(
            loss_like, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=())

        def bwd_fn(p, h, aux, positions, emb0, enc_h, shared, ct_h, ct_aux):
            outs, vjp = jax.vjp(
                lambda pp, hh, aa: remat_fn(pp, hh, aa, positions, emb0,
                                            enc_h, shared), p, h, aux)
            ct = (ct_h, ct_aux) if seg.kind == "decoder" else ct_h
            return vjp(ct)

        ct_h = h_sds
        bwd = _lower_cost(bwd_fn, args + (ct_h, aux_sds))
    return fwd, bwd


def _lower_cost(fn, arg_specs) -> C.CostSummary:
    lowered = jax.jit(fn).lower(*arg_specs)
    compiled = lowered.compile()
    out = C.summarize_compiled(compiled)
    del compiled, lowered
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def runnable_cells():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in cfg.shapes():
            yield arch, shape.name
        for shape_name, reason in cfg.skip_reasons().items():
            yield arch, f"SKIP:{shape_name}:{reason}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-corrections", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for arch, shape in runnable_cells():
            print(f"{arch},{shape}")
        return

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    meshes = args.mesh.split(",")
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in cfg.shapes()] if args.shape == "all"
                  else [s for s in args.shape.split(",")
                        if s in {x.name for x in cfg.shapes()}])
        for shape_name in shapes:
            for mesh_kind in meshes:
                multi = mesh_kind == "multi"
                tag = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"skip (exists) {tag}")
                    continue
                print(f"=== {tag} ===", flush=True)
                try:
                    res = lower_cell(arch, shape_name, multi,
                                     with_corrections=not args.no_corrections)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    r = res["roofline"]
                    print(f"  ok compile={res['compile_seconds']}s "
                          f"peak_hbm={res['memory']['peak_hbm_bytes']/1e9:.2f}GB "
                          f"compute={r['compute_s']*1e3:.2f}ms "
                          f"memory={r['memory_s']*1e3:.2f}ms "
                          f"coll={r['collective_s']*1e3:.2f}ms "
                          f"dominant={r['dominant']} "
                          f"useful={res['useful_flops_ratio']:.3f}",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"  FAIL {e}", flush=True)
                    traceback.print_exc()
                gc.collect()
    if failures:
        print("\nFAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err}")
        raise SystemExit(1)
    print("\nall requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
