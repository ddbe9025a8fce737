"""Mixture-of-Experts FFN: top-k token-choice routing with capacity dispatch.

Dispatch is sort-based (argsort by expert id → gather into an (E, C, d)
buffer → grouped einsum → weighted scatter-add back), which avoids the
O(T·E·C) one-hot dispatch tensor of the classic Switch formulation — essential
for 160-expert DeepSeek-V2 at 1M tokens/step.  Tokens beyond an expert's
capacity ``C = ceil(T·k/E · capacity_factor)`` are dropped (standard TPU MoE
semantics); the residual connection carries dropped tokens through.

Sharding: the (E, C, d) dispatch buffer and expert weights are sharded over
the ``experts`` logical axis (mapped to the data axis → expert parallelism;
XLA inserts the all-to-alls) and ``expert_mlp`` over the model axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import ParamBuilder, ShardingCtx


def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


EP_PAD_GROUP = 256  # pad expert allocation to the full-chip EP group size
EP_MIN_EXPERTS = 64  # only pad/EP-dispatch genuinely expert-rich archs


def expert_alloc(E: int) -> int:
    """Experts allocated in weights: padded to 256-way pure EP for archs with
    many experts (deepseek 160 -> 256; one expert per chip on a 256-chip pod;
    dummy experts receive no tokens).  Small-E archs stay unpadded."""
    if E >= EP_MIN_EXPERTS:
        return ((E + EP_PAD_GROUP - 1) // EP_PAD_GROUP) * EP_PAD_GROUP
    return E


def init_moe(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    Ea = expert_alloc(E)
    dt = _dt(cfg)
    pb = ParamBuilder(key)
    pb.dense("router", (d, E), ("embed_nosplit", "experts_nosplit"), jnp.float32)
    pb.dense("wg", (Ea, d, f), ("experts", "embed_nosplit", "expert_mlp"), dt)
    pb.dense("wu", (Ea, d, f), ("experts", "embed_nosplit", "expert_mlp"), dt)
    pb.dense("wo", (Ea, f, d), ("experts", "expert_mlp", "embed_nosplit"), dt)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        pb.dense("swg", (d, fs), ("embed_fsdp", "mlp"), dt)
        pb.dense("swu", (d, fs), ("embed_fsdp", "mlp"), dt)
        pb.dense("swo", (fs, d), ("mlp", "embed_fsdp"), dt)
    return pb.build()


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(np.ceil(n_tokens * cfg.moe_top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, int(np.ceil(c / 8) * 8))  # pad to a lane-friendly multiple


def router_topk(params, cfg: ModelConfig, xf):
    """Softmax router with renormalised top-k weights.  xf: (T, d)."""
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.moe_top_k)  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    return top_w, top_e, probs


def _sort_dispatch(xf, top_w, top_e, E_slots: int, C: int):
    """Sort-based capacity dispatch.  Returns (xe (E_slots, C, d),
    slot_token (E_slots*C,), slot_weight, counts (E_slots,), keep)."""
    T, d = xf.shape
    k = top_e.shape[-1]
    expert_flat = top_e.reshape(-1)  # (T*k,)
    weight_flat = top_w.reshape(-1)
    token_flat = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(expert_flat, stable=True)
    sorted_e = expert_flat[order]
    sorted_t = token_flat[order]
    sorted_w = weight_flat[order]
    counts = jnp.zeros((E_slots,), jnp.int32).at[sorted_e].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * k) - starts[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E_slots * C)  # drop sentinel
    slot_token = jnp.full((E_slots * C + 1,), T, jnp.int32).at[slot].set(
        sorted_t, mode="drop")[: E_slots * C]
    slot_weight = jnp.zeros((E_slots * C + 1,), jnp.float32).at[slot].set(
        sorted_w, mode="drop")[: E_slots * C]
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = x_pad[slot_token].reshape(E_slots, C, d)
    return xe, slot_token, slot_weight, counts, keep


def _combine(ye, slot_token, slot_weight, T: int):
    d = ye.shape[-1]
    yf = ye.reshape(-1, d) * slot_weight[:, None].astype(ye.dtype)
    return jnp.zeros((T, d), ye.dtype).at[slot_token].add(yf, mode="drop")


def _expert_mlp(xe, wg, wu, wo):
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype)))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
    return jnp.einsum("ecf,efd->ecd", g * u, wo.astype(xe.dtype))


def _shared_expert(params, cfg, sh, x, out):
    if cfg.n_shared_experts:
        gs = jax.nn.silu(x @ params["swg"].astype(x.dtype))
        us = x @ params["swu"].astype(x.dtype)
        hs = sh.act(gs * us, "batch", "seq", "mlp_act")
        y = hs @ params["swo"].astype(x.dtype)
        # reduce-scatter into the sequence-sharded residual layout
        out = out + sh.act(y, "batch", "seq_act", None)
    return out


def _ep_eligible(params, cfg: ModelConfig, sh: ShardingCtx, x) -> bool:
    """Use the shard_map pure-EP path when: mesh present, padded weights,
    and the (batch, seq) token grid divides the (data..., model) EP group."""
    if sh.mesh is None or params["wg"].shape[0] == cfg.n_experts:
        return False
    sizes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
    model = sizes.get("model", 1)
    n_data = int(np.prod([sizes[a] for a in ("pod", "data") if a in sizes]))
    B, S, _ = x.shape
    return (S % model == 0 and B % n_data == 0 and S // model >= 1
            and sh.rules.get("batch") is not None)


def apply_moe(params, cfg: ModelConfig, sh: ShardingCtx, x):
    """x (B, S, d) -> (B, S, d); routed top-k experts + optional shared expert.

    Returns (out, aux_metrics) with the load-balancing auxiliary loss terms.
    Dispatch substrate (DESIGN.md §6 / EXPERIMENTS.md §Perf hillclimb A):

    * pure-EP shard_map path — expert-rich archs (deepseek) on a mesh:
      experts padded to one-per-chip over (data x model); local top-k +
      sort dispatch; ONE all-to-all out + one back per layer.  ~50x less
      wire than XLA's handling of the global gather/scatter formulation.
    * global sort-dispatch path — small meshes / small-E archs / decode.
    """
    if _ep_eligible(params, cfg, sh, x):
        return _apply_moe_ep(params, cfg, sh, x)

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    T = B * S
    C = _capacity(cfg, T)
    xf = x.reshape(T, d)
    top_w, top_e, probs = router_topk(params, cfg, xf)
    xe, slot_token, slot_weight, counts, keep = _sort_dispatch(
        xf, top_w, top_e, E, C)
    xe = sh.act(xe, "experts", None, None)
    ye = _expert_mlp(xe, params["wg"][:E], params["wu"][:E], params["wo"][:E])
    ye = sh.act(ye, "experts", None, None)
    out = _combine(ye, slot_token, slot_weight, T).reshape(B, S, d)
    out = _shared_expert(params, cfg, sh, x, out)
    frac = counts.astype(jnp.float32) / jnp.maximum(T * k, 1)
    mean_prob = jnp.mean(probs, axis=0)
    aux = {"moe_aux_loss": E * jnp.sum(frac * mean_prob),
           "moe_drop_frac": 1.0 - jnp.sum(keep) / jnp.maximum(T * k, 1)}
    return sh.act(out, "batch", "seq_act", None), aux


def _apply_moe_ep(params, cfg: ModelConfig, sh: ShardingCtx, x):
    """Pure expert parallelism over the whole mesh via shard_map."""
    from jax.sharding import PartitionSpec as P

    mesh = sh.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep_axes = tuple(a for a in ("data", "model") if a in sizes)
    n_ep = int(np.prod([sizes[a] for a in ep_axes]))
    batch_ax = sh.rules.get("batch")
    bt = batch_ax if isinstance(batch_ax, (tuple, list)) else (batch_ax,)
    E, k = cfg.n_experts, cfg.moe_top_k
    E_alloc = params["wg"].shape[0]
    assert E_alloc % n_ep == 0
    E_per = E_alloc // n_ep
    B, S, d = x.shape

    def body(x_loc, router, wg, wu, wo):
        B_l, S_l, _ = x_loc.shape
        T_l = B_l * S_l
        xf = x_loc.reshape(T_l, d)
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
        C_src = max(8, int(np.ceil(T_l * k / E * cfg.capacity_factor / 8) * 8))
        xe, slot_token, slot_weight, counts, keep = _sort_dispatch(
            xf, top_w, top_e, E_alloc, C_src)
        send = xe.reshape(n_ep, E_per * C_src, d)
        recv = jax.lax.all_to_all(send, ep_axes, split_axis=0, concat_axis=0,
                                  tiled=False)
        tok = recv.reshape(n_ep, E_per, C_src, d).transpose(1, 0, 2, 3)
        tok = tok.reshape(E_per, n_ep * C_src, d)
        ye = _expert_mlp(tok, wg, wu, wo)
        back = ye.reshape(E_per, n_ep, C_src, d).transpose(1, 0, 2, 3)
        back = back.reshape(n_ep, E_per * C_src, d)
        ret = jax.lax.all_to_all(back, ep_axes, split_axis=0, concat_axis=0,
                                 tiled=False)
        out = _combine(ret.reshape(E_alloc * C_src, d), slot_token,
                       slot_weight, T_l).reshape(B_l, S_l, d)
        # global aux stats (cheap scalar psums over every mesh axis)
        all_axes = tuple(mesh.axis_names)
        tot = jax.lax.psum(jnp.float32(T_l * k), all_axes)
        counts_g = jax.lax.psum(counts[:E].astype(jnp.float32), all_axes)
        mean_prob = jax.lax.pmean(jnp.mean(probs, axis=0), all_axes)
        kept = jax.lax.psum(jnp.sum(keep).astype(jnp.float32), all_axes)
        aux = {"moe_aux_loss": E * jnp.sum(counts_g / tot * mean_prob),
               "moe_drop_frac": 1.0 - kept / tot}
        return out, aux

    x_spec = P(bt[0] if len(bt) == 1 else tuple(bt), "model", None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(ep_axes, None, None),
                  P(ep_axes, None, None), P(ep_axes, None, None)),
        out_specs=(x_spec, P()), check_vma=False,
    )(x, params["router"], params["wg"], params["wu"], params["wo"])
    out = _shared_expert(params, cfg, sh, x, out)
    return sh.act(out, "batch", "seq_act", None), aux
