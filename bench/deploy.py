"""A configuration file turned into the program's deployment.

``bench/configs/<name>.json`` holds the model's architecture and sizes as
the program takes them (``model``: fields of ``ModelConfig``), the module
under ``bench/references/`` that makes its weights, computes its reference
and counts its costs (``reference``), the servers (``deployment``) and the
WAN between the client and each server (``wan``).  This module is the only
place that knows how the program is built from them.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

# keys of a file's ``model`` that only its reference module reads (the
# program derives them: OLMo's and BLOOM's MLP follow their norm)
REFERENCE_ONLY = ("mlp",)


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: every key of
    its ``model`` that is a field of ``ModelConfig``, whatever the
    architecture; ``family`` and ``attn_kind`` are "dense" and "gqa" where
    the file does not state them.  A key that is neither such a field nor
    in :data:`REFERENCE_ONLY` raises, naming itself."""
    from repro.configs.base import ModelConfig

    # the file's name and its deployment set the other two fields
    stated = ({f.name for f in dataclasses.fields(ModelConfig)}
              - {"name", "max_seq_len"})
    m = cfg["model"]
    unknown = sorted(set(m) - stated - set(REFERENCE_ONLY))
    if unknown:
        raise ValueError(f"{cfg['name']}: model keys {unknown} are neither "
                         f"ModelConfig fields that a file states nor "
                         f"reference-only keys {list(REFERENCE_ONLY)}")
    kw = {"family": "dense", "attn_kind": "gqa"}
    kw.update((k, v) for k, v in m.items() if k in stated)
    return ModelConfig(name=cfg["name"],
                       max_seq_len=cfg["deployment"]["max_seq_len"], **kw)


def mix_bounds(mix: dict) -> Tuple[int, int]:
    return int(mix["prompt_tokens"]["max"]), int(mix["output_tokens"]["max"])


def device_groups(dep: dict):
    """The program's ``device_groups`` for a deployment: None on one chip
    (every server on the default device), else one chip per server in
    server order.  Raises where the servers do not match the chips."""
    chips, n = int(dep["chips"]), int(dep["servers"])
    if chips == 1:
        return None
    if n != chips:
        raise ValueError(f"a deployment on {chips} chips puts one server on "
                         f"each; it states {n} servers")
    from repro.launch.mesh import group_meshes

    return group_meshes({j: (1, 1) for j in range(n)})


def server_devices(system) -> Dict[int, List[int]]:
    """Server id -> ids of the devices its blocks and cache pool lie on."""
    import jax

    return {j: sorted({d.id for x in jax.tree.leaves((s.run_params,
                                                      s.pool.tree))
                       for d in x.devices()})
            for j, s in sorted(system.servers.items())}


def build(cfg: dict, mix: dict, params):
    """(system, controller): ``GeoServingSystem`` on the program's default
    path (batched prefill, fused decode, slab cache, XLA backend) and the
    ``OnlineBPRR`` controller that routes for it.  Each server holds
    ``blocks_per_server`` blocks and cache rows for ``rows`` sessions, on
    the chip :func:`device_groups` gives it."""
    import jax

    from repro.core import LLMSpec, Problem, ServerSpec, Workload
    from repro.core.online import OnlineBPRR
    from repro.serving import GeoServingSystem

    dep, wan = cfg["deployment"], cfg["wan"]
    mc = model_config(cfg)
    l_in, l_out = mix_bounds(mix)
    wl = Workload(l_in=l_in, l_out=l_out)
    llm = LLMSpec.from_model_config(mc)
    n, k, rows = (dep["servers"], dep["blocks_per_server"], dep["rows"])
    # room for exactly `rows` worst-case sessions on each hosted block
    mem = k * llm.block_bytes + (rows * k + 0.5) * llm.cache_bytes(
        wl.total_tokens)
    # tau only feeds the program's virtual clock and its routing costs;
    # no metric reads either
    servers = [ServerSpec(j, mem, tau=0.002 * (1 + 0.1 * j),
                          tau_prefill_base=0.01, tau_prefill_per_token=1e-5)
               for j in range(n)]
    prop = np.asarray(wan["rtt_s"][:n], float)[None]
    ser = 2 * 8 * float(wan["embed_bytes"]) / float(wan["bandwidth_bps"])
    problem = Problem(llm, servers, 1, prop + ser, prop + ser * l_in,
                      workload=wl)
    groups = device_groups(dep)
    system = GeoServingSystem(mc, params, problem, algorithm="proposed",
                              R=rows, max_new_tokens=l_out,
                              max_sessions=rows,
                              max_seq_len=dep["max_seq_len"],
                              device_groups=groups)
    ctl = OnlineBPRR(system.problem, R=system.R)
    chain = [(int(a), int(m)) for a, m in zip(system.placement.a,
                                               system.placement.m)]
    want = [(j * k, k) for j in range(n)]
    n_rows = [s.pool.n_rows for s in system.servers.values()]
    if sorted(chain) != want or n_rows != [rows] * n:
        raise RuntimeError(f"placement {chain} / rows differ from the "
                           f"deployment {want} x {rows}")
    given = {j: [(groups[j].devices.flat[0] if groups
                  else jax.devices()[0]).id] for j in range(n)}
    homes = server_devices(system)
    if homes != given:
        raise RuntimeError(f"servers' blocks and pools lie on devices "
                           f"{homes}, not on the chips given {given}")
    return system, ctl


def warm_up(system, requests, rows: int) -> Dict[str, int]:
    """Run every shape this cell's traffic will use once: each distinct
    prompt length (the program compiles its prefill step per bucket, and
    its slices of a prompt's hidden states per exact length) in batches of
    ``rows``, then decode rounds of every membership from ``rows`` down to
    one, reading each session's logits as the check's capture does.
    Returns what it ran."""
    from repro.core import shortest_path_route

    route, _ = shortest_path_route(system.problem, system.alive_placement(),
                                   0)
    lengths = sorted({len(r.prompt) for r in requests})
    rng = np.random.default_rng(0)

    def serve(prompt_lens: List[int], n_news: List[int]):
        sids = [system.create_session(
            rng.integers(2, system.cfg.vocab_size, size=L), 0, route, n)
            for L, n in zip(prompt_lens, n_news)]
        if system.try_admit_sessions(sids) != sids:
            raise RuntimeError("warm-up sessions were not admitted")
        while system.has_pending_prefill():
            system.prefill_round()
        while any(system.sessions[s].n_generated < system.sessions[s].n_new
                  for s in sids):
            system.decode_round()
            for s in sids:
                system.sessions[s].last_logits
        for s in sids:
            system.retire_session(s)

    for i in range(0, len(lengths), rows):
        batch = lengths[i:i + rows]
        serve(batch, [2] * len(batch))
    # memberships rows, rows - 1, ..., 1: one session finishes per round
    serve([lengths[0]] * rows, list(range(2, rows + 2)))
    return {"prompt_lengths": len(lengths),
            "prefill_batches": -(-len(lengths) // rows)}
