"""Perf-model + simulator cross-validation against the REAL engine.

Three parts (paper Figs 2/3/11/12 analogue + §4 concurrency dynamics):

1. block-linearity: the engine's block-level execution confirms the linear
   dependence of per-token time on #processed blocks (eq. (1)).
2. concurrency cross-validation: the SAME Poisson trace is played through
   the discrete-event simulator and through the continuous-batching engine
   (real JAX forward passes, WS-RR admission, shared cache pools) at design
   concurrency R ∈ {1, 4, 8}; we report the relative error of mean
   per-token and first-token times between the two paths.  Agreement within
   a few percent validates that the simulator's waiting/memory dynamics
   (eq. (5)/(20)) match what the engine actually does under interleaved
   sessions.
3. hybrid-topology cross-validation: the same trace served by a zamba2-style
   hybrid stack (mamba + shared-attention blocks) with per-FAMILY block
   compute weights (``LLMSpec.block_tau``) — the engine's family-polymorphic
   state pools against the simulator's weighted eq. (1) accounting.

Also emits a machine-readable ``BENCH_engine.json`` (tokens/s and
cross-validation error per scenario) so CI can track the perf trajectory.

Run:  PYTHONPATH=src:. python benchmarks/engine_validation.py [--full]
      [--smoke] [--json PATH]
"""
from __future__ import annotations

import json
import os
import numpy as np

from benchmarks.common import emit, timed

# The chip whose published peaks price calibrated τ when this runs off-TPU
# (launch.costs.PEAKS); on a TPU each server's own device kind is used.
MODELLED_DEVICE_KIND = "TPU v5 lite"


def _tau_device_kind():
    import jax

    return None if jax.default_backend() == "tpu" else MODELLED_DEVICE_KIND


# per-family relative block compute weights of the hybrid scenario: a
# shared-attention block (mamba mixer + width-2d attention+MLP) costs ~2.5x
# a plain mamba mixer; weights average ~1 so totals stay comparable to the
# uniform scenario
HYBRID_TAU = {"mamba": 0.7, "mamba_shared": 1.9}

# collected by run(): scenario name -> metrics dict (written as JSON)
_RESULTS = {}


def _record(name: str, **metrics):
    _RESULTS[name] = {k: (float(v) if isinstance(v, (int, float, np.floating))
                          else v) for k, v in metrics.items()}


def _xval_config(arch: str, L: int):
    """Reduced engine config with exactly L BPRR blocks for one arch."""
    from repro.configs import get_reduced_config

    cfg = get_reduced_config(arch)
    if cfg.n_layers != L:
        cfg = cfg.replace(n_layers=L)
    return cfg


def _concurrency_problem(block_tau=None):
    from repro.core import LLMSpec, Problem, ServerSpec, Workload

    llm = LLMSpec("xval", 8, block_bytes=50.0, cache_bytes_per_token=0.5,
                  block_tau=block_tau)
    servers = [
        ServerSpec(0, 500.0, 0.004, tau_prefill_base=0.002,
                   tau_prefill_per_token=0.0005),
        ServerSpec(1, 500.0, 0.004, tau_prefill_base=0.002,
                   tau_prefill_per_token=0.0005),
        ServerSpec(2, 260.0, 0.020, tau_prefill_base=0.004,
                   tau_prefill_per_token=0.001),
        ServerSpec(3, 260.0, 0.020, tau_prefill_base=0.004,
                   tau_prefill_per_token=0.001),
        ServerSpec(4, 260.0, 0.020, tau_prefill_base=0.004,
                   tau_prefill_per_token=0.001),
    ]
    rtt = np.array([[0.01, 0.01, 0.03, 0.03, 0.03]])
    return Problem(llm, servers, 1, rtt, 3 * rtt, workload=Workload(8, 12))


def _planet_problem(n_servers: int = 8, n_clients: int = 4,
                    mem: float = 3200.0):
    """Well-provisioned planet-scale topology for the 1M-request diurnal
    study: two server classes (fast/slow alternating), each client nearest
    a distinct server pair, ~200-280 eq. (15) cache slots per server.  At
    R=8 CG-BP gives every client a dedicated full-stack server, so the
    diurnal valley runs entirely in the zero-wait regime (the fast engine's
    W == W0 condition) and the midday rush spills onto the slow exact
    path — both branches of the vectorized event loop get exercised."""
    from repro.core import LLMSpec, Problem, ServerSpec, Workload

    llm = LLMSpec("planet", 8, block_bytes=50.0, cache_bytes_per_token=0.5)
    servers = [
        ServerSpec(j, mem if j % 2 == 0 else mem * 0.75,
                   0.004 if j % 2 == 0 else 0.006,
                   tau_prefill_base=0.002, tau_prefill_per_token=0.0005)
        for j in range(n_servers)
    ]
    rtt = np.full((n_clients, n_servers), 0.02)
    for c in range(n_clients):
        rtt[c, (2 * c) % n_servers] = 0.005
        rtt[c, (2 * c + 1) % n_servers] = 0.005
    return Problem(llm, servers, n_clients, rtt, 3 * rtt,
                   workload=Workload(8, 12))


def _fleet_problem(n_servers: int = 120, n_clients: int = 4, seed: int = 0):
    """Large elastic fleet for the churn study: heterogeneous memory and
    compute in a 3x4 class grid, dense random client RTTs — big enough
    that CG-BP re-placement (OnlineBPRR.replace_servers) is the dominant
    cost a storm has to amortize."""
    from repro.core import LLMSpec, Problem, ServerSpec, Workload

    llm = LLMSpec("fleet", 8, block_bytes=50.0, cache_bytes_per_token=0.5)
    rng = np.random.default_rng(seed)
    servers = [
        ServerSpec(j, float(400.0 + 100.0 * (j % 3)),
                   float(0.004 + 0.004 * (j % 4)),
                   tau_prefill_base=0.002, tau_prefill_per_token=0.0005)
        for j in range(n_servers)
    ]
    rtt = 0.005 + 0.045 * rng.random((n_clients, n_servers))
    return Problem(llm, servers, n_clients, rtt, 3 * rtt,
                   workload=Workload(8, 12))


def cross_validate(R: int, n_requests: int = 10, rate: float = 1.0,
                   seed: int = 0, trace: str = "poisson",
                   arch: str = "llama3_2_1b"):
    """Returns (engine metrics, sim metrics, relative errors) for one R.

    ``trace``: "poisson" (the paper's proxy-client arrivals) or "bursty"
    (4-request same-timestamp bursts — the coalescable-prefill workload:
    the engine admits each burst as one bucket group).  ``arch`` picks the
    served stack; "zamba2_7b" runs the hybrid topology with per-family
    block compute weights (``HYBRID_TAU``)."""
    import jax

    from repro.models import init_params, stack_block_kinds
    from repro.serving import ContinuousBatchingScheduler, GeoServingSystem
    from repro.sim import SimConfig, simulate
    from repro.sim.workload import (bursty_requests, poisson_requests,
                                    prompts_for)

    cfg = _xval_config(arch, 8)
    block_tau = None
    if cfg.family == "hybrid":
        block_tau = tuple(HYBRID_TAU[k] for k in stack_block_kinds(cfg))
    problem = _concurrency_problem(block_tau=block_tau)
    lw = problem.workload
    if trace == "bursty":
        requests = bursty_requests(n_bursts=max(1, n_requests // 4),
                                   burst_size=4, spacing=2.0)
    else:
        requests = poisson_requests(n_requests, rate, seed=seed)

    # --- simulator path ---------------------------------------------------
    sim = simulate(problem, SimConfig("proposed", n_requests=len(requests),
                                      rate=rate, seed=seed, R=R),
                   requests=requests)

    # --- engine path (same trace, same R) ---------------------------------
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    system = GeoServingSystem(cfg, params, problem, algorithm="proposed",
                              R=R, max_new_tokens=lw.l_out,
                              max_sessions=max(8, R))
    sched = ContinuousBatchingScheduler(system, R=R, arrival_rate=rate)
    prompts = prompts_for(requests, lw.l_in, cfg.vocab_size, seed=seed)
    for req, toks in zip(requests, prompts):
        sched.submit(req.rid, toks, req.arrival, n_new=lw.l_out,
                     client=req.client)
    served = [r for r in sched.run() if not r.dropped]

    eng = {
        "per_token_all": float(np.mean([r.per_token for r in served])),
        "first_token": float(np.mean([r.first_token for r in served])),
        "wait": float(np.mean([r.wait for r in served])),
        "max_concurrency": sched.max_concurrency,
    }
    simm = {
        "per_token_all": sim.per_token_all,
        "first_token": sim.first_token,
        "wait": sim.wait,
    }
    err = {k: abs(eng[k] - simm[k]) / max(simm[k], 1e-12)
           for k in ("per_token_all", "first_token")}
    return eng, simm, err


def prefill_throughput(R: int = 4, burst: int = 8, n_new: int = 4,
                       seed: int = 0):
    """Wall-clock prefill throughput of one same-timestamp burst, serial
    vs bucketed-batched admission.  Returns {mode: tokens/s} measured on a
    second (jit-warm) run."""
    import time

    import jax

    from repro.configs import get_reduced_config
    from repro.core import shortest_path_route
    from repro.models import init_params
    from repro.serving import GeoServingSystem

    from repro.core import LLMSpec, Problem, ServerSpec, Workload

    # amply-provisioned two-hop topology: the whole burst must be resident
    llm = LLMSpec("tput", 8, block_bytes=50.0, cache_bytes_per_token=0.5)
    servers = [ServerSpec(0, 2000.0, 0.004, tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005),
               ServerSpec(1, 2000.0, 0.004, tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)]
    rtt = np.array([[0.01, 0.01]])
    problem = Problem(llm, servers, 1, rtt, 3 * rtt,
                      workload=Workload(12, 12))
    lw = problem.workload
    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=problem.L)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, size=lw.l_in)
               for _ in range(burst)]

    out = {}
    for mode in ("serial", "batched"):
        system = GeoServingSystem(cfg, params, problem, algorithm="proposed",
                                  R=R, max_new_tokens=n_new,
                                  max_sessions=max(8, burst),
                                  prefill_mode=mode)

        def once():
            sids = []
            for toks in prompts:
                route, _ = shortest_path_route(system.problem,
                                               system.alive_placement(), 0)
                sids.append(system.create_session(toks, 0, route, n_new))
            t0 = time.perf_counter()
            admitted = system.try_admit_sessions(sids)
            system.drain_prefill()
            dt = time.perf_counter() - t0
            assert len(admitted) == burst, "burst must fit for the measure"
            for sid in sids:
                system.retire_session(sid)
            return dt

        once()  # jit warm-up
        dt = min(once() for _ in range(3))
        out[mode] = burst * lw.l_in / dt
    return out


def decode_throughput(n_servers: int = 2, n_sessions: int = 8,
                      n_rounds: int = 4, warm: int = 2, seed: int = 0):
    """Wall-clock decode throughput of one resident cohort: the
    device-resident fused rounds (``decode_mode="fused"``) against the
    per-session serial reference (``decode_mode="serial"``, one session per
    round — the ``prefill_mode="serial"``-style baseline).

    Benchmark hygiene: both paths run ``warm`` rounds first (trace +
    compile excluded) and each fused round ends on its token readback
    (the round's one host sync), so the timed window measures steady
    state.  Topology: ``n_servers`` servers hosting one equal share of the
    blocks each, sized so the WHOLE cohort is resident (every session
    routes through every server).  Returns tokens/s per mode + speedup +
    fused dispatches/round."""
    import time

    import jax

    from repro.configs import get_reduced_config
    from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                            shortest_path_route)
    from repro.models import init_params
    from repro.serving import GeoServingSystem

    L = max(8, n_servers)
    bps = L // n_servers  # blocks per server
    lw = Workload(4, warm + n_rounds + 2)
    llm = LLMSpec("dtput", L, block_bytes=500.0, cache_bytes_per_token=0.5)
    # memory: exactly `bps` blocks fit (one more would not), plus cache
    # slots for the whole cohort — forces an n_servers-hop route
    s_c = 0.5 * (lw.l_in + lw.l_out)
    mem = 500.0 * bps + s_c * (n_sessions * bps + 1)
    assert mem < 500.0 * (bps + 1), "cohort slots must fit under one block"
    servers = [ServerSpec(j, mem, 0.004, tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)
               for j in range(n_servers)]
    rtt = np.full((1, n_servers), 0.01)
    problem = Problem(llm, servers, 1, rtt, 3 * rtt, workload=lw)
    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=L)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, size=lw.l_in)
               for _ in range(n_sessions)]

    out = {}
    toks = {}
    for mode in ("serial", "fused"):
        system = GeoServingSystem(cfg, params, problem,
                                  algorithm="proposed", R=n_sessions,
                                  max_new_tokens=lw.l_out,
                                  max_sessions=n_sessions, decode_mode=mode)
        sids = []
        for p in prompts:
            route, _ = shortest_path_route(problem,
                                           system.alive_placement(), 0)
            sids.append(system.create_session(p, 0, route, lw.l_out))
        admitted = system.try_admit_sessions(sids)
        assert len(admitted) == n_sessions, "cohort must be fully resident"
        system.drain_prefill()
        hops = len(system.sessions[sids[0]].route.servers)
        assert hops == n_servers, f"expected {n_servers}-hop route: {hops}"

        def sweep():
            if mode == "fused":
                system.decode_round(sids)
            else:  # per-session reference: one session per round
                for sid in sids:
                    system.decode_round([sid])

        for _ in range(warm):
            sweep()
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            sweep()
        dt = time.perf_counter() - t0
        out[mode] = n_sessions * n_rounds / dt
        toks[mode] = [list(system.sessions[s].tokens) for s in sids]
        if mode == "fused":
            st = system.round_stats
            out["fused_dispatches_per_round"] = (
                (st["embed_dispatches"] + st["tail_dispatches"]
                 + st["hop_dispatches"]) / max(1, st["rounds"]))
    assert toks["fused"] == toks["serial"], \
        "fused and serial reference must emit identical token streams"
    return {"serial_tok_s": out["serial"], "fused_tok_s": out["fused"],
            "speedup": out["fused"] / out["serial"],
            "fused_dispatches_per_round": out["fused_dispatches_per_round"],
            "n_servers": n_servers, "n_sessions": n_sessions}


def shard_decode_throughput(n_sessions: int = 8, n_rounds: int = 4,
                            warm: int = 2, mesh_shape=(1, 1)):
    """Decode throughput of DEVICE-GROUP servers (mesh-sharded pooled
    steps, docs/serving.md "Device-group servers") against the mesh=None
    twin — same cohort, same rounds, token parity asserted at measure
    time.  Defaults to a 1-device mesh so the row runs on any host (the
    sharded-parity CI lane re-proves the multi-device matrix); also
    records the step-cost-calibrated τ (``launch.costs.tau_from_step_cost``)
    that ``GeoServingSystem.calibrated_problem`` folds back into eq. (1)."""
    import time

    import jax

    from repro.configs import get_reduced_config
    from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                            shortest_path_route)
    from repro.launch.mesh import compat_make_mesh
    from repro.models import init_params
    from repro.serving import GeoServingSystem

    L = 8
    lw = Workload(4, warm + n_rounds + 2)
    llm = LLMSpec("shard", L, block_bytes=50.0, cache_bytes_per_token=0.5)
    servers = [ServerSpec(j, 2000.0, 0.004, tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005) for j in range(2)]
    rtt = np.full((1, 2), 0.01)
    problem = Problem(llm, servers, 1, rtt, 3 * rtt, workload=lw)
    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=L)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=lw.l_in)
               for _ in range(n_sessions)]

    mesh = compat_make_mesh(mesh_shape, ("data", "model"))
    out, toks, tau_cal, group_chips = {}, {}, float("nan"), []
    for tag, m in (("twin", None), ("sharded", mesh)):
        system = GeoServingSystem(cfg, params, problem,
                                  algorithm="proposed", R=n_sessions,
                                  max_new_tokens=lw.l_out,
                                  max_sessions=n_sessions, mesh=m)
        sids = []
        for p in prompts:
            route, _ = shortest_path_route(problem,
                                           system.alive_placement(), 0)
            sids.append(system.create_session(p, 0, route, lw.l_out))
        assert len(system.try_admit_sessions(sids)) == n_sessions
        system.drain_prefill()
        for _ in range(warm):
            system.decode_round(sids)
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            system.decode_round(sids)
        dt = time.perf_counter() - t0
        out[tag] = n_sessions * n_rounds / dt
        toks[tag] = [list(system.sessions[s].tokens) for s in sids]
        if tag == "sharded":
            tau_cal = float(min(
                system.calibrate_taus(_tau_device_kind()).values()))
            group_chips = [system.servers[j].n_chips
                           for j in sorted(system.servers)]
    assert toks["sharded"] == toks["twin"], \
        "device-group decode must emit the twin's token stream"
    return {"sharded_tok_s": out["sharded"], "twin_tok_s": out["twin"],
            "ratio": out["sharded"] / out["twin"], "token_parity": 1,
            "tau_calibrated_s": tau_cal,
            "mesh_devices": int(np.prod(mesh_shape)),
            "group_chips": group_chips}


def hetero_validation(n_sessions: int = 6, n_rounds: int = 4,
                      warm: int = 2):
    """Heterogeneous device-group fleet {solo, (1,2) mesh, (2,2) mesh}
    served against the all-solo twin — token AND virtual-clock parity
    asserted at measure time — plus the calibrated-vs-uniform τ placement
    gap (``optgap.hetero``).  Needs 8 host devices, so ``run()`` invokes
    this through the ``--hetero-child`` subprocess (a fresh interpreter
    with ``--xla_force_host_platform_device_count=8``).

    Returns two rows:

    * ``hetero.decode.tput`` — hetero vs twin tokens/s, per-group chip
      counts, the per-server calibrated τ vector and its max/min spread
      (> 1 proves ``calibrate_taus`` is genuinely per-group).
    * ``optgap.hetero`` — CG-BP placements computed under the calibrated
      (normalised to the spec'd τ scale) and under a uniform τ vector on
      the SAME topology; memory caps each server at 6 of 8 blocks so the
      split is placement-sensitive, and the client's RTT favours the SLOW
      solo server so only the calibrated vector pulls blocks onto the big
      mesh groups.  Asserts the placements differ and that the calibrated
      placement costs no more when both are priced under calibrated τ.
    """
    import time

    import jax

    from repro.configs import get_reduced_config
    from repro.core import (LLMSpec, Problem, ServerSpec, Workload, cg_bp,
                            shortest_path_route, with_server_taus)
    from repro.launch.mesh import group_meshes
    from repro.models import init_params
    from repro.serving import GeoServingSystem

    assert len(jax.devices()) >= 8, \
        "hetero_validation needs 8 host devices (run via --hetero-child)"
    L = 8
    lw = Workload(4, warm + n_rounds + 2)
    llm = LLMSpec("hetero", L, block_bytes=50.0, cache_bytes_per_token=0.5)
    servers = [ServerSpec(j, 2000.0, 0.01 * (j + 1), tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005) for j in range(3)]
    rtt = np.full((1, 3), 0.01)
    problem = Problem(llm, servers, 1, rtt, 3 * rtt, workload=lw)
    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=L)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=lw.l_in)
               for _ in range(n_sessions)]

    shapes = {0: None, 1: (1, 2), 2: (2, 2)}
    out, toks, vts, taus, chips = {}, {}, {}, {}, []
    for tag, groups in (("twin", None), ("hetero", group_meshes(shapes))):
        system = GeoServingSystem(cfg, params, problem,
                                  algorithm="proposed", R=3,
                                  max_new_tokens=lw.l_out,
                                  max_sessions=n_sessions,
                                  device_groups=groups)
        sids = []
        for p in prompts:
            route, _ = shortest_path_route(problem,
                                           system.alive_placement(), 0)
            sids.append(system.create_session(p, 0, route, lw.l_out))
        assert len(system.try_admit_sessions(sids)) == n_sessions
        system.drain_prefill()
        for _ in range(warm):
            system.decode_round(sids)
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            system.decode_round(sids)
        dt = time.perf_counter() - t0
        out[tag] = n_sessions * n_rounds / dt
        toks[tag] = [list(system.sessions[s].tokens) for s in sids]
        vts[tag] = [float(system.sessions[s].virtual_time) for s in sids]
        if tag == "hetero":
            taus = system.calibrate_taus(_tau_device_kind())
            chips = [system.servers[j].n_chips
                     for j in sorted(system.servers)]
    assert toks["hetero"] == toks["twin"], \
        "hetero device groups must emit the all-solo twin's token stream"
    assert vts["hetero"] == vts["twin"], \
        "hetero device groups must keep the twin's virtual clocks"
    assert chips == [1, 2, 4], chips
    tau_vec = [taus[j] for j in sorted(taus)]
    tau_spread = max(tau_vec) / min(tau_vec)
    het_row = {"hetero_tok_s": out["hetero"], "twin_tok_s": out["twin"],
               "ratio": out["hetero"] / out["twin"], "token_parity": 1,
               "n_groups": len(chips), "group_chips": chips,
               "taus_s": tau_vec, "tau_spread": tau_spread}

    # --- optgap.hetero: the same 3-group fleet with placement-TIGHT
    # memories (5/3/6 of the 8 blocks) and client RTT favouring the slow
    # solo server.  The calibrated vector is normalised to the spec'd τ
    # scale (mean 0.01 s) so heterogeneity — not the raw-roofline-vs-RTT
    # unit gap — is the only difference from the uniform baseline.
    # CG-BP's m_j is memory-only; τ moves the SPAN assignment, so the gap
    # shows up in (a, m) and in the route cost: under uniform τ the big
    # (2,2) group lands on the tail span and every route must open on the
    # slow solo server; calibrated τ pulls it to the head span.
    tau_ref = 0.01
    mean_tau = sum(tau_vec) / len(tau_vec)
    scaled = {j: tau_ref * taus[j] / mean_tau for j in taus}
    opt_lw = Workload(4, 8)
    mems = (290.0, 180.0, 350.0)
    tight = [ServerSpec(j, mems[j], tau_ref, tau_prefill_base=0.002,
                        tau_prefill_per_token=0.0005) for j in range(3)]
    rtt_skew = np.array([[0.002, 0.004, 0.006]])
    base = Problem(llm, tight, 1, rtt_skew, 3 * rtt_skew, workload=opt_lw)
    cal_prob = with_server_taus(base, scaled)
    pl_cal, info_cal = cg_bp(cal_prob, 1)
    pl_uni, info_uni = cg_bp(base, 1)
    assert info_cal.feasible and info_uni.feasible
    _, cost_cal = shortest_path_route(cal_prob, pl_cal, 0)
    _, cost_uni = shortest_path_route(cal_prob, pl_uni, 0)
    differs = int(not (np.array_equal(pl_cal.m, pl_uni.m)
                       and np.array_equal(pl_cal.a, pl_uni.a)))
    assert differs, (list(pl_cal.a), list(pl_uni.a))
    assert cost_cal <= cost_uni * (1 + 1e-9), (cost_cal, cost_uni)
    og_row = {"cost_calibrated_s": float(cost_cal),
              "cost_uniform_s": float(cost_uni),
              "optgap_frac": float((cost_uni - cost_cal) / cost_uni),
              "placement_differs": differs,
              "m_calibrated": [int(v) for v in pl_cal.m],
              "a_calibrated": [int(v) for v in pl_cal.a],
              "m_uniform": [int(v) for v in pl_uni.m],
              "a_uniform": [int(v) for v in pl_uni.a],
              "tau_scaled_s": [scaled[j] for j in sorted(scaled)]}
    return {"hetero.decode.tput": het_row, "optgap.hetero": og_row}


def _hetero_rows(smoke: bool = False):
    """Rows of :func:`hetero_validation`.  Off-TPU: spawn a fresh
    interpreter with 8 forced host CPU devices (this process's jax device
    count is frozen at first import) and parse the child's JSON rows.  On a
    TPU host this process holds the chips, so a child could not reach them:
    the rows run here when the host has the 8 devices they need, and are
    otherwise not produced (None, with a line saying so)."""
    import jax

    if jax.default_backend() == "tpu":
        if len(jax.devices()) >= 8:
            return hetero_validation(n_rounds=2 if smoke else 4)
        print(f"hetero.decode.tput / optgap.hetero not produced: they need "
              f"8 devices, this TPU host has {len(jax.devices())}")
        return None

    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, os.path.abspath(__file__), "--hetero-child"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"--hetero-child failed:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _one_server_problem(slab_cap: int, l_out: int = 60):
    """One server hosting the whole 8-block stack with cache memory for
    EXACTLY ``slab_cap`` worst-case sessions — the fixed-width co-residency
    cap the paged layout is measured against."""
    from repro.core import LLMSpec, Problem, ServerSpec, Workload

    L, block_bytes = 8, 50.0
    lw = Workload(4, l_out)
    llm = LLMSpec("paged", L, block_bytes, cache_bytes_per_token=0.5)
    s_c = 0.5 * lw.total_tokens
    mem = block_bytes * L + s_c * slab_cap * L
    servers = [ServerSpec(0, mem, 0.004, tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)]
    rtt = np.array([[0.01]])
    return Problem(llm, servers, 1, rtt, 3 * rtt, workload=lw)


def _paged_cohort(problem, cfg, params, layout, n_sessions, n_new,
                  page_size=None, R=None):
    from repro.core import shortest_path_route
    from repro.serving import GeoServingSystem

    # R is the DESIGN concurrency CG-BP reserves worst-case memory for;
    # the paged layout oversubscribes past it at the pool level
    system = GeoServingSystem(
        cfg, params, problem, algorithm="proposed", R=R or n_sessions,
        max_new_tokens=problem.workload.l_out, max_sessions=n_sessions,
        decode_mode="fused", cache_layout=layout, page_size=page_size)
    rng = np.random.default_rng(0)
    sids = []
    for _ in range(n_sessions):
        route, _ = shortest_path_route(problem, system.alive_placement(), 0)
        sids.append(system.create_session(
            rng.integers(2, cfg.vocab_size, size=problem.workload.l_in),
            0, route, n_new))
    admitted = system.try_admit_sessions(sids)
    return system, sids, admitted


def paged_decode_throughput(n_sessions: int = 128, slab_cap: int = 32,
                            n_new: int = 4):
    """The paged co-residency headline (``decode.tput.R128``): sessions
    book prompt pages and grow on demand, so the SAME topology whose
    worst-case eq. (5) budget caps the slab layout at ``slab_cap``
    co-resident sessions holds the whole ``n_sessions`` cohort — measured
    admissions on both layouts plus the fused decode tokens/s of the full
    paged cohort (jit-warm is the prefill drain; rounds are timed)."""
    import time

    import jax

    from repro.configs import get_reduced_config
    from repro.models import init_params

    problem = _one_server_problem(slab_cap)
    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=problem.L)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)

    slab_sys, _, slab_admitted = _paged_cohort(
        problem, cfg, params, "slab", n_sessions, n_new, R=slab_cap)
    paged_sys, sids, paged_admitted = _paged_cohort(
        problem, cfg, params, "paged", n_sessions, n_new, page_size=2,
        R=slab_cap)
    assert len(paged_admitted) == n_sessions, \
        "paged admission must hold the whole cohort"
    paged_sys.drain_prefill()
    t0 = time.perf_counter()
    rounds = 0
    while any(paged_sys.sessions[s].n_generated < n_new for s in sids):
        paged_sys.decode_round()
        rounds += 1
    dt = time.perf_counter() - t0
    return {"paged_tok_s": n_sessions * n_new / dt,
            "slab_coresident": len(slab_admitted),
            "paged_coresident": len(paged_admitted),
            "coresidency_ratio": len(paged_admitted)
            / max(1, len(slab_admitted)),
            "rounds": rounds,
            "preemptions": paged_sys.round_stats["preemptions"]}


def oversubscription_scenario(n_sessions: int = 10, slab_cap: int = 2,
                              n_new: int = 30):
    """The preemption acceptance scenario (``oversub``): a cohort whose
    combined worst case overbooks the slab budget — slab admission REFUSES
    part of it, paged admission takes everything and serves it to
    completion by swapping sessions under page pressure (>= 1 preemption +
    resume), bit-exact per the tests; here we record the counts."""
    import jax

    from repro.configs import get_reduced_config
    from repro.models import init_params

    problem = _one_server_problem(slab_cap, l_out=n_new)
    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=problem.L)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)

    _, _, slab_admitted = _paged_cohort(
        problem, cfg, params, "slab", n_sessions, n_new, R=slab_cap)
    assert len(slab_admitted) < n_sessions, \
        "scenario must overbook the slab budget"
    paged_sys, sids, paged_admitted = _paged_cohort(
        problem, cfg, params, "paged", n_sessions, n_new, page_size=2,
        R=slab_cap)
    assert len(paged_admitted) == n_sessions
    paged_sys.drain_prefill()
    rounds = 0
    while any(paged_sys.sessions[s].n_generated < n_new for s in sids):
        paged_sys.decode_round()
        rounds += 1
        assert rounds < 20000, "oversubscribed cohort failed to converge"
    completed = sum(paged_sys.sessions[s].n_generated >= n_new
                    for s in sids)
    return {"n_sessions": n_sessions,
            "slab_admitted": len(slab_admitted),
            "paged_admitted": len(paged_admitted),
            "completed": completed, "rounds": rounds,
            "preemptions": paged_sys.round_stats["preemptions"],
            "resumes": paged_sys.round_stats["resumes"]}


def _assert_sim_parity(ref, fast):
    """The bit-exact twin contract: identical per-request rows (route,
    start, wait, every timing field) and identical aggregate metrics.
    ``decision_time_s`` is wall-clock and deliberately NOT part of it."""
    assert ref.requests == fast.requests, "fast/reference rows diverge"
    for f in ("drop_rate", "wait", "first_token", "per_token_rest",
              "per_token_all"):
        assert getattr(ref, f) == getattr(fast, f), (f, ref, fast)


def sim_throughput(n_requests: int = 2000, rate: float = 5.0, seed: int = 0):
    """Requests/s of the CPU-only discrete-event simulator on one long
    Poisson trace, measured for BOTH execution modes on the SAME trace:
    the per-request reference loop and the array-native fast engine
    (retirement-heap usage counters + memoized zero-wait decisions).
    Exact row parity is asserted before either number is recorded."""
    import time

    from repro.sim import SimConfig, simulate
    from repro.sim.workload import poisson_requests

    problem = _concurrency_problem()
    requests = poisson_requests(n_requests, rate, seed=seed)
    results, wall = {}, {}
    for mode in ("reference", "fast"):
        t0 = time.perf_counter()
        results[mode] = simulate(
            problem, SimConfig("proposed", n_requests=n_requests, rate=rate,
                               seed=seed, R=8, sim_mode=mode),
            requests=requests)
        wall[mode] = time.perf_counter() - t0
    _assert_sim_parity(results["reference"], results["fast"])
    st = results["fast"].fast_stats or {}
    return {"requests_per_s": n_requests / wall["reference"],
            "requests_per_s_reference": n_requests / wall["reference"],
            "requests_per_s_fast": n_requests / wall["fast"],
            "speedup": wall["reference"] / wall["fast"],
            "n_requests": n_requests, "wall_s": wall["reference"],
            "wall_s_fast": wall["fast"],
            "drop_rate": results["reference"].drop_rate,
            "fast_frac": st.get("fast_routes", 0) / max(1, n_requests),
            "parity": 1, "sim_mode": "both"}


def sim_throughput_1m(n_requests: int = 1_000_000, base_rate: float = 40.0,
                      peak_rate: float = 200.0, period: float = 7200.0,
                      seed: int = 0):
    """The planet-scale headline: a 1M-request diurnal trace (thinned
    nonhomogeneous Poisson, ~1.3 day-cycles) through the fast engine with
    array-backed metrics (``collect_rows=False``).  A 2000-request prefix
    is first replayed through BOTH modes with full rows as the exactness
    spot-check; trace generation is timed separately from the event loop."""
    import time

    from repro.sim import SimConfig, simulate
    from repro.sim.workload import diurnal_requests

    problem = _planet_problem()

    def _cfg(mode, n, collect):
        return SimConfig("proposed", n_requests=n, rate=1.0, seed=seed,
                         R=8, sim_mode=mode, collect_rows=collect)

    # both-modes parity spot-check on a prefix trace
    n_spot = min(2000, n_requests)
    spot = diurnal_requests(n_spot, base_rate, peak_rate, period=period,
                            n_clients=problem.n_clients, seed=seed)
    _assert_sim_parity(
        simulate(problem, _cfg("reference", n_spot, True), requests=spot),
        simulate(problem, _cfg("fast", n_spot, True), requests=spot))

    t0 = time.perf_counter()
    batch = diurnal_requests(n_requests, base_rate, peak_rate, period=period,
                             n_clients=problem.n_clients, seed=seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = simulate(problem, _cfg("fast", n_requests, False), requests=batch)
    wall = time.perf_counter() - t0
    st = res.fast_stats or {}
    return {"requests_per_s": n_requests / wall, "n_requests": n_requests,
            "wall_s": wall, "trace_gen_s": gen_s,
            "trace_span_s": float(batch.arrival[-1]),
            "drop_rate": res.drop_rate, "wait": res.wait,
            "fast_frac": st.get("fast_routes", 0) / max(1, n_requests),
            "compactions": st.get("compactions", 0),
            "parity_spot_check": 1, "sim_mode": "fast"}


def sim_churn_study(n_servers: int = 120, n_requests: int = 2000,
                    rate: float = 20.0, n_storms: int = 6,
                    storm_size: int = 10, seed: int = 3):
    """Elastic-fleet churn: a 120-server fleet serving a Poisson trace
    while timed storms knock out / revive ``storm_size`` servers at a
    time.  Each storm triggers ``OnlineBPRR.replace_servers`` — a full
    CG-BP re-placement plus ``RouteCostCache`` invalidation — and the
    study reports how routing survives it (drops, waits, fleet size)."""
    import time

    from repro.sim import simulate_churn
    from repro.sim.workload import churn_schedule, poisson_requests

    problem = _fleet_problem(n_servers=n_servers)
    requests = poisson_requests(n_requests, rate=rate, seed=seed,
                                n_clients=problem.n_clients)
    span = n_requests / rate
    spacing = span / (n_storms + 1)
    schedule = churn_schedule(n_servers, n_storms=n_storms,
                              storm_size=storm_size, first=spacing,
                              spacing=spacing, seed=1)
    t0 = time.perf_counter()
    res = simulate_churn(problem, requests, schedule, R=16)
    wall = time.perf_counter() - t0
    return {"n_servers": n_servers, "n_requests": n_requests,
            "n_storms": res.n_storms, "n_replacements": res.n_replacements,
            "drop_rate": res.drop_rate, "wait": res.wait,
            "per_token_all": res.per_token_all, "alive_min": res.alive_min,
            "requests_per_s": n_requests / wall, "wall_s": wall}


def chaos_recovery_study(n_sessions: int = 6, n_new: int = 12,
                         kill_round: int = 3, victim: int = 5):
    """Crash-recovery latency + goodput, engine-vs-simulator
    cross-validated.

    An 8-server toy fleet serves ``n_sessions`` single-hop sessions; a
    :class:`FaultPlan` crashes ``victim``'s server silently after
    ``kill_round`` decode rounds.  The ENGINE discovers the loss by
    missed deadline, bills detection + backoff + failover replay on the
    virtual clock, and finishes every stream.  The SIMULATOR side prices
    the same recovery analytically from the shared components —
    ``FailureDetector.detect_time``/``backoff_time``, the
    ``subchain_route`` splice, and ``recovery_replay_cost`` with the
    known replay token count — and the two totals must agree to float
    precision (``recovery_parity``).  Goodput is tokens over the fleet
    makespan, reported against the fault-free twin; the default victim
    is the slowest host (the makespan holder), so the billed recovery
    visibly dents fleet goodput."""
    import jax

    from repro.configs import get_reduced_config
    from repro.core import LLMSpec, Problem, Route, ServerSpec, Workload
    from repro.models import init_params
    from repro.serving import (FailureDetector, FaultEvent, FaultPlan,
                               GeoServingSystem)
    from repro.serving.faults import recovery_replay_cost
    from repro.sim import subchain_route

    cfg = get_reduced_config("llama3_2_1b")
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    n_servers, l_in = 8, 4
    detector = FailureDetector(timeout_factor=3.0, backoff_base=0.01,
                               backoff_cap=0.04)

    def build(plan=None):
        llm = LLMSpec("toy", cfg.n_layers, block_bytes=50.0,
                      cache_bytes_per_token=1.0)
        servers = [ServerSpec(j, mem_bytes=900.0, tau=0.01 * (j + 1),
                              tau_prefill_base=0.002,
                              tau_prefill_per_token=0.0005)
                   for j in range(n_servers)]
        rtt = np.full((1, n_servers), 0.02)
        prob = Problem(llm, servers, 1, rtt, rtt * 3,
                       workload=Workload(l_in, n_new))
        system = GeoServingSystem(cfg, params, prob, R=4,
                                  max_new_tokens=n_new,
                                  max_sessions=n_sessions + 2,
                                  fault_plan=plan, detector=detector)
        rng = np.random.RandomState(0)
        sids = []
        for j in range(n_sessions):
            a, m = int(system.placement.a[j]), int(system.placement.m[j])
            assert a == 0 and m == prob.L, "toy placement must replicate"
            sids.append(system.create_session(
                rng.randint(2, cfg.vocab_size, l_in), 0,
                Route(servers=(j,), blocks=(m,)), n_new))
        assert system.try_admit_sessions(sids) == sids
        system.drain_prefill()
        return prob, system, sids

    def drive(system, sids):
        done = {}
        while len(done) < len(sids):
            for sid in sids:
                sess = system.sessions.get(sid)
                if sid not in done and (sess.state == "failed"
                                        or sess.n_generated >= n_new):
                    done[sid] = system.retire_session(sid)
            if len(done) < len(sids):
                system.decode_round()
        return done

    # fault-free twin: baseline makespan/goodput + the crash's clock time
    prob, twin_sys, twin_sids = build()
    clocks = {s: twin_sys.sessions[s].virtual_time for s in twin_sids}
    ptok = {s: twin_sys.sessions[s].per_token_time for s in twin_sids}
    twin = drive(twin_sys, twin_sids)
    # deliver the crash just before the min member clock crosses into
    # round kill_round+1, so exactly kill_round decoded tokens replay
    t_kill = min(clocks[s] + kill_round * ptok[s] for s in twin_sids) - 1e-9
    makespan0 = max(s.start + s.virtual_time for s in twin.values())
    goodput0 = n_sessions * n_new / makespan0

    plan = FaultPlan([FaultEvent(time=t_kill, kind="crash", server=victim)])
    prob, system, sids = build(plan)
    done = drive(system, sids)
    vic = done[sids[victim]]
    makespan1 = max(s.start + s.virtual_time for s in done.values())
    goodput1 = n_sessions * n_new / makespan1

    # simulator-side analytic prediction from the SHARED pricing pieces
    expected_hop = float(prob.rtt_token[0, victim]
                         + prob.llm.tau_weight(0, prob.L)
                         * prob.servers[victim].tau)
    spliced = subchain_route(prob, twin_sys.placement, {victim},
                             0, prob.L, 0)
    repl, e = [], 0
    for j, k in zip(spliced.servers, spliced.blocks):
        repl.append((j, e, e + k))
        e += k
    predicted = (detector.detect_time(expected_hop)
                 + detector.backoff_time()
                 + recovery_replay_cost(prob, 0, repl, kill_round,
                                        l_in=l_in))
    err = abs(vic.recovery_time - predicted) / predicted
    assert vic.route.servers == spliced.servers, (vic.route, spliced)
    served = sum(1 for s in done.values() if s.state != "failed")
    return {"n_sessions": n_sessions, "served": served,
            "n_detections": int(vic.n_detections),
            "n_replays": int(vic.n_replays),
            "recovery_s": float(vic.recovery_time),
            "detect_s": float(vic.detect_time),
            "backoff_s": float(vic.backoff_time),
            "replay_s": float(vic.replay_time),
            "predicted_recovery_s": float(predicted),
            "recovery_err": float(err),
            "recovery_parity": int(err < 1e-6),
            "goodput_tok_s": float(goodput1),
            "goodput_fault_free_tok_s": float(goodput0),
            "goodput_frac": float(goodput1 / goodput0)}


def sim_scale_smoke(n_requests: int = 50_000, budget_s: float = 60.0):
    """Bounded CI scale check (the ``--sim-scale`` job): a 50k-request
    diurnal trace through the fast engine must finish under the wall
    budget on a cold CI runner.  Raises on budget overrun or drops."""
    import time

    from repro.sim import SimConfig, simulate
    from repro.sim.workload import diurnal_requests

    problem = _planet_problem()
    batch = diurnal_requests(n_requests, 40.0, 200.0, period=7200.0,
                             n_clients=problem.n_clients, seed=0)
    t0 = time.perf_counter()
    res = simulate(problem,
                   SimConfig("proposed", n_requests=n_requests, rate=1.0,
                             seed=0, R=8, sim_mode="fast",
                             collect_rows=False),
                   requests=batch)
    wall = time.perf_counter() - t0
    assert res.sim_mode == "fast", res.sim_mode
    assert wall < budget_s, \
        f"{n_requests} requests took {wall:.1f}s (budget {budget_s:.0f}s)"
    return {"n_requests": n_requests, "wall_s": wall,
            "requests_per_s": n_requests / wall, "budget_s": budget_s,
            "drop_rate": res.drop_rate}


def _emit_xval(name: str, eng, simm, err, us):
    emit(name, us,
         f"per_token eng={eng['per_token_all']*1e3:.2f}ms "
         f"sim={simm['per_token_all']*1e3:.2f}ms "
         f"err={err['per_token_all']:.1%} | "
         f"first_token eng={eng['first_token']*1e3:.1f}ms "
         f"sim={simm['first_token']*1e3:.1f}ms "
         f"err={err['first_token']:.1%} | "
         f"max_conc={eng['max_concurrency']}")
    _record(name, per_token_eng=eng["per_token_all"],
            per_token_sim=simm["per_token_all"],
            first_token_eng=eng["first_token"],
            first_token_sim=simm["first_token"],
            err_per_token=err["per_token_all"],
            err_first_token=err["first_token"],
            max_concurrency=eng["max_concurrency"])


def run(full: bool = False, smoke: bool = False):
    """``smoke``: reduced trace sizes + the essential scenario per class —
    the CI job that keeps the perf trajectory populated."""
    import jax

    from repro.configs import get_reduced_config
    from repro.core import LLMSpec, Problem, ServerSpec, Workload
    from repro.models import init_params
    from repro.serving import GeoServingSystem, generate

    cfg = get_reduced_config("llama3_2_1b").replace(n_layers=8)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    tau = 0.01

    # Fig 2b analogue: virtual per-token time vs #blocks on one server.
    times = {}
    for m_blocks in (2, 4, 8):
        llm = LLMSpec("t", cfg.n_layers, 10.0, 0.5)
        # one big server forced to host everything + tiny helpers
        servers = [ServerSpec(0, 10.0 * m_blocks + 50, tau)]
        if m_blocks < cfg.n_layers:
            servers += [ServerSpec(1, 10.0 * (cfg.n_layers - m_blocks) + 50,
                                   tau)]
        rtt = np.full((1, len(servers)), 0.005)
        prob = Problem(llm, servers, 1, rtt, rtt, workload=Workload(4, 8))
        system = GeoServingSystem(cfg, params, prob, algorithm="proposed",
                                  R=1, max_new_tokens=8)
        toks = np.arange(4) + 2
        (out, vt), us = timed(generate, system, toks, 6)
        times[m_blocks] = vt / 7  # per forward
        emit(f"perfmodel.blocks{m_blocks}", us,
             f"virtual_per_token={vt/7*1e3:.2f}ms")
        _record(f"perfmodel.blocks{m_blocks}",
                virtual_per_token_s=vt / 7)
    # linearity check: time(8 blocks)/time(2 blocks) tracks the block ratio
    # modulo the constant RTT term
    t2, t8 = times[2], times[8]
    rtt_const = 0.005
    slope2 = (t2 - 2 * rtt_const)
    slope8 = (t8 - rtt_const)
    emit("perfmodel.linearity", 0.0,
         f"per-block slope (2-block route)={slope2/2*1e3:.2f}ms "
         f"(8-block)={slope8/8*1e3:.2f}ms (model tau={tau*1e3:.1f}ms)")
    _record("perfmodel.linearity", slope2_s=slope2 / 2, slope8_s=slope8 / 8,
            model_tau_s=tau)

    # §4-style cross-validation under concurrency
    n_requests = 8 if smoke else (20 if full else 10)
    for R in ((4,) if smoke else (1, 4, 8)):
        (eng, simm, err), us = timed(cross_validate, R,
                                     n_requests=n_requests)
        _emit_xval(f"xval.R{R}", eng, simm, err, us)

    # bursty arrivals: same-timestamp bursts admit as ONE bucket group —
    # the coalescable-prefill workload for the batched prefill path
    for R in ((4,) if smoke else (4, 8)):
        (eng, simm, err), us = timed(cross_validate, R,
                                     n_requests=n_requests, trace="bursty")
        _emit_xval(f"xval.bursty.R{R}", eng, simm, err, us)

    # hybrid topology: zamba2-style stack (mamba + shared-attention blocks)
    # with per-family block compute weights — the family-polymorphic state
    # pools against the simulator's weighted eq. (1)
    for R in ((4,) if smoke else (4, 8)):
        (eng, simm, err), us = timed(cross_validate, R,
                                     n_requests=n_requests,
                                     arch="zamba2_7b")
        _emit_xval(f"xval.hybrid.R{R}", eng, simm, err, us)

    # measured prefill throughput: serial (one session per call) vs the
    # bucket-group batched path, same burst, jit-warm
    tput, us = timed(prefill_throughput, R=4, burst=4 if smoke else 8)
    emit("prefill.tput.R4", us,
         f"serial={tput['serial']:.0f} tok/s "
         f"batched={tput['batched']:.0f} tok/s "
         f"speedup={tput['batched'] / tput['serial']:.2f}x")
    _record("prefill.tput.R4", serial_tok_s=tput["serial"],
            batched_tok_s=tput["batched"],
            speedup=tput["batched"] / tput["serial"])

    # measured DECODE throughput — the headline the ROADMAP north-star
    # cares about: device-resident fused rounds vs the per-session serial
    # reference, warm, compile excluded.  R32 is the deliberately larger
    # scenario (8 servers, 32 co-resident sessions, 8-hop routes).
    for name, ns, nsess in (("decode.tput.R8", 2, 8),
                            ("decode.tput.R32", 8, 32)):
        row, us = timed(decode_throughput, n_servers=ns, n_sessions=nsess,
                        n_rounds=2 if smoke else 4)
        emit(name, us,
             f"serial={row['serial_tok_s']:.0f} tok/s "
             f"fused={row['fused_tok_s']:.0f} tok/s "
             f"speedup={row['speedup']:.2f}x "
             f"dispatches/round={row['fused_dispatches_per_round']:.0f}")
        _record(name, **row)

    # device-group serving: mesh-sharded pooled steps vs the mesh=None
    # twin (token parity asserted inside), plus the step-cost-calibrated τ
    row, us = timed(shard_decode_throughput, n_rounds=2 if smoke else 4)
    emit("shard.decode.tput", us,
         f"sharded={row['sharded_tok_s']:.0f} tok/s "
         f"twin={row['twin_tok_s']:.0f} tok/s ratio={row['ratio']:.2f}x "
         f"tau_cal={row['tau_calibrated_s']*1e6:.3f}us "
         f"({row['mesh_devices']} device(s))")
    _record("shard.decode.tput", **row)

    # heterogeneous device groups: a {solo, (1,2), (2,2)} fleet vs the
    # all-solo twin (token + virtual-clock parity asserted when measured)
    # and the calibrated-vs-uniform τ CG-BP placement gap.  Off-TPU it runs
    # in a fresh interpreter because this process's jax device count is
    # frozen at first import and the matrix needs 8 forced host devices.
    rows, us = timed(_hetero_rows, smoke=smoke)
    if rows is not None:
        het, og = rows["hetero.decode.tput"], rows["optgap.hetero"]
        emit("hetero.decode.tput", us,
             f"hetero={het['hetero_tok_s']:.0f} tok/s "
             f"twin={het['twin_tok_s']:.0f} tok/s "
             f"ratio={het['ratio']:.2f}x chips={het['group_chips']} "
             f"tau_spread={het['tau_spread']:.2f}x")
        _record("hetero.decode.tput", **het)
        emit("optgap.hetero", 0.0,
             f"calibrated={og['cost_calibrated_s']*1e3:.1f}ms "
             f"uniform={og['cost_uniform_s']*1e3:.1f}ms "
             f"gap={og['optgap_frac']*100:.0f}% "
             f"placement_differs={og['placement_differs']}")
        _record("optgap.hetero", **og)

    # paged cache pools: co-residency headline (the same topology's
    # worst-case budget caps slab at 1/4 of the cohort) + the
    # oversubscription-with-preemption scenario
    row, us = timed(paged_decode_throughput,
                    n_sessions=32 if smoke else 128,
                    slab_cap=8 if smoke else 32)
    emit("decode.tput.R128", us,
         f"paged={row['paged_tok_s']:.0f} tok/s "
         f"coresident {row['paged_coresident']} vs slab cap "
         f"{row['slab_coresident']} "
         f"({row['coresidency_ratio']:.1f}x)")
    _record("decode.tput.R128", **row)

    ov, us = timed(oversubscription_scenario,
                   n_sessions=6 if smoke else 10,
                   n_new=12 if smoke else 30)
    emit("oversub", us,
         f"slab admits {ov['slab_admitted']}/{ov['n_sessions']}, paged "
         f"serves {ov['completed']}/{ov['n_sessions']} to completion "
         f"({ov['preemptions']} preemptions, {ov['resumes']} resumes)")
    _record("oversub", **ov)

    # simulator throughput on a long trace, BOTH modes on the same trace
    # (exact row parity asserted inside before either number is recorded)
    st, us = timed(sim_throughput, n_requests=600 if smoke else 2000)
    emit("sim.tput", us,
         f"ref={st['requests_per_s_reference']:.0f} req/s "
         f"fast={st['requests_per_s_fast']:.0f} req/s "
         f"speedup={st['speedup']:.1f}x over {st['n_requests']} "
         f"requests (drop_rate={st['drop_rate']:.2f})")
    _record("sim.tput", **st)

    # planet-scale headline: 1M-request diurnal trace through the fast
    # engine (2k-request both-modes parity spot-check runs first)
    st, us = timed(sim_throughput_1m,
                   n_requests=20_000 if smoke else 1_000_000)
    emit("sim.tput.1M", us,
         f"{st['requests_per_s']:.0f} req/s over {st['n_requests']} "
         f"requests, {st['trace_span_s']/3600:.1f}h simulated in "
         f"{st['wall_s']:.1f}s (fast_frac={st['fast_frac']:.3f}, "
         f"drop_rate={st['drop_rate']:.3f})")
    _record("sim.tput.1M", **st)

    # chaos recovery: silent crash of the makespan-critical server,
    # timeout-detected and billed by the engine, priced analytically by
    # the simulator side from the shared detector/splice/replay pieces
    cr, us = timed(chaos_recovery_study)
    emit("chaos.recovery", us,
         f"recovery={cr['recovery_s']:.3f}s "
         f"(predicted {cr['predicted_recovery_s']:.3f}s, "
         f"err={cr['recovery_err']:.1e}), served "
         f"{cr['served']}/{cr['n_sessions']}, "
         f"goodput_frac={cr['goodput_frac']:.2f}")
    _record("chaos.recovery", **cr)

    # elastic-fleet churn: 120 servers, timed join/leave storms, each one
    # a full CG-BP re-placement through OnlineBPRR.replace_servers
    ch, us = timed(sim_churn_study,
                   n_requests=600 if smoke else 2000,
                   n_storms=3 if smoke else 6)
    emit("sim.churn", us,
         f"{ch['n_servers']} servers, {ch['n_replacements']} re-placements "
         f"over {ch['n_storms']} storms, alive_min={ch['alive_min']}, "
         f"drop_rate={ch['drop_rate']:.3f}, "
         f"{ch['requests_per_s']:.0f} req/s")
    _record("sim.churn", **ch)

    # kernel-backend throughput: pallas-vs-xla ratio per serving hot path
    # (decode attention / flash prefill).  On this CPU container the pallas
    # side runs in interpret mode, so the ratio is a placeholder (<~1x);
    # the recorded field is the hook real-TPU runs fill with the true
    # kernel speedup.
    from benchmarks.kernel_bench import throughput_scenarios

    kt, us = timed(throughput_scenarios, full=full)
    for name, row in kt.items():
        emit(name, us / len(kt),
             f"pallas={row['pallas_tok_s']:.0f} tok/s "
             f"xla={row['xla_tok_s']:.0f} tok/s "
             f"ratio={row['pallas_over_xla']:.2f}x")
        _record(name, **row)


def write_json(path: str):
    """Dump the collected scenario metrics as machine-readable JSON."""
    payload = {"benchmark": "engine_validation", "scenarios": _RESULTS}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path} ({len(_RESULTS)} scenarios)")


# scenarios the committed BENCH_engine.json must carry, with the fields
# (and floors) CI verifies WITHOUT re-timing anything — wall-clock numbers
# are whatever the committed full run measured; only structure and the
# machine-independent ratios/counters are checked
_REQUIRED_ROWS = {
    "perfmodel.blocks2": ("virtual_per_token_s",),
    "perfmodel.blocks8": ("virtual_per_token_s",),
    "xval.R4": ("err_per_token", "err_first_token"),
    "prefill.tput.R4": ("serial_tok_s", "batched_tok_s", "speedup"),
    "decode.tput.R8": ("serial_tok_s", "fused_tok_s", "speedup"),
    "decode.tput.R32": ("serial_tok_s", "fused_tok_s", "speedup"),
    "shard.decode.tput": ("sharded_tok_s", "twin_tok_s", "ratio",
                          "token_parity", "tau_calibrated_s"),
    "hetero.decode.tput": ("hetero_tok_s", "twin_tok_s", "ratio",
                           "token_parity", "tau_spread", "n_groups"),
    "optgap.hetero": ("cost_calibrated_s", "cost_uniform_s",
                      "optgap_frac", "placement_differs"),
    "decode.tput.R128": ("paged_tok_s", "slab_coresident",
                         "paged_coresident", "coresidency_ratio"),
    "oversub": ("n_sessions", "slab_admitted", "paged_admitted",
                "completed", "preemptions", "resumes"),
    "sim.tput": ("requests_per_s", "requests_per_s_reference",
                 "requests_per_s_fast", "speedup", "parity"),
    "sim.tput.1M": ("requests_per_s", "n_requests", "wall_s",
                    "parity_spot_check", "fast_frac"),
    "sim.churn": ("n_servers", "n_requests", "n_replacements",
                  "drop_rate", "alive_min"),
    "chaos.recovery": ("recovery_s", "predicted_recovery_s",
                       "recovery_parity", "goodput_frac", "served",
                       "n_sessions"),
}


def check_json(path: str) -> int:
    """``--check-only``: validate the structure of a committed
    BENCH_engine.json (the CI path — no flaky wall-clock re-timing).
    Returns the number of scenarios checked; raises on any violation."""
    with open(path) as f:
        payload = json.load(f)
    assert payload.get("benchmark") == "engine_validation", path
    data = payload["scenarios"]
    for name, fields in _REQUIRED_ROWS.items():
        assert name in data, f"missing scenario {name!r}"
        for field in fields:
            v = data[name].get(field)
            assert isinstance(v, (int, float)) and np.isfinite(v), \
                f"{name}.{field} missing or non-finite: {v!r}"
    for name, row in data.items():
        if name.startswith("xval."):
            assert row["err_per_token"] < 0.10, (name, row)
            assert row["err_first_token"] < 0.10, (name, row)
    # machine-independent floors: the existing speedup ratios plus the
    # paged acceptance criteria (>= 4x co-residency on the same topology;
    # the oversubscribed cohort fully served with actual preemption)
    assert data["prefill.tput.R4"]["speedup"] > 1.0
    assert data["decode.tput.R32"]["speedup"] >= 2.0
    r128 = data["decode.tput.R128"]
    assert r128["coresidency_ratio"] >= 4.0, r128
    # device-group serving: parity is pass/fail (asserted when measured),
    # the calibrated τ must be a usable eq. (1) input
    shard = data["shard.decode.tput"]
    assert shard["token_parity"] == 1, shard
    assert shard["tau_calibrated_s"] > 0 and shard["ratio"] > 0, shard
    # heterogeneous device groups: parity is pass/fail, the calibrated τ
    # vector must be genuinely per-group (spread > 1), and the CG-BP
    # placement under calibrated τ must differ from — and, priced under
    # calibrated τ, cost no more than — the uniform-τ placement
    het = data["hetero.decode.tput"]
    assert het["token_parity"] == 1 and het["ratio"] > 0, het
    assert het["tau_spread"] > 1.0 and het["n_groups"] >= 3, het
    og = data["optgap.hetero"]
    assert og["placement_differs"] == 1, og
    assert og["cost_calibrated_s"] > 0, og
    assert og["optgap_frac"] >= 0.0, og
    assert og["cost_calibrated_s"] <= og["cost_uniform_s"] * (1 + 1e-9), og
    ov = data["oversub"]
    assert ov["slab_admitted"] < ov["n_sessions"], ov
    assert ov["completed"] == ov["n_sessions"] == ov["paged_admitted"], ov
    assert ov["preemptions"] >= 1 and ov["resumes"] >= 1, ov
    # planet-scale simulator floors: exact fast/reference parity was
    # asserted when measured (pass/fail flags), the fast engine must not
    # be slower than the reference, and the 1M-request study must clear
    # 20x the same file's reference throughput on the same machine
    st = data["sim.tput"]
    assert st["parity"] == 1 and st["speedup"] >= 1.0, st
    m1 = data["sim.tput.1M"]
    assert m1["parity_spot_check"] == 1, m1
    assert m1["n_requests"] >= 1_000_000, m1
    assert m1["requests_per_s"] >= 20 * st["requests_per_s_reference"], \
        (m1, st)
    assert 0.0 < m1["fast_frac"] <= 1.0, m1
    ch = data["sim.churn"]
    assert ch["n_servers"] >= 100 and ch["n_replacements"] >= 1, ch
    assert 0.0 <= ch["drop_rate"] <= 0.5, ch
    assert 0 < ch["alive_min"] <= ch["n_servers"], ch
    # chaos recovery: engine-vs-simulator recovery pricing must agree
    # (shared detector/splice/replay pieces — pass/fail, not a tolerance),
    # every session survives the crash, and the billed recovery costs
    # real goodput without collapsing it
    cr = data["chaos.recovery"]
    assert cr["recovery_parity"] == 1 and cr["recovery_s"] > 0.0, cr
    assert cr["served"] == cr["n_sessions"], cr
    assert 0.3 <= cr["goodput_frac"] <= 1.0, cr
    print(f"OK: {len(data)} scenarios, all {len(_REQUIRED_ROWS)} required "
          f"rows present; decode R32 speedup "
          f"{data['decode.tput.R32']['speedup']:.2f}x, paged co-residency "
          f"{r128['coresidency_ratio']:.1f}x, oversub served "
          f"{ov['completed']}/{ov['n_sessions']} with "
          f"{ov['preemptions']} preemptions, sim 1M at "
          f"{m1['requests_per_s']/st['requests_per_s_reference']:.0f}x "
          f"reference")
    return len(data)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="longer traces (20 requests per scenario)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scenario set for CI")
    ap.add_argument("--check-only", action="store_true",
                    help="validate the committed --json file's structure "
                         "and ratio floors without re-timing anything")
    ap.add_argument("--hetero-child", action="store_true",
                    help="run ONLY the heterogeneous device-group scenarios "
                         "and print their JSON rows to stdout (needs 8 host "
                         "devices; run() spawns this with "
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--sim-scale", action="store_true",
                    help="bounded planet-scale smoke: a 50k-request "
                         "diurnal fast trace must finish under a fixed "
                         "wall budget (the sim-scale CI job)")
    ap.add_argument("--json", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_engine.json"), help="output path for the JSON metrics")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.hetero_child:
        print(json.dumps(hetero_validation(
            n_rounds=2 if args.smoke else 4)))
    elif args.sim_scale:
        row = sim_scale_smoke()
        print(f"sim-scale OK: {row['n_requests']} requests in "
              f"{row['wall_s']:.1f}s ({row['requests_per_s']:.0f} req/s, "
              f"budget {row['budget_s']:.0f}s, "
              f"drop_rate={row['drop_rate']:.3f})")
    elif args.check_only:
        check_json(args.json)
    else:
        run(full=args.full, smoke=args.smoke)
        write_json(args.json)
