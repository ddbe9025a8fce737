"""Family-polymorphic state pools: hybrid (zamba2) and enc-dec (seamless)
stacks served end-to-end through the geo engine — engine-vs-monolithic
parity, solo-vs-grouped bit-exactness through the pooled programs, exact-
length (no-padding) prefill-group semantics for recurrent-state stacks,
mid-stream failover replay on hybrid and enc-dec routes, per-family τ
weights, and per-session sampling policies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (LLMSpec, Problem, Route, ServerSpec, Workload,
                        route_per_token_time, route_prefill_time,
                        shortest_path_route)
from repro.models import (NULL_SH, decode_step, init_params, prefill,
                          stack_block_kinds)
from repro.serving import (ContinuousBatchingScheduler, GeoServingSystem,
                           SamplingSpec, bucket_for, new_block_cache,
                           state_spec_for, state_specs)

_PARAMS_CACHE = {}


def _params_for(cfg):
    if cfg.name not in _PARAMS_CACHE:
        _PARAMS_CACHE[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)[0]
    return _PARAMS_CACHE[cfg.name]


def _build(arch, n_servers=3, R=2, mem=1000.0, max_sessions=8, l_out=8,
           max_new=8):
    cfg = get_reduced_config(arch)
    params = _params_for(cfg)
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=100.0,
                  cache_bytes_per_token=1.0)
    servers = [ServerSpec(j, mem_bytes=mem, tau=0.01 * (j + 1),
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)
               for j in range(n_servers)]
    rtt = np.full((1, n_servers), 0.02)
    prob = Problem(llm, servers, 1, rtt, rtt * 3,
                   workload=Workload(4, l_out))
    system = GeoServingSystem(cfg, params, prob, algorithm="proposed", R=R,
                              max_new_tokens=max_new,
                              max_sessions=max_sessions)
    return cfg, params, prob, system


def _frames_for(cfg, rng, n):
    return rng.randn(n, cfg.frame_dim).astype(np.float32)


def _monolithic_ref(cfg, params, prompt, n_new, frames=None):
    batch = {"tokens": jnp.asarray(prompt)[None]}
    if frames is not None:
        batch["frames"] = jnp.asarray(frames)[None]
    logits, caches = prefill(params, cfg, NULL_SH, batch,
                             cache_len=len(prompt) + n_new + 4)
    seq = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        lg, caches = decode_step(params, cfg, NULL_SH, caches,
                                 jnp.asarray([seq[-1]]), pos)
        seq.append(int(jnp.argmax(lg[0])))
        pos += 1
    return seq


def _run_engine_sessions(system, jobs, n_new, coalesce):
    """jobs: [(prompt, frames|None), ...].  Admit (batched when coalesce),
    decode to completion.  Returns (token lists, per-session logit lists)."""
    sids = []
    for prompt, frames in jobs:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(prompt, 0, route, n_new,
                                          frames=frames))
    hist = {}
    if coalesce:
        assert system.try_admit_sessions(sids) == sids
        system.drain_prefill()
        for sid in sids:
            hist[sid] = [np.asarray(system.sessions[sid].last_logits)]
        while True:
            todo = [s for s in sids
                    if system.sessions[s].n_generated < n_new]
            if not todo:
                break
            system.decode_round(todo)
            for sid in todo:
                hist[sid].append(
                    np.asarray(system.sessions[sid].last_logits))
        out = [list(system.sessions[sid].tokens) for sid in sids]
        for sid in sids:
            system.retire_session(sid)
    else:
        out = []
        for sid in sids:
            assert system.try_admit_session(sid)
            hist[sid] = [np.asarray(system.sessions[sid].last_logits)]
            while system.sessions[sid].n_generated < n_new:
                system.decode_round([sid])
                hist[sid].append(
                    np.asarray(system.sessions[sid].last_logits))
            out.append(list(system.sessions[sid].tokens))
            system.retire_session(sid)
    return out, [hist[s] for s in sids]


# ---------------------------------------------------------------------------
# StateSpec dispatch
# ---------------------------------------------------------------------------


def test_state_spec_dispatch_and_kinds():
    z = get_reduced_config("zamba2_7b")  # 7 layers, period 3
    assert stack_block_kinds(z) == ("mamba", "mamba", "mamba_shared",
                                    "mamba", "mamba", "mamba_shared",
                                    "mamba")
    s = get_reduced_config("seamless_m4t_large_v2")  # 2 enc + 2 dec
    assert stack_block_kinds(s) == ("enc", "enc", "dec", "dec")
    zspecs = state_specs(z)
    assert all(sp.recurrent for sp in zspecs)
    assert zspecs[2].needs_emb0 and not zspecs[0].needs_emb0
    sspecs = state_specs(s)
    assert not sspecs[0].decode_active and sspecs[2].cross


def test_unknown_kind_raises_value_error():
    cfg = get_reduced_config("llama3_2_1b")
    with pytest.raises(ValueError, match="decoder"):
        new_block_cache(cfg, "transfusion", 1, 8)
    with pytest.raises(ValueError, match="rwkv"):
        state_spec_for("diffusion")
    with pytest.raises(ValueError, match="block kinds"):
        stack_block_kinds(cfg.replace(family="holographic"))


def test_bucket_for_family_rules():
    z = state_specs(get_reduced_config("zamba2_7b"))
    r = state_specs(get_reduced_config("rwkv6_7b"))
    d = state_specs(get_reduced_config("llama3_2_1b"))
    s = state_specs(get_reduced_config("seamless_m4t_large_v2"))
    # recurrent state (mamba AND rwkv): exact length, never padded
    assert bucket_for((8, 16), 5, z) == 5
    assert bucket_for((8, 16), 5, r) == 5
    # attention-only stacks bucket (enc-dec decoders included)
    assert bucket_for((8, 16), 5, d) == 8
    assert bucket_for((8, 16), 5, s) == 8
    assert bucket_for((8, 16), 17, d) is None  # overflow -> chunked


def test_per_family_tau_weights():
    llm = LLMSpec("w", 4, 10.0, 1.0, block_tau=(0.5, 0.5, 2.0, 1.0))
    assert llm.tau_weight(0, 4) == 4.0
    assert llm.tau_weight(0, 2) == 1.0
    assert llm.tau_weight(2, 4) == 3.0
    np.testing.assert_allclose(llm.tau_cumweights(), [0, 0.5, 1.0, 3.0, 4.0])
    servers = [ServerSpec(0, 100.0, 0.01, tau_prefill_base=0.004),
               ServerSpec(1, 100.0, 0.02, tau_prefill_base=0.004)]
    rtt = np.array([[0.1, 0.1]])
    prob = Problem(llm, servers, 1, rtt, rtt, workload=Workload(4, 8))
    route = Route(servers=(0, 1), blocks=(2, 2))
    # hop 0 carries weight 1.0, hop 1 weight 3.0 — NOT the uniform 2/2
    np.testing.assert_allclose(route_per_token_time(prob, route, 0),
                               0.1 + 1.0 * 0.01 + 0.1 + 3.0 * 0.02)
    np.testing.assert_allclose(route_prefill_time(prob, route, 0),
                               0.1 + 1.0 * 0.004 + 0.1 + 3.0 * 0.004)


# ---------------------------------------------------------------------------
# Engine vs monolithic (token streams; logits to float-eps across programs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["zamba2_7b", "seamless_m4t_large_v2"])
def test_engine_matches_monolithic(arch):
    cfg, params, prob, system = _build(arch)
    rng = np.random.RandomState(0)
    toks = rng.randint(2, cfg.vocab_size, 6)
    frames = _frames_for(cfg, rng, 5) if cfg.is_enc_dec else None
    n_new = 5
    ref = _monolithic_ref(cfg, params, toks, n_new, frames=frames)

    sid, logits = system.submit(toks, frames=frames)
    batch = {"tokens": jnp.asarray(toks)[None]}
    if frames is not None:
        batch["frames"] = jnp.asarray(frames)[None]
    ref_logits, caches = prefill(params, cfg, NULL_SH, batch,
                                 cache_len=len(toks) + n_new + 4)
    # logits agree to float32 rounding (engine and monolithic are different
    # jitted programs; XLA fusion reorders reductions), tokens exactly.  The
    # absolute term covers near-zero logits, where rtol says nothing: up to
    # ~1.4e-5 was seen on the CPU backend for zamba2's shared-attention
    # stack, against the O(1) differences a wrong cache or mask gives.
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(ref_logits[0]), rtol=2e-4,
                               atol=5e-5)
    seq = [int(jnp.argmax(ref_logits[0]))]
    pos = len(toks)
    for _ in range(n_new - 1):
        lg_ref, caches = decode_step(params, cfg, NULL_SH, caches,
                                     jnp.asarray([seq[-1]]), pos)
        lg = system.decode(sid, seq[-1])
        np.testing.assert_allclose(np.asarray(lg[0]),
                                   np.asarray(lg_ref[0]), rtol=2e-4,
                                   atol=5e-5)
        seq.append(int(jnp.argmax(lg_ref[0])))
        pos += 1
    assert seq == ref
    system.finish(sid)


# ---------------------------------------------------------------------------
# Solo vs grouped (bit-exact: the SAME pooled program, different mask bits)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["zamba2_7b", "seamless_m4t_large_v2"])
def test_solo_vs_grouped_bitexact(arch):
    cfg, _, _, sys_solo = _build(arch)
    rng = np.random.RandomState(1)
    # hybrid: mixed lengths -> exact-length groups; enc-dec: equal enc lens
    lengths = [4, 6, 4]
    jobs = [(rng.randint(2, cfg.vocab_size, n),
             _frames_for(cfg, rng, 5) if cfg.is_enc_dec else None)
            for n in lengths]
    n_new = 4
    toks_solo, logits_solo = _run_engine_sessions(sys_solo, jobs, n_new,
                                                  coalesce=False)
    _, _, _, sys_grp = _build(arch)
    toks_grp, logits_grp = _run_engine_sessions(sys_grp, jobs, n_new,
                                                coalesce=True)
    assert toks_solo == toks_grp
    for ls, lg in zip(logits_solo, logits_grp):
        assert len(ls) == len(lg) == n_new
        for a, b in zip(ls, lg):
            np.testing.assert_array_equal(a, b)  # bit-for-bit


def test_mamba_exact_length_prefill_groups():
    """Recurrent-state stacks must never pad: mixed-length hybrid admissions
    form one exact-length group per length, each group's chunk plan is a
    single exact-length shot, and results are bit-identical to solo runs
    (checked above); here we pin the grouping/plan semantics."""
    cfg, _, _, system = _build("zamba2_7b")
    rng = np.random.RandomState(2)
    prompts = [rng.randint(2, cfg.vocab_size, n) for n in (4, 7, 4)]
    sids = []
    for p in prompts:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(p, 0, route, 4))
    assert system.try_admit_sessions(sids) == sids
    groups = {(g.bucket, tuple(s.sid for s in g.members))
              for g in system._prefill_groups}
    assert groups == {(4, (sids[0], sids[2])), (7, (sids[1],))}, \
        "exact-length grouping: equal lengths coalesce, no padding"
    assert system._prefill_plan(7) == [(0, 7, 7)]  # one exact-length shot
    assert system._prefill_plan(4) == [(0, 4, 4)]
    system.drain_prefill()
    for sid in sids:
        assert system.sessions[sid].state == "active"
        system.retire_session(sid)


def test_encdec_mixed_enc_lengths_group_separately():
    """Enc-dec groups are keyed by encoder length too (the pooled encoder
    pass is exact-length); decoder prompts still bucket."""
    cfg, _, _, system = _build("seamless_m4t_large_v2")
    rng = np.random.RandomState(3)
    jobs = [(rng.randint(2, cfg.vocab_size, 5), _frames_for(cfg, rng, 4)),
            (rng.randint(2, cfg.vocab_size, 6), _frames_for(cfg, rng, 9)),
            (rng.randint(2, cfg.vocab_size, 4), _frames_for(cfg, rng, 4))]
    sids = []
    for p, f in jobs:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(p, 0, route, 4, frames=f))
    assert system.try_admit_sessions(sids) == sids
    keys = {(g.bucket, g.enc_len, tuple(s.sid for s in g.members))
            for g in system._prefill_groups}
    assert keys == {(8, 4, (sids[0], sids[2])), (8, 9, (sids[1],))}
    system.drain_prefill()
    toks = {}
    while any(system.sessions[s].n_generated < 4 for s in sids):
        system.decode_round()
    for sid in sids:
        toks[sid] = list(system.sessions[sid].tokens)
        system.retire_session(sid)
    # each matches its own monolithic reference
    params = _params_for(cfg)
    for sid, (p, f) in zip(sids, jobs):
        assert toks[sid][len(p):] == _monolithic_ref(cfg, params, p, 4,
                                                     frames=f)


# ---------------------------------------------------------------------------
# Failover replay on hybrid / enc-dec routes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["zamba2_7b", "seamless_m4t_large_v2"])
def test_failover_mid_stream_exact(arch):
    """Kill a route server while two sessions are co-resident mid-stream:
    both streams must continue bit-identically to the no-failure engine run
    (replay goes through the same pooled programs)."""
    cfg, _, _, ref_sys = _build(arch, n_servers=4)
    rng = np.random.RandomState(4)
    jobs = [(rng.randint(2, cfg.vocab_size, 5),
             _frames_for(cfg, rng, 5) if cfg.is_enc_dec else None)
            for _ in range(2)]
    n_new = 6
    ref_toks, ref_logits = _run_engine_sessions(ref_sys, jobs, n_new,
                                                coalesce=True)

    _, _, _, system = _build(arch, n_servers=4)
    sids = []
    for p, f in jobs:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(p, 0, route, n_new, frames=f))
    assert system.try_admit_sessions(sids) == sids
    system.drain_prefill()
    system.decode_round(sids)
    system.decode_round(sids)
    victim = system.sessions[sids[0]].route.servers[0]
    system.kill_server(victim)
    while any(system.sessions[s].n_generated < n_new for s in sids):
        system.decode_round(
            [s for s in sids if system.sessions[s].n_generated < n_new])
    for sid, ref in zip(sids, ref_toks):
        sess = system.sessions[sid]
        assert victim not in sess.route.servers
        assert list(sess.tokens) == ref, \
            "post-failover stream must equal the no-failure stream"
        system.retire_session(sid)


# ---------------------------------------------------------------------------
# Scheduler end-to-end + hybrid cross-validation
# ---------------------------------------------------------------------------


def test_encdec_chunked_billing_counts_enc_hops_once():
    """A chunked enc-dec prompt pays per-chunk protocol cost only on hops
    it actually traverses: encoder-only hops are traversed (and billed)
    exactly once, decoder hops once per chunk."""
    cfg = get_reduced_config("seamless_m4t_large_v2")
    params = _params_for(cfg)
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=100.0,
                  cache_bytes_per_token=1.0)
    # mem caps every server at 2 hosted blocks -> the first hop of any
    # route covers exactly the 2 encoder blocks (a pure-encoder hop)
    servers = [ServerSpec(j, mem_bytes=250.0, tau=0.01,
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005) for j in range(3)]
    rtt = np.full((1, 3), 0.02)
    prob = Problem(llm, servers, 1, rtt, rtt * 3, workload=Workload(4, 4))
    system = GeoServingSystem(cfg, params, prob, R=2, max_new_tokens=4,
                              prefill_buckets=(4,), max_seq_len=16)
    rng = np.random.RandomState(9)
    toks = rng.randint(2, cfg.vocab_size, 7)  # chunks (0,4,4), (4,3,4)
    sid, _ = system.submit(toks, frames=_frames_for(cfg, rng, 5))
    sess = system.sessions[sid]
    n_enc = cfg.n_enc_layers
    expected = 0.0
    for off, span, _ in [(0, 4, 4), (4, 3, 4)]:
        e = 0
        for j, k in zip(sess.route.servers, sess.route.blocks):
            if max(e, n_enc) < e + k or off == 0:  # dec hop, or first round
                expected += (prob.rtt_prefill[0, j]
                             + k * prob.servers[j].tau_prefill(span))
            e += k
    assert e == cfg.n_layers
    assert sess.route.blocks[0] <= n_enc, "first hop must be encoder-only"
    np.testing.assert_allclose(sess.prefill_time, expected, rtol=1e-12)
    system.finish(sid)


def test_encdec_through_scheduler():
    cfg, _, _, system = _build("seamless_m4t_large_v2", mem=900.0,
                               l_out=5, max_new=5)
    sched = ContinuousBatchingScheduler(system, R=4)
    rng = np.random.RandomState(5)
    for rid in range(4):
        sched.submit(rid, rng.randint(2, cfg.vocab_size, 5), 0.0, n_new=5,
                     frames=_frames_for(cfg, rng, 6))
    served = sched.run()
    assert len(served) == 4 and not any(r.dropped for r in served)
    for used, cap in system.slot_usage().values():
        assert used == 0


@pytest.mark.parametrize("R", [4])
def test_engine_vs_simulator_hybrid_tolerance(R):
    """Same Poisson trace through the simulator (weighted eq. (1)) and the
    hybrid-stack engine: mean per-token and first-token times within 10%."""
    from benchmarks.engine_validation import cross_validate

    eng, simm, err = cross_validate(R, n_requests=6, rate=1.5, seed=1,
                                    arch="zamba2_7b")
    assert err["per_token_all"] < 0.10, (eng, simm)
    assert err["first_token"] < 0.10, (eng, simm)


# ---------------------------------------------------------------------------
# Sampling policies
# ---------------------------------------------------------------------------


def test_sampling_greedy_default_matches_argmax():
    cfg, params, _, system = _build("llama3_2_1b")
    rng = np.random.RandomState(6)
    toks = rng.randint(2, cfg.vocab_size, 5)
    sid, _ = system.submit(toks, sampling=SamplingSpec(kind="greedy"))
    sess = system.sessions[sid]
    while sess.n_generated < 5:
        system.decode_round([sid])
    got = list(sess.tokens[len(toks):])
    system.retire_session(sid)
    assert got == _monolithic_ref(cfg, params, toks, 5)


def test_sampling_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        SamplingSpec(kind="beam")
    with pytest.raises(ValueError, match="temperature"):
        SamplingSpec(kind="temperature", temperature=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingSpec(kind="top_k", top_k=0)


def test_sampling_seeded_deterministic_and_topk_support():
    cfg, params, _, system = _build("llama3_2_1b")
    rng = np.random.RandomState(7)
    toks = rng.randint(2, cfg.vocab_size, 5)
    spec = SamplingSpec(kind="top_k", temperature=0.8, top_k=3, seed=11)

    def run_once(sys_):
        sid, _ = sys_.submit(toks, sampling=spec)
        sess = sys_.sessions[sid]
        logits_hist = [np.asarray(sess.last_logits)]
        while sess.n_generated < 6:
            sys_.decode_round([sid])
            logits_hist.append(np.asarray(sess.last_logits))
        out = list(sess.tokens[len(toks):])
        sys_.retire_session(sid)
        return out, logits_hist

    out1, hist1 = run_once(system)
    _, _, _, system2 = _build("llama3_2_1b")
    out2, _ = run_once(system2)
    assert out1 == out2, "same (seed, token index) must draw the same stream"
    # every sampled token lies within the top-k of the logits it came from
    for tok, lg in zip(out1, hist1[:-1]):
        topk = set(np.argsort(lg)[-spec.top_k:])
        assert tok in topk, (tok, topk)


def test_sampling_solo_vs_grouped_identical():
    """The sampling key is a pure function of (seed, token index), so a
    stochastic session draws the identical stream alone or co-resident."""
    rng = np.random.RandomState(8)
    prompts = [rng.randint(2, 64, 4) for _ in range(3)]
    specs = [SamplingSpec(kind="temperature", temperature=0.7, seed=i)
             for i in range(3)]

    def run(coalesce):
        _, _, _, system = _build("llama3_2_1b")
        sids = []
        for p, sp in zip(prompts, specs):
            route, _ = shortest_path_route(system.problem,
                                           system.alive_placement(), 0)
            sids.append(system.create_session(p, 0, route, 5, sampling=sp))
        if coalesce:
            assert system.try_admit_sessions(sids) == sids
            system.drain_prefill()
            while any(system.sessions[s].n_generated < 5 for s in sids):
                system.decode_round(
                    [s for s in sids
                     if system.sessions[s].n_generated < 5])
            out = [list(system.sessions[s].tokens) for s in sids]
            for s in sids:
                system.retire_session(s)
            return out
        out = []
        for sid in sids:
            assert system.try_admit_session(sid)
            while system.sessions[sid].n_generated < 5:
                system.decode_round([sid])
            out.append(list(system.sessions[sid].tokens))
            system.retire_session(sid)
        return out

    assert run(False) == run(True)
