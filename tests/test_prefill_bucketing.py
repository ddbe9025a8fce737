"""Batched prefill with prompt-length bucketing: parity of the
bucketed/chunked pooled path against the legacy serial prefill (logits to
float32 rounding across programs, bit-exact solo vs grouped), chunk
interleaving with decode rounds, and group deferral under the slot budget."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                        shortest_path_route)
from repro.models import NULL_SH, decode_step, init_params, prefill
from repro.serving import (ContinuousBatchingScheduler, GeoServingSystem,
                           bucket_for, default_prefill_buckets)
from repro.sim.workload import bursty_requests, prompts_for_lengths


# The serial exact-length prefill and the padded-bucket pooled prefill are
# different XLA programs: the compiler tiles and orders their float32
# reductions differently (an S-row vs a bucket-row matmul), so their logits
# agree to float32 rounding (up to ~7e-6 absolute on O(1) logits on the CPU
# backend), not bit for bit.  A masking or cache-offset bug moves logits by
# orders of magnitude more.  Solo vs grouped runs of ONE program stay
# bit-exact below.
PROGRAM_TOL = dict(atol=5e-5, rtol=1e-4)


def _build(arch="llama3_2_1b", n_servers=4, R=2, mem=900.0, max_sessions=8,
           l_out=8, max_new=8, prefill_mode="batched", prefill_buckets=None,
           l_in=4):
    cfg = get_reduced_config(arch)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=50.0,
                  cache_bytes_per_token=1.0)
    servers = [ServerSpec(j, mem_bytes=mem, tau=0.01 * (j + 1),
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)
               for j in range(n_servers)]
    rtt = np.full((1, n_servers), 0.02)
    prob = Problem(llm, servers, 1, rtt, rtt * 3,
                   workload=Workload(l_in, l_out))
    system = GeoServingSystem(cfg, params, prob, algorithm="proposed", R=R,
                              max_new_tokens=max_new,
                              max_sessions=max_sessions,
                              prefill_mode=prefill_mode,
                              prefill_buckets=prefill_buckets)
    return cfg, params, prob, system


def _run_group(system, prompts, n_new, coalesce: bool):
    """Create all sessions, admit them (in one batch when ``coalesce``),
    decode to completion.  Returns per-session (tokens, [logits/token])."""
    sids = []
    for toks in prompts:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(toks, 0, route, n_new))
    if coalesce:
        admitted = system.try_admit_sessions(sids)
        assert admitted == sids, "every session must fit"
        system.drain_prefill()
    else:
        for sid in sids:
            assert system.try_admit_session(sid)
    hist = {sid: [np.asarray(system.sessions[sid].last_logits)]
            for sid in sids}
    while True:
        todo = [s for s in sids if system.sessions[s].n_generated < n_new]
        if not todo:
            break
        system.decode_round(todo)
        for sid in todo:
            hist[sid].append(np.asarray(system.sessions[sid].last_logits))
    out = [list(system.sessions[sid].tokens) for sid in sids]
    for sid in sids:
        system.retire_session(sid)
    return out, [hist[s] for s in sids]


def _monolithic_ref(cfg, params, prompt, n_new):
    logits, caches = prefill(params, cfg, NULL_SH,
                             {"tokens": jnp.asarray(prompt)[None]},
                             cache_len=len(prompt) + n_new + 4)
    seq = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        lg, caches = decode_step(params, cfg, NULL_SH, caches,
                                 jnp.asarray([seq[-1]]), pos)
        seq.append(int(jnp.argmax(lg[0])))
        pos += 1
    return seq


def test_default_buckets_and_lookup():
    assert default_prefill_buckets(44) == (8, 16, 32, 44)
    assert default_prefill_buckets(8) == (8,)
    assert bucket_for((8, 16), 3) == 8
    assert bucket_for((8, 16), 8) == 8
    assert bucket_for((8, 16), 9) == 16
    assert bucket_for((8, 16), 17) is None  # overflow -> chunked


def test_single_session_bucket_bitexact():
    """A group of ONE padded session (prompt 5 -> bucket 8) must match the
    legacy serial (exact-length) prefill: the same tokens, and logits within
    ``PROGRAM_TOL`` (two different programs)."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, 64, 5)]
    _, _, _, sys_serial = _build(prefill_mode="serial", l_in=5)
    toks_s, logits_s = _run_group(sys_serial, prompts, 6, coalesce=False)
    _, _, _, sys_batched = _build(prefill_mode="batched", l_in=5)
    toks_b, logits_b = _run_group(sys_batched, prompts, 6, coalesce=True)
    assert toks_s == toks_b
    for a, b in zip(logits_s[0], logits_b[0]):
        np.testing.assert_allclose(a, b, **PROGRAM_TOL)


@pytest.mark.parametrize("arch", ["llama3_2_1b", "rwkv6_7b"])
def test_mixed_length_group_parity(arch):
    """Mixed-length co-admitted sessions (two buckets for decoder; exact-
    length groups for rwkv) must reproduce the serial path: the same tokens,
    and logits within ``PROGRAM_TOL``."""
    rng = np.random.RandomState(1)
    lengths = [3, 5, 5, 9, 12]
    prompts = [rng.randint(2, 64, n) for n in lengths]
    n_new = 5
    cfg, params, _, sys_serial = _build(arch, prefill_mode="serial", l_in=6)
    toks_s, logits_s = _run_group(sys_serial, prompts, n_new, coalesce=False)
    _, _, _, sys_batched = _build(arch, prefill_mode="batched", l_in=6)
    toks_b, logits_b = _run_group(sys_batched, prompts, n_new, coalesce=True)
    assert toks_s == toks_b
    for ls, lb in zip(logits_s, logits_b):
        assert len(ls) == len(lb) == n_new
        for a, b in zip(ls, lb):
            np.testing.assert_allclose(a, b, **PROGRAM_TOL)
    # and the serial reference itself equals the monolithic stack
    for p, got in zip(prompts, toks_s):
        assert got[len(p):] == _monolithic_ref(cfg, params, p, n_new)


def test_chunked_long_prompt_parity():
    """A prompt longer than the largest bucket is prefilled in chunks that
    attend over the already-cached prefix.  The chunked path must be
    bit-for-bit identical whether the session is admitted alone or in a
    batch (the fixed-shape pooled program makes this structural), and must
    generate the exact serial/monolithic token stream."""
    rng = np.random.RandomState(2)
    prompts = [rng.randint(2, 64, 19)]  # chunks [0:8) [8:16) [16:19)->pad 8
    n_new = 6
    cfg, params, _, sys_seq = _build(prefill_mode="batched",
                                     prefill_buckets=(4, 8), l_in=19)
    assert bucket_for(sys_seq.prefill_buckets, 19) is None
    toks_q, logits_q = _run_group(sys_seq, prompts, n_new, coalesce=False)
    _, _, _, sys_batched = _build(prefill_mode="batched",
                                  prefill_buckets=(4, 8), l_in=19)
    toks_b, logits_b = _run_group(sys_batched, prompts, n_new, coalesce=True)
    assert toks_q == toks_b
    for a, b in zip(logits_q[0], logits_b[0]):
        np.testing.assert_array_equal(a, b)  # bit-for-bit
    # token stream equals the serial exact-length path and the monolithic
    # stack (padding jitters logits at float-eps scale, never the argmax)
    _, _, _, sys_serial = _build(prefill_mode="serial", l_in=19)
    toks_s, _ = _run_group(sys_serial, prompts, n_new, coalesce=False)
    assert toks_b == toks_s
    assert toks_b[0][19:] == _monolithic_ref(cfg, params, prompts[0], n_new)


def test_chunked_mixed_with_short_group():
    """Chunked long prompts co-admitted WITH short bucketed prompts: every
    session bit-exact vs its own solo admission, and token-exact vs the
    serial engine."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(2, 64, 19), rng.randint(2, 64, 4),
               rng.randint(2, 64, 17)]
    n_new = 5
    _, _, _, sys_seq = _build(prefill_mode="batched", prefill_buckets=(4, 8),
                              l_in=8)
    toks_q, logits_q = _run_group(sys_seq, prompts, n_new, coalesce=False)
    _, _, _, sys_batched = _build(prefill_mode="batched",
                                  prefill_buckets=(4, 8), l_in=8)
    toks_b, logits_b = _run_group(sys_batched, prompts, n_new, coalesce=True)
    assert toks_q == toks_b
    for ls, lb in zip(logits_q, logits_b):
        for a, b in zip(ls, lb):
            np.testing.assert_array_equal(a, b)  # bit-for-bit
    _, _, _, sys_serial = _build(prefill_mode="serial", l_in=8)
    toks_s, _ = _run_group(sys_serial, prompts, n_new, coalesce=False)
    assert toks_b == toks_s


def test_chunk_rounds_interleave_with_decode():
    """While a long prompt prefills chunk by chunk, a resident active
    session must be able to decode between chunk rounds (no head-of-line
    blocking) — and the late-prefilling session still matches serial."""
    rng = np.random.RandomState(4)
    short, long = rng.randint(2, 64, 4), rng.randint(2, 64, 19)
    n_new = 6
    _, _, _, system = _build(prefill_mode="batched", prefill_buckets=(4, 8),
                             l_in=8)
    route, _ = shortest_path_route(system.problem, system.alive_placement(), 0)
    sid_a = system.create_session(short, 0, route, n_new)
    assert system.try_admit_session(sid_a)
    route, _ = shortest_path_route(system.problem, system.alive_placement(), 0)
    sid_b = system.create_session(long, 0, route, n_new)
    assert system.try_admit_sessions([sid_b]) == [sid_b]
    decoded_during_prefill = 0
    rounds = 0
    while system.has_pending_prefill():
        system.prefill_round()
        rounds += 1
        if system.has_pending_prefill():
            before = system.sessions[sid_a].n_generated
            system.decode_round()
            decoded_during_prefill += (system.sessions[sid_a].n_generated
                                       - before)
    assert rounds == 3  # chunks [0:8) [8:16) [16:19)
    assert decoded_during_prefill >= 2, \
        "resident session must advance between chunk rounds"
    while any(system.sessions[s].n_generated < n_new for s in (sid_a, sid_b)):
        system.decode_round()
    # bit-exact check of the chunk-interleaved session vs the serial engine
    _, _, _, sys_serial = _build(prefill_mode="serial", l_in=8)
    toks_s, _ = _run_group(sys_serial, [short, long], n_new, coalesce=False)
    assert list(system.sessions[sid_a].tokens) == toks_s[0]
    assert list(system.sessions[sid_b].tokens) == toks_s[1]


def test_group_deferral_when_budget_exhausted():
    """A co-admitted batch larger than the slot budget: the fitting prefix
    is admitted as a group, the overflow claims nothing and is deferred by
    the scheduler — no overbooking, everyone eventually served."""
    # one server hosting both blocks, 8 block-slots, k=2 per session ->
    # at most 4 resident sessions
    cfg, params, prob, system = _build(n_servers=1, R=1, mem=180.0,
                                       max_sessions=8, l_out=6, max_new=6)
    # engine level: direct batch admission admits only what fits
    rng = np.random.RandomState(5)
    sids = []
    for _ in range(6):
        route, _ = shortest_path_route(prob, system.alive_placement(), 0)
        sids.append(system.create_session(rng.randint(2, 64, 4), 0, route, 6))
    admitted = system.try_admit_sessions(sids)
    system.drain_prefill()
    assert 0 < len(admitted) < len(sids), (admitted, sids)
    for used, cap in system.slot_usage().values():
        assert used <= cap
    for sid in admitted:
        system.retire_session(sid)
    for sid in set(sids) - set(admitted):
        assert system.sessions[sid].state == "admitted"  # claimed nothing
        system.sessions.pop(sid)

    # scheduler level: a same-timestamp burst under the same tight budget
    _, _, _, system2 = _build(n_servers=1, R=1, mem=180.0, max_sessions=8,
                              l_out=6, max_new=6)
    sched = ContinuousBatchingScheduler(system2, R=1)
    for i in range(6):
        sched.submit(i, rng.randint(2, cfg.vocab_size, 4), 0.0, n_new=6)
    served = sched.run()
    assert len(served) == 6 and not any(r.dropped for r in served)
    # WS-RR spreads committed starts, so the overflow either waits (the
    # controller predicted the contention) or defers (it did not)
    assert any(r.wait > 0 for r in served) or \
        any(r.n_deferrals > 0 for r in served)
    for used, cap in system2.slot_usage().values():
        assert used == 0


def test_bucketed_failover_replay_exact():
    """Failover replay must reproduce bucket-group-prefilled caches: kill a
    server after co-admitted (padded) sessions started decoding."""
    cfg, params, prob, system = _build(n_servers=4, R=2, l_in=6)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(2, cfg.vocab_size, 5),
               rng.randint(2, cfg.vocab_size, 7)]
    n_new = 6
    refs = [_monolithic_ref(cfg, params, p, n_new) for p in prompts]
    sids = []
    for p in prompts:
        route, _ = shortest_path_route(prob, system.alive_placement(), 0)
        sids.append(system.create_session(p, 0, route, n_new))
    assert system.try_admit_sessions(sids) == sids
    system.drain_prefill()
    system.decode_round(sids)
    victim = system.sessions[sids[0]].route.servers[0]
    system.kill_server(victim)
    while any(system.sessions[s].n_generated < n_new for s in sids):
        system.decode_round(
            [s for s in sids if system.sessions[s].n_generated < n_new])
    for sid, p, ref in zip(sids, prompts, refs):
        sess = system.sessions[sid]
        assert victim not in sess.route.servers
        assert sess.tokens[len(p):] == ref
        system.retire_session(sid)


def test_chunked_failover_replay_exact():
    """Failover of a session whose prompt was CHUNK-prefilled: the replay
    must follow the session's chunk plan through the same pooled programs
    (legacy exact-length replay would rebuild subtly different caches)."""
    cfg, params, prob, system = _build(n_servers=4, R=2, l_in=8,
                                       prefill_buckets=(4, 8))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(2, cfg.vocab_size, 19),
               rng.randint(2, cfg.vocab_size, 17)]
    n_new = 6
    refs = [_monolithic_ref(cfg, params, p, n_new) for p in prompts]
    sids = []
    for p in prompts:
        route, _ = shortest_path_route(prob, system.alive_placement(), 0)
        sids.append(system.create_session(p, 0, route, n_new))
    assert system.try_admit_sessions(sids) == sids
    system.drain_prefill()
    system.decode_round(sids)
    victim = system.sessions[sids[0]].route.servers[0]
    system.kill_server(victim)
    while any(system.sessions[s].n_generated < n_new for s in sids):
        system.decode_round(
            [s for s in sids if system.sessions[s].n_generated < n_new])
    for sid, p, ref in zip(sids, prompts, refs):
        sess = system.sessions[sid]
        assert victim not in sess.route.servers
        assert sess.tokens[len(p):] == ref
        system.retire_session(sid)


def test_bursty_trace_mixed_lengths_end_to_end():
    """Bursty arrivals with mixed prompt lengths through the full
    scheduler: same tokens as the serial engine, zero drops."""
    lengths = (3, 5, 9, 12)
    reqs = bursty_requests(n_bursts=2, burst_size=4, spacing=5.0)
    results = {}
    for mode in ("serial", "batched"):
        cfg, params, prob, system = _build(mem=2000.0, max_sessions=10,
                                           l_out=6, max_new=6, l_in=8,
                                           prefill_mode=mode)
        sched = ContinuousBatchingScheduler(system, R=8)
        prompts = prompts_for_lengths(reqs, lengths, cfg.vocab_size, seed=9)
        for req, toks in zip(reqs, prompts):
            sched.submit(req.rid, toks, req.arrival, n_new=6)
        served = sched.run()
        assert len(served) == 8 and not any(r.dropped for r in served)
        results[mode] = ([list(r.tokens) for r in served],
                         [(r.start, r.first_token, r.per_token) for r in
                          served])
    assert results["serial"][0] == results["batched"][0], "same tokens"
    for a, b in zip(results["serial"][1], results["batched"][1]):
        np.testing.assert_allclose(a, b, rtol=1e-12), \
            "virtual clock must not depend on prefill batching"


def test_scheduler_coalesces_same_time_starts():
    """A same-timestamp burst must reach the engine as ONE admission batch
    (the bucket group), not as one-session batches: arrivals process before
    same-time starts, so every zero-wait start is in the heap when the
    first pops."""
    cfg, params, prob, system = _build(mem=2000.0, max_sessions=8, l_out=6,
                                       max_new=6)
    batches = []
    orig = system.try_admit_sessions

    def spy(sids, now=0.0):
        batches.append(list(sids))
        return orig(sids, now=now)

    system.try_admit_sessions = spy
    sched = ContinuousBatchingScheduler(system, R=8)
    rng = np.random.RandomState(11)
    for rid in range(4):
        sched.submit(rid, rng.randint(2, cfg.vocab_size, 4), 0.0, n_new=6)
    served = sched.run()
    assert len(served) == 4 and not any(r.dropped for r in served)
    assert any(len(b) == 4 for b in batches), \
        f"burst must admit as one batch, got {batches}"


@pytest.mark.parametrize("R", [4, 8])
def test_engine_vs_simulator_bursty_tolerance(R):
    """Same bursty trace through the simulator and the real engine: mean
    per-token and first-token times agree within 10%."""
    from benchmarks.engine_validation import cross_validate

    eng, simm, err = cross_validate(R, n_requests=8, trace="bursty")
    assert err["per_token_all"] < 0.10, (eng, simm)
    assert err["first_token"] < 0.10, (eng, simm)


def test_crash_during_prefill_group():
    """Silent crash of one group's route server between chunk rounds:
    that group's in-flight members fail with a machine-readable reason
    and billed timeout detection, while the OTHER group (distinct route)
    prefill-completes and decodes bit-exact vs a fault-free run."""
    from repro.core.perf_model import Route

    def _setup():
        cfg, params, prob, system = _build(prefill_buckets=(4,), l_in=12,
                                           max_new=5, l_out=5)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(2, cfg.vocab_size, 12) for _ in range(4)]
        sids = []
        for i, toks in enumerate(prompts):
            j = 1 if i < 2 else 2  # group A -> server 1, group B -> server 2
            a, m = int(system.placement.a[j]), int(system.placement.m[j])
            assert a == 0 and m == prob.L, "toy placement must replicate"
            sids.append(system.create_session(
                toks, 0, Route(servers=(j,), blocks=(m,)), 5))
        assert system.try_admit_sessions(sids) == sids
        assert len(system._prefill_groups) == 2  # distinct routes
        return system, sids

    # fault-free twin: group B's oracle streams
    ref, ref_sids = _setup()
    ref.drain_prefill()
    while any(ref.sessions[s].n_generated < 5 for s in ref_sids):
        ref.decode_round()
    ref_b = [list(ref.sessions[s].tokens) for s in ref_sids[2:]]

    system, sids = _setup()
    system.prefill_round()  # one chunk round: both groups mid-prompt
    system.inject_crash(1)  # silent: next dispatch discovers it
    system.drain_prefill()
    while any(system.sessions[s].state == "active"
              and system.sessions[s].n_generated < 5 for s in sids):
        system.decode_round()

    for sid in sids[:2]:  # group A: failed mid-prefill, detection billed
        sess = system.sessions[sid]
        assert sess.state == "failed"
        assert sess.fail_reason == "server_lost_mid_prefill"
        assert sess.n_detections >= 1 and sess.detect_time > 0.0
    # group B: untouched, bit-exact streams
    assert [list(system.sessions[s].tokens) for s in sids[2:]] == ref_b
    assert all(system.sessions[s].recovery_time == 0.0 for s in sids[2:])
    assert not system.servers[1].alive and 1 in system.suspected_servers()
    # failed members released their claims: no leaked slots on server 1
    for sid in sids:
        system.retire_session(sid)
    assert all(u == 0 for u, _ in system.slot_usage().values())
