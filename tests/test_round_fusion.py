"""Device-resident decode rounds: per-round dispatch contract (ONE embed,
ONE fused lm_head+sample tail, one fused gather+step+scatter per
(hop, server)), donation safety of the pooled cache trees, the round
step's one-token writes into its pool, and round-for-round parity of
the fused path against the pre-refactor ``decode_mode="serial"``
reference on decoder / rwkv / hybrid / enc-dec
scenarios — tokens and the virtual clock identical, logits to float-ulp
(the fused tail's round-width GEMM may order per-row reductions
differently than the width-1 reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                        shortest_path_route)
from repro.models import init_params
from repro.models.blocks import stack_block_kinds
from repro.models.model import block_param_range
from repro.serving import GeoServingSystem, SamplingSpec
from repro.serving.kv_cache import (make_pool_decode_step,
                                    make_pool_round_step, new_state_pool_tree)

# fused round tail vs per-session reference lm_head: same values up to the
# GEMM-width reduction order — a few float32 ulps on these scales
LOGIT_TOL = dict(atol=5e-6, rtol=1e-4)

_PARAMS_CACHE = {}


def _params_for(cfg):
    if cfg.name not in _PARAMS_CACHE:
        _PARAMS_CACHE[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)[0]
    return _PARAMS_CACHE[cfg.name]


def _build(arch, decode_mode, n_servers=2, max_new=4, cache_layout="slab",
           page_size=None):
    cfg = get_reduced_config(arch)
    params = _params_for(cfg)
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=100.0,
                  cache_bytes_per_token=1.0)
    servers = [ServerSpec(j, mem_bytes=1000.0, tau=0.01 * (j + 1),
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)
               for j in range(n_servers)]
    rtt = np.full((1, n_servers), 0.02)
    prob = Problem(llm, servers, 1, rtt, rtt * 3,
                   workload=Workload(4, max_new))
    system = GeoServingSystem(cfg, params, prob, algorithm="proposed", R=2,
                              max_new_tokens=max_new, max_sessions=4,
                              decode_mode=decode_mode,
                              cache_layout=cache_layout, page_size=page_size)
    return cfg, system


# one scenario per state family: decoder / recurrent / hybrid / enc-dec —
# shared by the fused-vs-serial and the paged-vs-slab parity matrices
FAMILY_SCENARIOS = [
    ("llama3_2_1b", (4, 6, 5), None),       # decoder (mixed positions)
    ("rwkv6_7b", (4, 6, 4), None),          # recurrent pools
    ("zamba2_7b", (4, 6), None),            # hybrid (emb0 threading)
    ("seamless_m4t_large_v2", (4, 6, 5), (5, 8, 5)),  # enc-dec (cross-KV)
]


def _jobs_for(cfg, lengths, enc_lens=None, seed=0):
    rng = np.random.RandomState(seed)
    jobs = []
    for i, n in enumerate(lengths):
        frames = None
        if cfg.is_enc_dec:
            frames = rng.randn(enc_lens[i], cfg.frame_dim).astype(np.float32)
        jobs.append((rng.randint(2, cfg.vocab_size, n), frames))
    return jobs


def _admit(system, jobs, n_new, sampling=None):
    sids = []
    for prompt, frames in jobs:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(prompt, 0, route, n_new,
                                          frames=frames, sampling=sampling))
    assert system.try_admit_sessions(sids) == sids
    system.drain_prefill()
    return sids


def _serve(system, jobs, n_new, sampling=None):
    """Admit as one batch, decode to completion round for round.  Returns
    (token lists, per-round logits histories, virtual times)."""
    sids = _admit(system, jobs, n_new, sampling=sampling)
    hist = {sid: [np.asarray(system.sessions[sid].last_logits)]
            for sid in sids}
    while True:
        todo = [s for s in sids if system.sessions[s].n_generated < n_new]
        if not todo:
            break
        system.decode_round(todo)
        for sid in todo:
            hist[sid].append(np.asarray(system.sessions[sid].last_logits))
    toks = [list(system.sessions[s].tokens) for s in sids]
    vts = [float(system.sessions[s].virtual_time) for s in sids]
    for sid in sids:
        system.retire_session(sid)
    return toks, [hist[s] for s in sids], vts


# ---------------------------------------------------------------------------
# Fused vs pre-refactor reference: round-for-round equality
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,lengths,enc_lens", FAMILY_SCENARIOS)
def test_fused_matches_serial_reference(arch, lengths, enc_lens):
    """Token streams and virtual-clock accounting must be IDENTICAL between
    the device-resident rounds and the pre-refactor per-session reference,
    round for round; logits agree to float-ulp."""
    results = {}
    for mode in ("fused", "serial"):
        cfg, system = _build(arch, mode)
        jobs = _jobs_for(cfg, lengths, enc_lens=enc_lens)
        results[mode] = _serve(system, jobs, n_new=4)
    toks_f, hist_f, vt_f = results["fused"]
    toks_s, hist_s, vt_s = results["serial"]
    assert toks_f == toks_s, f"{arch}: fused tokens diverge from reference"
    assert vt_f == vt_s, f"{arch}: virtual clock diverges"
    for hf, hs in zip(hist_f, hist_s):
        assert len(hf) == len(hs) == 4
        for a, b in zip(hf, hs):
            np.testing.assert_allclose(a, b, **LOGIT_TOL)


# ---------------------------------------------------------------------------
# Paged vs slab layout: the exact-reference-twin contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,lengths,enc_lens", FAMILY_SCENARIOS)
@pytest.mark.parametrize("mode", ["fused", "serial"])
def test_paged_matches_slab(arch, lengths, enc_lens, mode):
    """cache_layout="paged" must be BIT-exact against the slab reference —
    tokens, logits, and the virtual clock — for every state family, both
    decode modes, grouped and solo.  The paged step only re-indexes the
    self-KV time axis through the page table around the UNCHANGED step
    body, so any divergence is an aliasing/indexing bug, not float noise."""
    results = {}
    for layout in ("slab", "paged"):
        cfg, system = _build(arch, mode, cache_layout=layout, page_size=2)
        jobs = _jobs_for(cfg, lengths, enc_lens=enc_lens)
        grouped = _serve(system, jobs, n_new=4)
        solo = [_serve(system, [job], n_new=4) for job in jobs]
        results[layout] = (grouped, solo)
    (toks_s, hist_s, vt_s), solo_s = results["slab"]
    (toks_p, hist_p, vt_p), solo_p = results["paged"]
    assert toks_p == toks_s, f"{arch}/{mode}: paged tokens diverge"
    assert vt_p == vt_s, f"{arch}/{mode}: paged virtual clock diverges"
    for hp, hs in zip(hist_p, hist_s):
        for a, b in zip(hp, hs):
            np.testing.assert_array_equal(a, b)  # bit-for-bit
    for (tp, _, vp), (ts, _, vs) in zip(solo_p, solo_s):
        assert tp == ts and vp == vs, f"{arch}/{mode}: solo diverges"


def test_fused_matches_serial_stochastic_sampling():
    """The fused tail derives PRNG keys on device from raw (seed, index)
    rows — the streams must equal the host-side ``key_for`` reference,
    across the full uint32 seed range (seeds >= 2**31 ride the round's
    uint32 buffer; wider seeds are rejected at spec construction)."""
    with pytest.raises(ValueError, match="seed"):
        SamplingSpec(kind="temperature", seed=2 ** 32)
    spec = SamplingSpec(kind="top_k", temperature=0.7, top_k=12,
                        seed=2 ** 31 + 13)
    results = {}
    for mode in ("fused", "serial"):
        cfg, system = _build("llama3_2_1b", mode, max_new=6)
        results[mode] = _serve(system, _jobs_for(cfg, (4, 6)), n_new=6,
                               sampling=spec)
    assert results["fused"][0] == results["serial"][0]


def test_fused_failover_matches_reference():
    """Failover mid-generation on the fused path: lazy hop records must
    replay to the exact no-failure streams."""
    cfg, ref = _build("llama3_2_1b", "serial", n_servers=4, max_new=6)
    jobs = _jobs_for(cfg, (4, 5))
    toks_ref, _, _ = _serve(ref, jobs, n_new=6)

    cfg, system = _build("llama3_2_1b", "fused", n_servers=4, max_new=6)
    sids = _admit(system, jobs, n_new=6)
    system.decode_round(sids)
    victim = system.sessions[sids[0]].route.servers[0]
    system.kill_server(victim)
    while any(system.sessions[s].n_generated < 6 for s in sids):
        system.decode_round(
            [s for s in sids if system.sessions[s].n_generated < 6])
    for sid, ref_toks in zip(sids, toks_ref):
        assert victim not in system.sessions[sid].route.servers
        assert list(system.sessions[sid].tokens) == ref_toks


# ---------------------------------------------------------------------------
# Per-round dispatch contract
# ---------------------------------------------------------------------------


def test_one_embed_one_tail_dispatch_per_round():
    """Exactly ONE embed dispatch and ONE lm_head+sample dispatch per
    decode round, however many sessions share it — counted both by the
    engine's own round_stats and by wrapping the jitted callables."""
    cfg, system = _build("llama3_2_1b", "fused", max_new=5)
    sids = _admit(system, _jobs_for(cfg, (4, 6, 5)), n_new=5)

    calls = {"embed": 0, "tail": 0}
    orig_embed, orig_tail = system._embed, system._round_tail

    def counting_embed(*a, **k):
        calls["embed"] += 1
        return orig_embed(*a, **k)

    def counting_tail(*a, **k):
        calls["tail"] += 1
        return orig_tail(*a, **k)

    system._embed = counting_embed
    system._round_tail = counting_tail
    base = dict(system.round_stats)
    n_rounds = 4
    for _ in range(n_rounds):
        out = system.decode_round(sids)
        assert len(out) == len(sids)
    assert calls == {"embed": n_rounds, "tail": n_rounds}
    assert system.round_stats["rounds"] - base["rounds"] == n_rounds
    assert (system.round_stats["embed_dispatches"]
            - base["embed_dispatches"]) == n_rounds
    assert (system.round_stats["tail_dispatches"]
            - base["tail_dispatches"]) == n_rounds
    # one fused gather+step+scatter per (hop, server) per round
    hops = len(system.sessions[sids[0]].route.servers)
    assert (system.round_stats["hop_dispatches"]
            - base["hop_dispatches"]) == n_rounds * hops


def test_solo_and_grouped_share_one_round_program():
    """The fixed round width makes solo == grouped structural on the fused
    path: per-session tokens AND logits are bit-for-bit identical."""
    jobs_all = None
    results = {}
    for tag, solo in (("grouped", False), ("solo", True)):
        cfg, system = _build("llama3_2_1b", "fused", max_new=5)
        jobs_all = _jobs_for(cfg, (4, 6, 5))
        if solo:
            toks, hist = [], []
            for job in jobs_all:
                t, h, _ = _serve(system, [job], n_new=5)
                toks += t
                hist += h
        else:
            toks, hist, _ = _serve(system, jobs_all, n_new=5)
        results[tag] = (toks, hist)
    assert results["solo"][0] == results["grouped"][0]
    for hs, hg in zip(results["solo"][1], results["grouped"][1]):
        for a, b in zip(hs, hg):
            np.testing.assert_array_equal(a, b)  # bit-for-bit


# ---------------------------------------------------------------------------
# Donation safety
# ---------------------------------------------------------------------------


def _pool_leaves(system):
    return {j: jax.tree.leaves(srv.pool.tree)
            for j, srv in system.servers.items()}


def test_donated_pool_never_reread():
    """The pooled steps donate their cache trees: after a round, every
    pre-round pool leaf is DEAD (the step consumed its buffer in place).
    The engine must keep decoding correctly afterwards — i.e. it rebound
    every pool reference and never touches the poisoned tree."""
    cfg, system = _build("llama3_2_1b", "fused", max_new=6)
    sids = _admit(system, _jobs_for(cfg, (4, 6)), n_new=6)
    before = _pool_leaves(system)
    system.decode_round(sids)
    donated = [leaf for leaves in before.values() for leaf in leaves
               if leaf.is_deleted()]
    assert donated, "decode round must donate the pool trees"
    # the old tree is poison: any read must raise, not return stale data
    dead = donated[0]
    with pytest.raises(RuntimeError, match="deleted"):
        _ = dead + 0
    # and the engine keeps producing the reference stream on the NEW pools
    cfg, ref = _build("llama3_2_1b", "serial", max_new=6)
    toks_ref, _, _ = _serve(ref, _jobs_for(cfg, (4, 6)), n_new=6)
    while any(system.sessions[s].n_generated < 6 for s in sids):
        system.decode_round(sids)
    assert [list(system.sessions[s].tokens) for s in sids] == toks_ref


def test_donated_prefill_pool_never_reread():
    """The batched prefill step donates too: admitting a bucket group kills
    the pre-prefill pool leaves."""
    cfg, system = _build("llama3_2_1b", "fused", max_new=4)
    before = _pool_leaves(system)
    _admit(system, _jobs_for(cfg, (4, 6)), n_new=4)
    assert any(leaf.is_deleted() for leaves in before.values()
               for leaf in leaves), "pooled prefill must donate the pool"


def test_stale_tree_reuse_raises():
    """Holding a pool tree across a donated step and calling again with it
    is a contract violation — jax must refuse loudly (this is what makes
    'a donated pool is never re-read' testable rather than silent)."""
    cfg, system = _build("llama3_2_1b", "fused", max_new=4)
    sids = _admit(system, _jobs_for(cfg, (4,)), n_new=4)
    srv = next(iter(system.servers.values()))
    stale = srv.pool.tree
    system.decode_round(sids)  # donates `stale`, rebinds pool.tree
    N, d = srv.pool.n_rows, cfg.d_model
    # RuntimeError when jax trips on the dead array while tracing;
    # ValueError (invalid buffer) when the program was already compiled
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        srv._step(srv.run_params, srv.shared, stale,
                  jnp.zeros((N, 1, d), jnp.float32),
                  jnp.zeros((N,), jnp.int32), srv._dummy, srv._zero_encl,
                  jnp.zeros((srv.m, N), bool), srv.layer_ids)


def test_retirement_and_readmission_after_donation():
    """Slot bookkeeping survives donated pools: retire a cohort, admit a
    fresh one, streams match a fresh engine."""
    cfg, system = _build("llama3_2_1b", "fused", max_new=4)
    jobs1 = _jobs_for(cfg, (4, 6), seed=0)
    jobs2 = _jobs_for(cfg, (5, 4), seed=1)
    _serve(system, jobs1, n_new=4)
    got, _, _ = _serve(system, jobs2, n_new=4)
    cfg, fresh = _build("llama3_2_1b", "fused", max_new=4)
    want, _, _ = _serve(fresh, jobs2, n_new=4)
    assert got == want
    for used, cap in system.slot_usage().values():
        assert used == 0


# ---------------------------------------------------------------------------
# The round step writes one token per active row per layer, nothing else
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3_2_1b", "deepseek_v2_236b"])
def test_round_step_writes_only_the_new_token(arch):
    """One hop over a pool of random contents, with a row outside the hop
    and rows active in only one of the server's layers: every pool entry
    other than (active layer, active row, pos[row]) is bit-identical to
    before, and the written entries are what the host-staged decode step
    writes from the same rows (GQA K/V and MLA latent/krope leaves)."""
    cfg = get_reduced_config(arch)
    kinds = stack_block_kinds(cfg)
    L, N, T, W = len(kinds), 4, 16, 3
    assert L == 2 and set(kinds) == {"decoder"}
    run_params = (block_param_range(_params_for(cfg), cfg, "decoder", 0, L),)
    rng = np.random.RandomState(0)
    template = new_state_pool_tree(cfg, "decoder", L, N, T)
    before = {k: rng.randn(*x.shape).astype(x.dtype)
              for k, x in template.items()}
    h_round = jnp.asarray(rng.randn(W, 1, cfg.d_model), jnp.float32)
    pos_round = jnp.asarray([5, 0, 15], jnp.int32)
    slot_of_row = jnp.asarray([2, -1, 0, 1], jnp.int32)
    row_of_slot = jnp.asarray([2, 3, 0], jnp.int32)
    active = np.array([[True, False, True, False],   # layer 0
                       [True, False, False, True]])  # layer 1
    lids = jnp.arange(L, dtype=jnp.int32)
    dummy = jnp.zeros((1, 1, 1), jnp.float32)
    encl_round = jnp.zeros((W,), jnp.int32)

    hop = make_pool_round_step(cfg, kinds)
    out_round, (after,) = hop(
        run_params, None, ({k: jnp.asarray(v) for k, v in before.items()},),
        h_round, pos_round, dummy, encl_round, slot_of_row, row_of_slot,
        jnp.asarray(active), lids)
    src = np.clip(np.asarray(slot_of_row), 0, W - 1)
    pos = np.asarray(pos_round)[src]
    step = make_pool_decode_step(cfg, kinds)
    h_staged, (staged,) = step(
        run_params, None, ({k: jnp.asarray(v) for k, v in before.items()},),
        h_round[src], jnp.asarray(pos), dummy, encl_round[src],
        jnp.asarray(active), lids)

    written = np.zeros((L, N, T), bool)
    for layer in range(L):
        for row in range(N):
            written[layer, row, pos[row]] = active[layer, row]
    assert written.sum() == 4
    for key, old in before.items():
        new, ref = np.asarray(after[key]), np.asarray(staged[key])
        np.testing.assert_array_equal(new[~written], old[~written])
        np.testing.assert_array_equal(new[written], ref[written])
        assert not np.any(np.all(new[written] == old[written], axis=-1)), \
            f"{key}: an active row's token was not written"
    for slot, row in enumerate(np.asarray(row_of_slot)):
        np.testing.assert_array_equal(np.asarray(out_round[slot]),
                                      np.asarray(h_staged[row]))
