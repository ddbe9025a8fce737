"""Engine rounds are backend-independent: ``GeoServingSystem`` must produce
IDENTICAL round results — token streams, admission/grouping decisions, and
virtual-clock accounting — with ``backend="xla"`` and ``backend="pallas"``
(interpret mode off-TPU), with per-round logits agreeing to float-eps.

Scenarios cover every kernel<->oracle gap the pooled call sites exercise:
mixed-position pooled rows (co-resident sessions with different prompt
lengths), windowed gemma3, ALiBi bloom, MLA deepseek decode, rwkv and
hybrid (zamba2) recurrent pools, enc-dec (seamless) cross-attention with
mixed encoder lengths, and chunked prefill (q_start).  The CI
``kernel-parity`` job runs this file (interpret mode, off-TPU) so kernel
changes cannot land without oracle parity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                        shortest_path_route)
from repro.models import init_params
from repro.serving import GeoServingSystem

# per-round logits across backends: different compute substrates (online-
# softmax kernels vs dense oracle), so float-eps — tokens must be EXACT
LOGIT_TOL = dict(atol=5e-4, rtol=5e-4)

_PARAMS_CACHE = {}


def _params_for(cfg):
    if cfg.name not in _PARAMS_CACHE:
        _PARAMS_CACHE[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)[0]
    return _PARAMS_CACHE[cfg.name]


def _build(arch, backend, n_servers=2, max_new=4, **kw):
    cfg = get_reduced_config(arch)
    params = _params_for(cfg)
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=100.0,
                  cache_bytes_per_token=1.0)
    servers = [ServerSpec(j, mem_bytes=1000.0, tau=0.01 * (j + 1),
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)
               for j in range(n_servers)]
    rtt = np.full((1, n_servers), 0.02)
    prob = Problem(llm, servers, 1, rtt, rtt * 3, workload=Workload(4, max_new))
    system = GeoServingSystem(cfg, params, prob, algorithm="proposed", R=2,
                              max_new_tokens=max_new, max_sessions=4,
                              backend=backend, **kw)
    return cfg, system


def _serve(system, jobs, n_new):
    """Admit ``jobs`` [(prompt, frames|None), ...] as ONE coalesced batch
    (mixed lengths -> mixed positions in the pooled rows), decode all to
    completion.  Returns (token lists, logits histories, virtual times)."""
    sids = []
    for prompt, frames in jobs:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(prompt, 0, route, n_new,
                                          frames=frames))
    assert system.try_admit_sessions(sids) == sids
    system.drain_prefill()
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


def _jobs_for(cfg, lengths, enc_lens=None, seed=0):
    rng = np.random.RandomState(seed)
    jobs = []
    for i, n in enumerate(lengths):
        frames = None
        if cfg.is_enc_dec:
            frames = rng.randn(enc_lens[i], cfg.frame_dim).astype(np.float32)
        jobs.append((rng.randint(2, cfg.vocab_size, n), frames))
    return jobs


def _assert_backend_parity(arch, lengths=(4, 6, 5), enc_lens=None, n_new=4,
                           **kw):
    results = {}
    for backend in ("xla", "pallas"):
        cfg, system = _build(arch, backend, **kw)
        jobs = _jobs_for(cfg, lengths, enc_lens=enc_lens)
        results[backend] = _serve(system, jobs, n_new)
    toks_x, hist_x, vt_x = results["xla"]
    toks_p, hist_p, vt_p = results["pallas"]
    assert toks_x == toks_p, \
        f"{arch}: token streams differ across backends"
    assert vt_x == vt_p, \
        f"{arch}: virtual-clock accounting differs across backends"
    for hx, hp in zip(hist_x, hist_p):
        assert len(hx) == len(hp) == n_new
        for a, b in zip(hx, hp):
            np.testing.assert_allclose(a, b, **LOGIT_TOL)


# one scenario per kernel<->oracle gap -----------------------------------

def test_backend_parity_decoder_mixed_positions():
    """Plain GQA decoder; co-resident sessions at different prompt lengths
    decode at different per-row positions inside one pooled step."""
    _assert_backend_parity("llama3_2_1b", lengths=(4, 7, 5))


def test_backend_parity_windowed_gemma3():
    """Sliding-window + local:global pattern: the traced per-layer window
    flows into the kernels as a dynamic scalar."""
    _assert_backend_parity("gemma3_4b", lengths=(4, 6))


def test_backend_parity_alibi_bloom():
    """ALiBi slopes in prefill and decode."""
    _assert_backend_parity("bloom_176b", lengths=(4, 6, 5))


def test_backend_parity_mla_deepseek():
    """MLA: unabsorbed per-head prefill + absorbed latent-space decode with
    the faithful 1/sqrt(nope+rope) scale."""
    _assert_backend_parity("deepseek_v2_236b", lengths=(4, 6))


def test_backend_parity_rwkv():
    """Recurrent pools: wkv6 kernel prefill with carried-state out; decode
    stays on the (elementwise) XLA step on both backends."""
    _assert_backend_parity("rwkv6_7b", lengths=(4, 6, 4))


def test_backend_parity_hybrid_zamba2():
    """Hybrid stacks: ssd kernel for the mamba mixers + flash/decode
    kernels for the parameter-shared attention blocks."""
    _assert_backend_parity("zamba2_7b", lengths=(4, 6), n_new=3)


def test_backend_parity_encdec_seamless():
    """Enc-dec: non-causal encoder prefill, cross-attention with per-row
    kv_len over the over-allocated cross cache, mixed encoder lengths."""
    _assert_backend_parity("seamless_m4t_large_v2", lengths=(4, 6, 5),
                           enc_lens=(5, 8, 5))


def test_backend_parity_chunked_prefill():
    """Chunked prefill: prompts longer than the largest bucket run in
    chunks whose suffix queries mask via the kernels' static q_start."""
    _assert_backend_parity("llama3_2_1b", lengths=(9, 11), n_new=3,
                           prefill_buckets=(4,), max_seq_len=24)


def test_backend_validation():
    with pytest.raises(ValueError, match="pallas"):
        _build("llama3_2_1b", "tpu-only")
