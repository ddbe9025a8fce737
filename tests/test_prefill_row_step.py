"""The row-indexed prefill step (the served prefill dispatch): it computes
one session's prompt chunk on that session's pool row alone and writes the
row back into the donated pool in place.  Checked against the N-row pooled
prefill step with only that row active, in the slab and paged layouts and
for the decoder, recurrent, shared-attention and enc-dec families; and in
the engine, where a bucket group dispatches it once per member per hop."""
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
from repro.serving import GeoServingSystem
from repro.serving.kv_cache import (_SELF_KV_KEYS, kind_runs,
                                    make_paged_prefill_step,
                                    make_paged_row_prefill_step,
                                    make_pool_prefill_step,
                                    make_pool_row_prefill_step,
                                    new_paged_pool_tree, new_state_pool_tree)
from tests.test_prefill_bucketing import PROGRAM_TOL

N, T_MAX, PAGE, ROW = 4, 16, 4, 2  # pool rows, cache length, page, row
ENC_POOL, ENC_LEN = 8, 6  # cross-KV extent of the pool, of the session

_PARAMS = {}


def _params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)[0]
    return _PARAMS[cfg.name]


def _random_pool(cfg, kinds, layout, rng):
    """Per-run pool trees of random contents (numpy), and the page table
    (paged: row ROW holds pages for its first 12 positions only, so the
    rest of its table points at the trash page; other rows are full)."""
    trees, table = [], None
    max_pages = T_MAX // PAGE
    if layout == "paged":
        ids = rng.permutation(np.arange(1, N * max_pages + 1))
        table = ids.reshape(N, max_pages).astype(np.int32)
        table[ROW, 3:] = 0
    for kind, lo, hi in kind_runs(kinds):
        if layout == "paged":
            t = new_paged_pool_tree(cfg, kind, hi - lo, N, T_MAX, PAGE,
                                    N * max_pages + 1, ENC_POOL)
        else:
            t = new_state_pool_tree(cfg, kind, hi - lo, N, T_MAX, ENC_POOL)
        trees.append({k: rng.randn(*x.shape).astype(x.dtype)
                      for k, x in t.items()})
    return tuple(trees), table


def _row_view(tree, key, table, layout):
    """Row ROW's (layers, T_MAX, ...) view of one self-KV leaf."""
    x = np.asarray(tree[key])
    if layout == "slab":
        return x[:, ROW]
    g = x[:, table[ROW]]  # (layers, max_pages, page, ...)
    return g.reshape((g.shape[0], T_MAX) + g.shape[3:])


def _steps(cfg, kinds, layout):
    if layout == "paged":
        return (make_paged_row_prefill_step(cfg, kinds, "xla", PAGE),
                make_paged_prefill_step(cfg, kinds, "xla", PAGE))
    return (make_pool_row_prefill_step(cfg, kinds),
            make_pool_prefill_step(cfg, kinds))


def _run_both(cfg, kinds, layout, pool, table, h, emb0, enc, active,
              offset, phase):
    """(row step, N-row step with only ROW active) on copies of ``pool``."""
    params = _params(cfg)
    run_params = tuple(block_param_range(params, cfg, kind, lo, hi)
                       for kind, lo, hi in kind_runs(kinds))
    shared = params.get("shared")
    lids = jnp.arange(len(kinds), dtype=jnp.int32)
    dummy = jnp.zeros((1, 1, 1), jnp.float32)
    row_step, n_step = _steps(cfg, kinds, layout)
    pt = () if table is None else (jnp.asarray(table),)

    def fresh():
        return tuple({k: jnp.asarray(v) for k, v in t.items()} for t in pool)

    def rows(x):
        if x is None:
            return dummy
        return jnp.zeros((N,) + x.shape[1:], x.dtype).at[ROW].set(x[0])

    one = (h, dummy if emb0 is None else emb0, dummy if enc is None else enc)
    h_row, got = row_step(run_params, shared, fresh(), *pt, *one,
                          np.int32(ROW), jnp.asarray(active[:, None]), lids,
                          offset, phase)
    mask = np.zeros((len(kinds), N), bool)
    mask[:, ROW] = active
    h_all, want = n_step(run_params, shared, fresh(), *pt, rows(h),
                         rows(emb0), rows(enc), jnp.asarray(mask), lids,
                         offset, phase)
    return h_row, got, h_all[ROW][None], want


def _check(layout, pool, table, got, want, h_row, h_ref, offset, t):
    """Other rows (pages) bit-equal to before; row ROW as the N-row step
    writes it; the chunk's hidden rows as the N-row step gives them."""
    np.testing.assert_allclose(np.asarray(h_row), np.asarray(h_ref),
                               **PROGRAM_TOL)
    other = [r for r in range(N) if r != ROW]
    keep_pages = np.setdiff1d(np.arange(1, table.size + 1), table[ROW]) \
        if table is not None else None
    for old_t, new_t, ref_t in zip(pool, got, want):
        for key, old in old_t.items():
            new, ref = np.asarray(new_t[key]), np.asarray(ref_t[key])
            if layout == "paged" and key in _SELF_KV_KEYS:
                np.testing.assert_array_equal(new[:, keep_pages],
                                              old[:, keep_pages])
            else:
                np.testing.assert_array_equal(new[:, other], old[:, other])
            if key in _SELF_KV_KEYS:
                n_row = _row_view(new_t, key, table, layout)
                r_row = _row_view(ref_t, key, table, layout)
                o_row = _row_view(old_t, key, table, layout)
                lim = T_MAX if table is None else 3 * PAGE
                np.testing.assert_allclose(n_row[:, :lim], r_row[:, :lim],
                                           **PROGRAM_TOL)
                # positions outside the chunk keep what the row held
                np.testing.assert_array_equal(n_row[:, :offset],
                                              o_row[:, :offset])
                np.testing.assert_array_equal(n_row[:, offset + t:lim],
                                              o_row[:, offset + t:lim])
            else:
                np.testing.assert_allclose(new[:, ROW], ref[:, ROW],
                                           **PROGRAM_TOL)


LAYOUTS = ["slab", "paged"]
# (arch, chunk width, offsets): recurrent stacks prefill at the exact
# length in one shot; attention stacks also resume at a chunk offset
CASES = [("llama3_2_1b", 4, (0, 8)), ("rwkv6_7b", 5, (0,)),
         ("zamba2_7b", 5, (0,)), ("seamless_m4t_large_v2", 4, (0, 8))]


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("arch,t,offsets", CASES,
                         ids=[c[0] for c in CASES])
def test_row_step_writes_only_its_row(arch, t, offsets, layout):
    cfg = get_reduced_config(arch)
    kinds = stack_block_kinds(cfg)
    rng = np.random.RandomState(0)
    pool, table = _random_pool(cfg, kinds, layout, rng)
    d = cfg.d_model
    n_enc = cfg.n_enc_layers
    active = np.ones(len(kinds), bool)
    active[0] = n_enc > 0  # a hop that starts one layer in (encoder: all)
    enc = None
    if n_enc:  # the encoder phase first: it reads and writes no state
        frames = jnp.asarray(rng.randn(1, ENC_LEN, d), jnp.float32)
        h_row, got, h_ref, want = _run_both(
            cfg, kinds, layout, pool, table, frames, None, None, active,
            0, "enc")
        np.testing.assert_allclose(np.asarray(h_row), np.asarray(h_ref),
                                   **PROGRAM_TOL)
        for old_t, new_t in zip(pool, got):
            for key, old in old_t.items():
                np.testing.assert_array_equal(np.asarray(new_t[key]), old)
        enc = h_row
    for offset in offsets:
        h = jnp.asarray(rng.randn(1, t, d), jnp.float32)
        emb0 = (jnp.asarray(rng.randn(1, t, d), jnp.float32)
                if "mamba_shared" in kinds else None)
        phase = "dec" if n_enc else "all"
        h_row, got, h_ref, want = _run_both(
            cfg, kinds, layout, pool, table, h, emb0, enc, active, offset,
            phase)
        _check(layout, pool, table, got, want, h_row, h_ref, offset, t)


def _system(layout):
    cfg = get_reduced_config("llama3_2_1b")
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=50.0,
                  cache_bytes_per_token=1.0)
    servers = [ServerSpec(j, mem_bytes=900.0, tau=0.01 * (j + 1),
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005) for j in range(4)]
    rtt = np.full((1, 4), 0.02)
    prob = Problem(llm, servers, 1, rtt, rtt * 3, workload=Workload(6, 5))
    return GeoServingSystem(
        cfg, _params(cfg), prob, algorithm="proposed", R=2,
        max_new_tokens=5, max_sessions=8, max_seq_len=48,
        cache_layout=layout, page_size=4 if layout == "paged" else None)


def _serve(system, prompts, coalesce, calls=None):
    route, _ = shortest_path_route(system.problem, system.alive_placement(),
                                   0)
    if calls is not None:
        for srv in system.servers.values():
            fn = srv.prefill_row

            def spy(h, *a, _fn=fn, _srv=srv, **kw):
                calls.append((_srv.sid, h.shape))
                return _fn(h, *a, **kw)

            srv.prefill_row = spy
    sids = [system.create_session(p, 0, route, 5) for p in prompts]
    if coalesce:
        assert system.try_admit_sessions(sids) == sids
        system.drain_prefill()
    else:
        for sid in sids:
            assert system.try_admit_session(sid)
    hist = {s: [np.asarray(system.sessions[s].last_logits)] for s in sids}
    while any(system.sessions[s].n_generated < 5 for s in sids):
        todo = [s for s in sids if system.sessions[s].n_generated < 5]
        system.decode_round(todo)
        for s in todo:
            hist[s].append(np.asarray(system.sessions[s].last_logits))
    return route, [list(system.sessions[s].tokens) for s in sids], \
        [hist[s] for s in sids]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_group_dispatches_one_row_per_member(layout):
    """A bucket group of three members dispatches the row step three times
    per hop, each with one row, and every member's stream is bit-identical
    to its solo admission."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(2, 64, n) for n in (5, 7, 6)]  # one bucket, 8
    calls = []
    route, toks_g, logits_g = _serve(_system(layout), prompts, True, calls)
    hops = [j for j in route.servers]
    assert [j for j, _ in calls] == [j for j in hops for _ in prompts]
    assert {shape for _, shape in calls} == {(1, 8, 64)}
    _, toks_s, logits_s = _serve(_system(layout), prompts, False)
    assert toks_g == toks_s
    for lg, ls in zip(logits_g, logits_s):
        for a, b in zip(lg, ls):
            np.testing.assert_array_equal(a, b)
