"""Device-group servers: sharded pooled serving == the mesh=None twin.

The tentpole contract (docs/serving.md "Device-group servers"): threading a
``jax.sharding.Mesh`` through the pooled serving steps must not change WHAT
is computed — only where.  Three tiers of evidence:

* trivial 1-device mesh: the constraint path is BIT-exact against mesh=None
  (tokens, virtual clock, logits) — runs everywhere, no forced devices;
* real 8-device mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
  the ``sharded-parity`` CI lane): token streams and the virtual clock are
  EXACTLY equal across decoder / MLA / MoE-EP x fused / serial x slab /
  paged; logits agree to float-eps (sharded contracting-dim matmuls reorder
  reductions);
* a subprocess acceptance test that forces 8 host devices itself, so tier-1
  proves the multi-device contract even when collected on one device.

Also here: τ calibration from the sharded step's per-device cost analysis
(``calibrate_taus`` -> ``with_server_taus``) and the pure-EP shard_map MoE
under a real multi-device mesh.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                        shortest_path_route)
from repro.launch.mesh import compat_make_mesh
from repro.models import init_params
from repro.serving import GeoServingSystem

pytestmark = pytest.mark.sharded

N_DEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    N_DEV < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8"
    " (the sharded-parity CI lane)")

# sharded matmuls split contracting dims -> per-device partial sums reduce
# in a different order than the single-device GEMM; same float32 scale of
# slack as the fused-tail tolerance in test_round_fusion.py
LOGIT_TOL = dict(atol=5e-6, rtol=1e-4)

# arch x mesh shape: deepseek = MLA latent caches, TP over "model";
# llama4-scout = small-E MoE, experts sharded over "data" (EP);
# llama3 = plain GQA decoder.
ARCH_MESH = [
    ("llama3_2_1b", (2, 4)),
    ("deepseek_v2_236b", (2, 4)),
    ("llama4_scout_17b_a16e", (4, 2)),
]

_PARAMS_CACHE = {}


def _params_for(cfg):
    if cfg.name not in _PARAMS_CACHE:
        _PARAMS_CACHE[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)[0]
    return _PARAMS_CACHE[cfg.name]


def _problem(cfg, n_servers=2, l_out=4):
    llm = LLMSpec("toy", cfg.n_layers, block_bytes=100.0,
                  cache_bytes_per_token=1.0)
    servers = [ServerSpec(j, mem_bytes=1000.0, tau=0.01 * (j + 1),
                          tau_prefill_base=0.002,
                          tau_prefill_per_token=0.0005)
               for j in range(n_servers)]
    rtt = np.full((1, n_servers), 0.02)
    return Problem(llm, servers, 1, rtt, rtt * 3, workload=Workload(4, l_out))


def _build(arch, mesh, *, decode_mode="fused", cache_layout="slab",
           page_size=None, max_new=4):
    cfg = get_reduced_config(arch)
    system = GeoServingSystem(cfg, _params_for(cfg), _problem(cfg, 2, max_new),
                              algorithm="proposed", R=2,
                              max_new_tokens=max_new, max_sessions=4,
                              decode_mode=decode_mode,
                              cache_layout=cache_layout, page_size=page_size,
                              mesh=mesh)
    return cfg, system


def _jobs_for(cfg, lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, cfg.vocab_size, n) for n in lengths]


def _serve(system, jobs, n_new=4):
    """Admit, prefill, decode to completion.  Returns (tokens, virtual
    times, per-round logits histories) per session."""
    sids = []
    for prompt in jobs:
        route, _ = shortest_path_route(system.problem,
                                       system.alive_placement(), 0)
        sids.append(system.create_session(prompt, 0, route, n_new))
    assert system.try_admit_sessions(sids) == sids
    system.drain_prefill()
    hist = {s: [np.asarray(system.sessions[s].last_logits)] for s in sids}
    while True:
        todo = [s for s in sids if system.sessions[s].n_generated < n_new]
        if not todo:
            break
        system.decode_round(todo)
        for s in todo:
            hist[s].append(np.asarray(system.sessions[s].last_logits))
    toks = [list(system.sessions[s].tokens) for s in sids]
    vts = [float(system.sessions[s].virtual_time) for s in sids]
    for s in sids:
        system.retire_session(s)
    return toks, vts, [hist[s] for s in sids]


# ---------------------------------------------------------------------------
# Trivial mesh: bit-exact twin, no forced devices needed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3_2_1b", "deepseek_v2_236b"])
def test_trivial_mesh_is_bit_exact(arch):
    """A 1-device mesh exercises the whole sharded code path (device_put'd
    params/pools, constrained steps, frozen rules in the jit keys) with
    no actual partitioning — everything, logits included, must be
    BIT-identical to mesh=None."""
    cfg, ref = _build(arch, None)
    jobs = _jobs_for(cfg, (4, 6))
    want = _serve(ref, jobs)

    mesh = compat_make_mesh((1, 1), ("data", "model"))
    cfg, system = _build(arch, mesh)
    got = _serve(system, jobs)
    assert got[0] == want[0], f"{arch}: tokens diverge under trivial mesh"
    assert got[1] == want[1], f"{arch}: virtual clock diverges"
    for hg, hw in zip(got[2], want[2]):
        for a, b in zip(hg, hw):
            np.testing.assert_array_equal(a, b)  # bit-for-bit


def test_mesh_rules_roundtrip_and_override():
    """``mesh_rules`` is accepted as a dict or a frozen tuple and lands on
    every server; the derived default comes from ``serving_rules``."""
    from repro.launch.sharding import freeze_rules, serving_rules, thaw_rules

    mesh = compat_make_mesh((1, 1), ("data", "model"))
    cfg, system = _build("llama3_2_1b", mesh)
    srv = next(iter(system.servers.values()))
    derived = serving_rules(cfg, mesh, srv.pool.n_rows, srv.pool.max_len)
    assert srv.mesh_rules == derived
    assert thaw_rules(freeze_rules(derived)) == derived
    assert freeze_rules(None) is None and thaw_rules(None) == {}

    override = dict(derived, batch=None)
    cfg2, system2 = _build("llama3_2_1b", mesh)
    system2b = GeoServingSystem(cfg2, _params_for(cfg2), _problem(cfg2),
                                R=2, max_new_tokens=4, max_sessions=4,
                                mesh=mesh, mesh_rules=override)
    srv2 = next(iter(system2b.servers.values()))
    assert srv2.mesh_rules["batch"] is None


# ---------------------------------------------------------------------------
# τ calibration from the (sharded) step's cost analysis
# ---------------------------------------------------------------------------


def test_calibrated_taus_feed_perf_model():
    """AOT cost -> roofline -> per-server τ: finite, positive, folded into a
    COPY of the problem (the live engine keeps its spec'd τ — the parity
    contract says a mesh must not change the virtual clock)."""
    mesh = compat_make_mesh((1, 1), ("data", "model"))
    cfg, system = _build("llama3_2_1b", mesh)
    cost = next(iter(system.servers.values())).decode_step_cost()
    assert cost.flops > 0 and cost.bytes_accessed > 0
    taus = system.calibrate_taus("TPU v5 lite")
    assert set(taus) == set(system.servers)
    assert all(np.isfinite(t) and t > 0 for t in taus.values())
    cal = system.calibrated_problem("TPU v5 lite")
    np.testing.assert_allclose(cal.tau(),
                               [taus[s.sid] for s in cal.servers])
    # the live problem is untouched
    assert system.problem.tau().tolist() == [0.01, 0.02]


def test_calibration_without_mesh():
    """mesh=None servers calibrate too (n_chips=1): the same entry point
    covers plain single-device serving."""
    cfg, system = _build("llama3_2_1b", None)
    taus = system.calibrate_taus("TPU v5 lite")
    assert all(np.isfinite(t) and t > 0 for t in taus.values())
    # priced by the server's own device unless a kind is named: the CPU
    # has no published peaks, so it raises instead of borrowing a TPU's
    with pytest.raises(ValueError, match="cpu"):
        system.calibrate_taus()


# ---------------------------------------------------------------------------
# Real 8-device mesh: the parity matrix (sharded-parity CI lane)
# ---------------------------------------------------------------------------


@needs8
@pytest.mark.parametrize("layout,page_size", [("slab", None), ("paged", 2)])
@pytest.mark.parametrize("mode", ["fused", "serial"])
@pytest.mark.parametrize("arch,mesh_shape", ARCH_MESH)
def test_sharded_matches_single_device(arch, mesh_shape, mode, layout,
                                       page_size):
    """The acceptance matrix: decoder / MLA / MoE-EP x fused / serial x
    slab / paged on a real (data, model) mesh — tokens and virtual clock
    EXACTLY equal to the mesh=None twin, logits to float-eps."""
    cfg, ref = _build(arch, None, decode_mode=mode, cache_layout=layout,
                      page_size=page_size)
    jobs = _jobs_for(cfg, (4, 6, 5))
    want = _serve(ref, jobs)

    mesh = compat_make_mesh(mesh_shape, ("data", "model"))
    cfg, system = _build(arch, mesh, decode_mode=mode, cache_layout=layout,
                         page_size=page_size)
    got = _serve(system, jobs)
    assert got[0] == want[0], f"{arch}/{mode}/{layout}: tokens diverge"
    assert got[1] == want[1], f"{arch}/{mode}/{layout}: vclock diverges"
    for hg, hw in zip(got[2], want[2]):
        for a, b in zip(hg, hw):
            np.testing.assert_allclose(a, b, **LOGIT_TOL)


@needs8
def test_sharded_solo_matches_grouped():
    """Under a mesh, solo and grouped sessions still share ONE pooled
    program — bit-for-bit identical tokens and logits."""
    mesh = compat_make_mesh((2, 4), ("data", "model"))
    cfg, system = _build("deepseek_v2_236b", mesh)
    jobs = _jobs_for(cfg, (4, 6, 5))
    toks_g, _, hist_g = _serve(system, jobs)
    toks_s, hist_s = [], []
    for job in jobs:
        t, _, h = _serve(system, [job])
        toks_s += t
        hist_s += h
    assert toks_s == toks_g
    for hs, hg in zip(hist_s, hist_g):
        for a, b in zip(hs, hg):
            np.testing.assert_array_equal(a, b)  # bit-for-bit


@needs8
def test_sharded_step_params_and_pools_actually_shard():
    """On an 8-device mesh at least one param leaf and one cache leaf must
    be non-trivially partitioned (the point of a device group), and the
    calibrated τ reflects per-device costs."""
    mesh = compat_make_mesh((2, 4), ("data", "model"))
    cfg, system = _build("deepseek_v2_236b", mesh)
    srv = next(iter(system.servers.values()))

    def any_sharded(tree):
        return any(
            not leaf.sharding.is_fully_replicated
            for leaf in jax.tree.leaves(tree))

    assert any_sharded(srv.run_params), "no param leaf is partitioned"
    assert any_sharded(srv.pool.tree), "no cache leaf is partitioned"
    taus = system.calibrate_taus("TPU v5 lite")
    assert all(np.isfinite(t) and t > 0 for t in taus.values())


@needs8
def test_ep_shard_map_on_real_mesh():
    """Pure-EP shard_map dispatch == global sort-dispatch on a REAL
    multi-device mesh (test_moe_ep.py proves it on 1 device; here the
    all_to_alls actually move tokens between devices)."""
    from repro.models import moe as moe_mod
    from repro.models.layers import NULL_SH, ShardingCtx

    cfg = get_reduced_config("deepseek_v2_236b").replace(capacity_factor=8.0)
    E = cfg.n_experts
    params, _ = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 16, cfg.d_model), jnp.float32) * 0.3
    ref, aux_ref = moe_mod.apply_moe(params, cfg, NULL_SH, x)

    mesh = compat_make_mesh((2, 2), ("data", "model"))
    sh = ShardingCtx(mesh, {"batch": "data", "seq_act": None})
    padded = dict(params)
    for k in ("wg", "wu", "wo"):
        w = params[k]
        pad = jnp.zeros((2 * E - E,) + w.shape[1:], w.dtype)
        padded[k] = jnp.concatenate([w, pad], axis=0)
    got, aux = moe_mod._apply_moe_ep(padded, cfg, sh, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    assert float(aux["moe_drop_frac"]) < 1e-6


# ---------------------------------------------------------------------------
# Heterogeneous device groups: per-server meshes (the tentpole)
# ---------------------------------------------------------------------------

HETERO_SHAPES = {0: None, 1: (1, 2), 2: (2, 2)}  # solo, 2-dev TP, 4-dev


def _build_hetero(arch, groups, *, decode_mode="fused", cache_layout="slab",
                  page_size=None, max_new=4):
    """3-server deployment at R=3 (every server hosts every block, so every
    group's sharded step actually runs) with per-server device groups."""
    cfg = get_reduced_config(arch)
    system = GeoServingSystem(cfg, _params_for(cfg),
                              _problem(cfg, 3, max_new), algorithm="proposed",
                              R=3, max_new_tokens=max_new, max_sessions=4,
                              decode_mode=decode_mode,
                              cache_layout=cache_layout, page_size=page_size,
                              device_groups=groups)
    assert len(system.servers) == 3  # R=3: every server hosts every block
    return cfg, system


def test_all_solo_device_groups_are_byte_identical():
    """device_groups with every entry None (or missing) IS the unsharded
    engine: same jit twin from the factory cache, bit-identical serving."""
    cfg, ref = _build_hetero("llama3_2_1b", None)
    jobs = _jobs_for(cfg, (4, 6))
    want = _serve(ref, jobs)

    cfg, system = _build_hetero("llama3_2_1b", {0: None, 2: None})
    for srv in system.servers.values():
        assert srv.mesh is None and srv.n_chips == 1
    got = _serve(system, jobs)
    assert got[0] == want[0] and got[1] == want[1]
    for hg, hw in zip(got[2], want[2]):
        for a, b in zip(hg, hw):
            np.testing.assert_array_equal(a, b)  # bit-for-bit


@needs8
@pytest.mark.parametrize("layout,page_size", [("slab", None), ("paged", 2)])
@pytest.mark.parametrize("mode", ["fused", "serial"])
def test_hetero_groups_match_all_solo_twin(mode, layout, page_size):
    """The hetero acceptance matrix: mixed {solo, 2-device, 4-device}
    groups on one host — token streams and the virtual clock EXACTLY equal
    to the all-solo twin across fused/serial x slab/paged."""
    from repro.launch.mesh import group_meshes

    cfg, ref = _build_hetero("llama3_2_1b", None, decode_mode=mode,
                             cache_layout=layout, page_size=page_size)
    jobs = _jobs_for(cfg, (4, 6, 5))
    want = _serve(ref, jobs)

    groups = group_meshes(HETERO_SHAPES)
    cfg, system = _build_hetero("llama3_2_1b", groups, decode_mode=mode,
                                cache_layout=layout, page_size=page_size)
    assert [system.servers[j].n_chips for j in sorted(system.servers)] \
        == [1, 2, 4]
    got = _serve(system, jobs)
    assert got[0] == want[0], f"hetero/{mode}/{layout}: tokens diverge"
    assert got[1] == want[1], f"hetero/{mode}/{layout}: vclock diverges"
    for hg, hw in zip(got[2], want[2]):
        for a, b in zip(hg, hw):
            np.testing.assert_allclose(a, b, **LOGIT_TOL)


@needs8
def test_hetero_groups_disjoint_devices_and_own_rules():
    """Each server's params/pool live on ITS OWN device slice; per-group
    rule derivation is independent (frozen_serving_rules cache keys on the
    group's mesh)."""
    from repro.launch.mesh import group_meshes
    from repro.launch.sharding import serving_rules

    groups = group_meshes(HETERO_SHAPES)
    cfg, system = _build_hetero("llama3_2_1b", groups)
    seen = set()
    for j, srv in system.servers.items():
        devs = set(srv.group.devices)
        assert not (devs & seen), f"server {j} shares devices"
        seen |= devs
        if srv.mesh is not None:
            assert srv.mesh_rules == serving_rules(
                cfg, srv.mesh, srv.pool.n_rows, srv.pool.max_len)
            for leaf in jax.tree.leaves(srv.run_params):
                assert set(leaf.sharding.device_set) == devs


@needs8
def test_hetero_calibrated_taus_are_non_constant():
    """The acceptance criterion: on a heterogeneous deployment (identical
    spec'd servers, different device groups) calibrate_taus() yields a
    NON-constant vector — bigger groups get smaller per-device roofline
    bounds — and calibrated_problem() carries it while the live problem
    keeps its spec'd τ."""
    from repro.launch.mesh import group_meshes

    groups = group_meshes(HETERO_SHAPES)
    cfg, system = _build_hetero("llama3_2_1b", groups)
    taus = system.calibrate_taus("TPU v5 lite")
    assert set(taus) == {0, 1, 2}
    assert all(np.isfinite(t) and t > 0 for t in taus.values())
    assert len({round(t, 15) for t in taus.values()}) > 1, taus
    # more devices -> per-device step cost can only shrink
    assert taus[2] <= taus[0] * (1 + 1e-9), taus
    cal = system.calibrated_problem("TPU v5 lite")
    np.testing.assert_allclose(cal.tau(), [taus[0], taus[1], taus[2]])
    assert system.problem.tau().tolist() == [0.01, 0.02, 0.03]


def test_device_groups_and_global_mesh_are_exclusive():
    cfg = get_reduced_config("llama3_2_1b")
    mesh = compat_make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="not both"):
        GeoServingSystem(cfg, _params_for(cfg), _problem(cfg), R=2,
                         max_new_tokens=4, max_sessions=4, mesh=mesh,
                         device_groups={0: mesh})


# ---------------------------------------------------------------------------
# Padded MoE EP through the pooled decode step (satellite of PR 9)
# ---------------------------------------------------------------------------


def _pad_model_experts(params, E, E_alloc):
    """Zero-pad the stacked per-layer expert weights (L, E, ...) ->
    (L, E_alloc, ...): the global path slices ``[:E]`` so the pad is inert
    on the solo twin, while a mesh makes the pooled decode step take the
    pure-EP all-to-all (kv_cache._ep_row_grid)."""
    out = jax.tree.map(lambda x: x, params)  # fresh containers, shared leaves
    ffn = out["segments"]["blocks"]["ffn"]
    for k in ("wg", "wu", "wo"):
        w = ffn[k]
        pad = jnp.zeros((w.shape[0], E_alloc - E) + w.shape[2:], w.dtype)
        ffn[k] = jnp.concatenate([w, pad], axis=1)
    return out


@needs8
def test_padded_ep_through_pooled_decode_step():
    """ROADMAP closure: the padded `_apply_moe_ep` all-to-all path runs
    THROUGH a pooled decode step on a real (2,2) mesh — not just
    standalone.  The decoder body regroups the pool's rows into a
    (n_data, rows/n_data) grid for the position-free FFN half; tokens and
    the virtual clock stay EXACTLY equal to the solo twin on the same
    padded params (which the global path slices back to E)."""
    import repro.serving.kv_cache as KV
    from repro.launch.sharding import freeze_rules

    cfg = get_reduced_config("llama4_scout_17b_a16e")
    E = cfg.n_experts
    params = _pad_model_experts(_params_for(cfg), E, 2 * E)

    def build(mesh):
        return GeoServingSystem(cfg, params, _problem(cfg, 2, 4),
                                algorithm="proposed", R=2, max_new_tokens=4,
                                max_sessions=4, mesh=mesh)

    ref = build(None)
    jobs = _jobs_for(cfg, (4, 6, 5))
    want = _serve(ref, jobs)

    mesh = compat_make_mesh((2, 2), ("data", "model"))
    system = build(mesh)
    srv = next(iter(system.servers.values()))
    frozen = freeze_rules(srv.mesh_rules)
    grid = KV._ep_row_grid(cfg, mesh, frozen, srv.run_params[0],
                           srv.pool.n_rows)
    assert grid == (2, srv.pool.n_rows // 2), \
        "pooled decode step did not engage the EP row grid"
    got = _serve(system, jobs)
    assert got[0] == want[0], "EP-through-decode: tokens diverge"
    assert got[1] == want[1], "EP-through-decode: vclock diverges"
    for hg, hw in zip(got[2], want[2]):
        for a, b in zip(hg, hw):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


@needs8
def test_unpadded_moe_keeps_reference_decode_trace():
    """Reduced (unpadded) MoE configs must NOT take the EP decode branch:
    the gate keys on padded expert weights, so existing sharded parity
    stays byte-identical."""
    import repro.serving.kv_cache as KV
    from repro.launch.sharding import freeze_rules

    cfg = get_reduced_config("llama4_scout_17b_a16e")
    mesh = compat_make_mesh((2, 2), ("data", "model"))
    system = GeoServingSystem(cfg, _params_for(cfg), _problem(cfg, 2, 4),
                              R=2, max_new_tokens=4, max_sessions=4,
                              mesh=mesh)
    srv = next(iter(system.servers.values()))
    frozen = freeze_rules(srv.mesh_rules)
    assert KV._ep_row_grid(cfg, mesh, frozen, srv.run_params[0],
                           srv.pool.n_rows) is None


# ---------------------------------------------------------------------------
# Subprocess acceptance: force 8 devices regardless of the parent process
# ---------------------------------------------------------------------------

_ACCEPT_SCRIPT = r"""
import jax, numpy as np
assert len(jax.devices()) == 8, jax.devices()
from repro.configs import get_reduced_config
from repro.core import (LLMSpec, Problem, ServerSpec, Workload,
                        shortest_path_route)
from repro.launch.mesh import compat_make_mesh
from repro.models import init_params
from repro.serving import GeoServingSystem


def run(arch, mesh_shape):
    cfg = get_reduced_config(arch)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    llm = LLMSpec("toy", cfg.n_layers, 100.0, 1.0)
    servers = [ServerSpec(j, 1000.0, 0.01 * (j + 1), 0.002, 0.0005)
               for j in range(2)]
    rtt = np.full((1, 2), 0.02)
    prob = Problem(llm, servers, 1, rtt, 3 * rtt, workload=Workload(4, 4))
    out = {}
    for tag, mesh in (("ref", None),
                      ("sharded", compat_make_mesh(mesh_shape,
                                                   ("data", "model")))):
        system = GeoServingSystem(cfg, params, prob, R=2, max_new_tokens=4,
                                  max_sessions=4, mesh=mesh)
        rng = np.random.RandomState(0)
        sids = []
        for n in (4, 6):
            route, _ = shortest_path_route(prob, system.alive_placement(), 0)
            sids.append(system.create_session(
                rng.randint(2, cfg.vocab_size, n), 0, route, 4))
        assert system.try_admit_sessions(sids) == sids
        system.drain_prefill()
        while any(system.sessions[s].n_generated < 4 for s in sids):
            system.decode_round()
        out[tag] = ([list(system.sessions[s].tokens) for s in sids],
                    [float(system.sessions[s].virtual_time) for s in sids])
    assert out["sharded"][0] == out["ref"][0], (arch, "tokens")
    assert out["sharded"][1] == out["ref"][1], (arch, "vclock")


run("deepseek_v2_236b", (2, 4))   # MLA, TP over model
run("llama4_scout_17b_a16e", (4, 2))  # MoE, EP over data
print("SHARDED_PARITY_OK")
"""


@pytest.mark.slow
def test_forced_8_device_parity_subprocess(tmp_path):
    """The acceptance criterion, self-contained: a fresh interpreter forces
    8 host devices via XLA_FLAGS, then checks sharded-vs-twin token and
    virtual-clock equality for the TP (deepseek MLA) and EP (llama4-scout)
    configs.  Runs in tier-1 even though the parent has 1 device."""
    script = tmp_path / "accept.py"
    script.write_text(_ACCEPT_SCRIPT)
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(repo / "src")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_PARITY_OK" in proc.stdout
