"""Chip smoke: OLMo-1B at its published widths, served through
``GeoServingSystem`` on a TPU, with random bf16 weights from ``--seed``.

    python chip_smoke.py [--seed 0]        # one chip
    python chip_smoke.py --chips 4         # the four-chip phase only

One chip: four servers of four blocks each share the chip and form one
route.  ``ContinuousBatchingScheduler`` serves prompts of 64-512 tokens,
32 new tokens each, on the default path (batched prefill, fused decode,
slab cache, ``backend="xla"``), then again with ``backend="pallas"``.  One
request's prefill logits and first 8 decode-step logits are held against
the float32 monolithic forward of ``repro.models.model``; the Pallas run
must lower its round and prefill steps to Mosaic kernels
(``tpu_custom_call``) with no call falling back to XLA, and agree with the
XLA run.

``--chips 4``: one request traced through the same fleet with each
server on its own chip (a route that crosses all four) against the fleet
on one chip, and through one server as a (2, 2) device group against its
solo twin.

Times printed are wall clock from this smoke, not benchmark results.  The
last line of stdout is ``{"ok": true, "device": {...}}``; a failed phase,
or a host without a TPU, exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from importlib import metadata
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_SERVERS = 4
PROMPT_LENS = (64, 200, 333, 512)  # prefill buckets 64, 256, 512
N_NEW = 32
MAX_SEQ_LEN = 1024
MAX_SESSIONS = 8
N_CHECK_STEPS = 8

# Logits are compared as max|diff| / max|ref| over the vocab, per row.
# The engine runs bf16 weights and activations with f32 accumulation; bf16
# keeps 8 significant bits (relative rounding 2^-9), the residual stream
# is rounded to bf16 after every block and the cache holds bf16 K/V.  On
# the CPU backend the engine sat 0.8% from the f32 reference (highest
# matmul precision) at 4 and at 8 full-width blocks alike, while changing
# one of 64 prompt tokens moved the reference's own logits 12-22%: a wrong
# position, mask or cache row shows at that scale.  Two bf16 runs that
# round at different points (Pallas kernels against XLA, a (2, 2) device
# group against its solo twin) differ from each other by about as much as
# either differs from the reference, so one tolerance serves all checks.
TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase's check failed; the smoke exits non-zero."""


def require(ok, msg):
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not ok:
        raise SmokeFailure(msg)


def device_report():
    """Versions and devices; exits non-zero (no result line) off-TPU."""
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"versions: python {sys.version.split()[0]} jax {jax.__version__} "
          f"jaxlib {metadata.version('jaxlib')} libtpu {libtpu}")
    devs = jax.devices()
    d0 = devs[0]
    print(f"devices: {len(devs)} x platform={d0.platform} "
          f"device_kind={d0.device_kind!r}: {devs}")
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {d0.platform}); nothing was "
              "run", file=sys.stderr)
        sys.exit(1)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def olmo_problem(cfg, n_servers=N_SERVERS, blocks_per_server=None):
    """Servers sized for ``blocks_per_server`` blocks (default: the stack
    split evenly) plus cache slots for MAX_SESSIONS worst-case sessions on
    each block — what CG-BP then places as a chain.  At OLMo-1B's widths
    four such servers hold 4 x 4 blocks of 128 MiB each and 4 x 4 x 8 rows
    of 8 MiB of bf16 cache (1024 tokens): about 3 GiB on top of the 2.4 GB
    of weights the client keeps, well inside one 16 GB chip."""
    from repro.core import LLMSpec, Problem, ServerSpec, Workload

    wl = Workload(l_in=max(PROMPT_LENS), l_out=N_NEW)
    llm = LLMSpec.from_model_config(cfg)
    k = blocks_per_server or cfg.n_layers // n_servers
    mem = k * llm.block_bytes + MAX_SESSIONS * llm.cache_bytes(
        wl.total_tokens) * k
    servers = [ServerSpec(j, mem, tau=0.002 * (1 + 0.1 * j),
                          tau_prefill_base=0.01, tau_prefill_per_token=1e-5)
               for j in range(n_servers)]
    rtt = 0.01 * (1 + np.arange(n_servers, dtype=float))[None]
    return Problem(llm, servers, 1, rtt, 3 * rtt, workload=wl)


def build(cfg, params, problem, backend="xla", device_groups=None):
    from repro.serving import GeoServingSystem

    system = GeoServingSystem(cfg, params, problem, algorithm="proposed",
                              R=MAX_SESSIONS // 2, max_new_tokens=N_NEW,
                              max_sessions=MAX_SESSIONS,
                              max_seq_len=MAX_SEQ_LEN, backend=backend,
                              device_groups=device_groups)
    placed = [(j, int(system.placement.a[j]), int(system.placement.m[j]))
              for j in range(problem.n_servers)]
    print(f"  placement (server, first block, blocks): {placed}")
    return system


def prompts_for(cfg, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, cfg.vocab_size, size=n) for n in PROMPT_LENS]


def serve(system, prompts):
    """Every prompt through the continuous-batching scheduler; every one
    must come back served with ``n_new`` tokens."""
    from repro.serving import ContinuousBatchingScheduler

    sched = ContinuousBatchingScheduler(system, R=MAX_SESSIONS // 2)
    for rid, p in enumerate(prompts):
        sched.submit(rid, p, arrival=0.0, n_new=N_NEW)
    t0 = time.perf_counter()
    served = sched.run()
    wall = time.perf_counter() - t0
    bad = [(r.rid, r.fail_reason) for r in served
           if r.dropped or r.fail_reason is not None]
    require(not bad, f"requests failed: {bad}")
    require(len(served) == len(prompts), "requests lost")
    for r, p in zip(served, prompts):
        require(len(r.tokens) == len(p) + N_NEW, (r.rid, len(r.tokens)))
    print(f"  served {len(served)} requests ({[len(p) for p in prompts]} "
          f"prompt tokens, {N_NEW} new each), none failed; wall clock "
          f"{wall:.1f} s incl. compiles")
    return [np.asarray(r.tokens) for r in served]


def trace_logits(system, prompt, n_steps=N_CHECK_STEPS):
    """One request through the served path (bucketed prefill, fused decode
    rounds): its prefill logits and ``n_steps`` decode-step logits as f32
    rows, the tokens it chose, and wall-clock round times."""
    from repro.core import shortest_path_route

    route, _ = shortest_path_route(system.problem, system.alive_placement(),
                                   0)
    sid = system.create_session(prompt, 0, route, n_steps + 1)
    require(system.try_admit_sessions([sid]) == [sid], "not admitted")
    system.drain_prefill()
    sess = system.sessions[sid]
    logits = [np.asarray(sess.last_logits, np.float32)]
    times = []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        system.decode_round([sid])
        jax.block_until_ready([s.pool.tree for s in system.servers.values()])
        times.append(time.perf_counter() - t0)
        logits.append(np.asarray(sess.last_logits, np.float32))
    tokens = list(sess.tokens)
    print(f"  traced request: prompt {len(prompt)} tokens, route "
          f"{route.servers} x {route.blocks} blocks")
    require(len(set(route.servers)) == len(system.servers), route)
    system.retire_session(sid)
    return np.stack(logits), tokens, times


def report_rounds(tag, times):
    steady = float(np.median(times[1:]))
    print(f"  {tag}: first decode round {times[0]:.3f} s (wall clock, "
          f"compile + run); steady round {steady * 1e3:.2f} ms (wall clock, "
          f"median of {len(times) - 1}, block_until_ready)")


def reference_logits(cfg, params, tokens, prompt_len, n_steps=N_CHECK_STEPS):
    """The monolithic forward in float32 at highest matmul precision on the
    same weights, upcast, fed the engine's own tokens."""
    from repro.models import NULL_SH, decode_step, prefill

    cfg32 = cfg.replace(param_dtype="float32", act_dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    cache_len = prompt_len + n_steps + 1
    with jax.default_matmul_precision("highest"):
        pre = jax.jit(lambda p, t: prefill(p, cfg32, NULL_SH, {"tokens": t},
                                           cache_len=cache_len))
        dec = jax.jit(lambda p, c, t, pos: decode_step(p, cfg32, NULL_SH, c,
                                                       t, pos))
        lg, caches = pre(p32, jnp.asarray(tokens[:prompt_len])[None])
        out = [np.asarray(lg[0], np.float32)]
        for i in range(n_steps):
            pos = prompt_len + i
            lg, caches = dec(p32, caches, jnp.asarray([tokens[pos]]),
                             jnp.int32(pos))
            out.append(np.asarray(lg[0], np.float32))
    return np.stack(out)


def rel_err(got, want, vocab):
    """max |got - want| / max |want| over the real vocab, per row."""
    got, want = got[:, :vocab], want[:, :vocab]
    return np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)


def shared_rows(tokens_a, tokens_b, prompt_len):
    """Logit rows two traced runs can be compared on: the prefill row, and
    each decode step fed a token both runs chose.  With random weights two
    bf16 runs can break a near-tie differently, after which their inputs
    differ."""
    gen_a, gen_b = tokens_a[prompt_len:], tokens_b[prompt_len:]
    agree = next((i for i, (a, b) in enumerate(zip(gen_a, gen_b)) if a != b),
                 min(len(gen_a), len(gen_b)))
    return min(1 + agree, N_CHECK_STEPS + 1)


def check(tag, got, want, vocab, tol):
    err = rel_err(got, want, vocab)
    worst = f"{err[1:].max():.3e}" if len(err) > 1 else "n/a"
    print(f"  {tag}: max |diff| / max |ref| = {err.max():.3e} "
          f"(prefill {err[0]:.3e}, worst decode step {worst}; largest abs "
          f"diff {np.abs(got[:, :vocab] - want[:, :vocab]).max():.3e}; "
          f"bit-identical {np.array_equal(got, want)}; tolerance {tol})")
    require(np.isfinite(got).all(), f"{tag}: non-finite logits")
    require(err.max() <= tol, f"{tag}: {err.max():.3e} > {tol}")


def lowered_steps(system, bucket):
    """StableHLO of one server's pooled round step and of its row-indexed
    prefill step at ``bucket`` tokens, lowered on the server's live
    operands."""
    srv = next(iter(system.servers.values()))
    cfg = system.cfg
    N, W = srv.pool.n_rows, system._round_width
    act = jnp.dtype(cfg.act_dtype)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    i32 = jnp.int32
    mask = spec((srv.m, N), jnp.bool_)
    round_txt = srv._round_step.lower(
        srv.run_params, srv.shared, srv.pool.tree,
        spec((W, 1, cfg.d_model), act), spec((W,), i32), srv._dummy,
        spec((W,), i32), spec((N,), i32), spec((W,), i32), mask,
        srv.layer_ids).as_text()
    prefill_txt = srv._prefill_row.lower(
        srv.run_params, srv.shared, srv.pool.tree,
        spec((1, bucket, cfg.d_model), act), srv._dummy, srv._dummy,
        spec((), i32), spec((srv.m, 1), jnp.bool_), srv.layer_ids, 0,
        "all").as_text()
    return round_txt, prefill_txt


def peak_memory(devices):
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"  device {d.id}: peak_bytes_in_use "
              f"{peak if peak is None else f'{peak / 2**30:.2f} GiB'}")


def one_chip(cfg, seed):
    """The default path, then Pallas, each against the f32 reference."""
    from repro.kernels.runtime import XLA_FALLBACKS
    from repro.models import init_params

    params, _ = init_params(jax.random.PRNGKey(seed), cfg)
    problem = olmo_problem(cfg)
    prompts = prompts_for(cfg, seed)
    check_prompt = prompts[0]

    print("phase: serve, backend=xla")
    system = build(cfg, params, problem)
    xla_logits, tokens, times = trace_logits(system, check_prompt)
    report_rounds("xla", times)
    xla_tokens = serve(system, prompts)
    del system

    print("phase: serve, backend=pallas")
    XLA_FALLBACKS.clear()
    system = build(cfg, params, problem, backend="pallas")
    pl_logits, pl_tokens, times = trace_logits(system, check_prompt)
    report_rounds("pallas", times)
    pl_served = serve(system, prompts)
    round_txt, prefill_txt = lowered_steps(system, bucket=len(check_prompt))
    del system
    for tag, txt in (("round", round_txt), ("prefill", prefill_txt)):
        n = txt.count("tpu_custom_call")
        print(f"  pallas {tag} step: {n} tpu_custom_call site(s)")
        require(n > 0, f"pallas {tag} step lowered without a Mosaic kernel")
    print(f"  XLA fallbacks under backend=pallas: {dict(XLA_FALLBACKS)}")
    require(not XLA_FALLBACKS, dict(XLA_FALLBACKS))
    same = sum(np.array_equal(a, b) for a, b in zip(xla_tokens, pl_served))
    print(f"  pallas and xla emitted the same tokens for {same}/"
          f"{len(prompts)} requests")

    print("phase: check logits against the float32 reference")
    n = len(check_prompt)
    check("xla engine vs f32 reference", xla_logits,
          reference_logits(cfg, params, tokens, n), cfg.vocab_size, TOL)
    check("pallas engine vs f32 reference", pl_logits,
          reference_logits(cfg, params, pl_tokens, n), cfg.vocab_size,
          TOL)
    rows = shared_rows(pl_tokens, tokens, n)
    check(f"pallas vs xla engine ({rows} rows on shared tokens)",
          pl_logits[:rows], xla_logits[:rows], cfg.vocab_size, TOL)
    peak_memory(jax.devices()[:1])


def four_chips(cfg, seed):
    """Four one-chip servers on four chips against the same fleet on one
    chip; one server as a (2, 2) group against its solo twin."""
    from repro.launch.mesh import group_meshes
    from repro.models import init_params

    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    params, _ = init_params(jax.random.PRNGKey(seed), cfg)
    problem = olmo_problem(cfg)
    prompts = prompts_for(cfg, seed)

    print("phase: four servers on one chip")
    system = build(cfg, params, problem)
    one_logits, one_tokens, times = trace_logits(system, prompts[0])
    report_rounds("one chip", times)
    del system

    print("phase: four servers, one chip each")
    groups = group_meshes({j: (1, 1) for j in range(N_SERVERS)})
    system = build(cfg, params, problem, device_groups=groups)
    homes = []
    for j, srv in sorted(system.servers.items()):
        held = {d for x in jax.tree.leaves((srv.run_params, srv.pool.tree))
                for d in x.devices()}
        require(len(held) == 1, (j, held))
        homes.append(held.pop().id)
    print(f"  server -> device: {dict(zip(sorted(system.servers), homes))}")
    require(len(set(homes)) == N_SERVERS, homes)
    logits, tokens, times = trace_logits(system, prompts[0])
    report_rounds("four chips", times)
    del system
    rows = shared_rows(tokens, one_tokens, len(prompts[0]))
    check(f"four chips vs one chip ({rows} rows on shared tokens)",
          logits[:rows], one_logits[:rows], cfg.vocab_size, TOL)

    print("phase: one server as a (2, 2) device group vs its solo twin")
    solo_problem = olmo_problem(cfg, n_servers=1)
    solo = build(cfg, params, solo_problem)
    solo_logits, solo_tokens, _ = trace_logits(solo, prompts[0])
    del solo
    grouped = build(cfg, params, solo_problem,
                    device_groups=group_meshes({0: (2, 2)}))
    srv = grouped.servers[0]
    spread = {d.id for x in jax.tree.leaves(srv.run_params)
              for d in x.devices()}
    print(f"  grouped server params span devices {sorted(spread)}")
    require(len(spread) == 4, spread)
    g_logits, g_tokens, times = trace_logits(grouped, prompts[0])
    report_rounds("(2, 2) group", times)
    rows = shared_rows(g_tokens, solo_tokens, len(prompts[0]))
    check(f"(2, 2) group vs solo twin ({rows} rows on shared tokens)",
          g_logits[:rows], solo_logits[:rows], cfg.vocab_size, TOL)
    del grouped
    peak_memory(devs[:4])


def compile_listener():
    """Sum of backend compile seconds and persistent-cache hits, from
    jax.monitoring."""
    totals = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return totals


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    device = device_report()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    totals = compile_listener()
    cfg = get_config("olmo_1b")
    print(f"model: {cfg.name} at published widths: {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.param_dtype}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(cfg, args.seed)
        device["count"] = 4
    else:
        one_chip(cfg, args.seed)
    print(f"wall clock {time.perf_counter() - t0:.1f} s; backend compile "
          f"{totals['compile_s']:.1f} s; persistent cache hits "
          f"{totals['cache_hits']}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
