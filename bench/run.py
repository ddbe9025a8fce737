"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Set-up makes the weights from the
seed on the chip, builds the deployment and runs every shape the traffic
will use; then the window serves the traffic on the wall clock for
``--seconds`` (``bench.loop``) and drains what is in flight, keeping the
logits of the requests the check sampled.  Once the program's state is
freed, those logits and the served tokens are checked against the plain
reference (``bench.check``).  With ``--trace 1`` the window is traced and
the per-layer metrics are printed in place of the end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared beside its limit); the last lines of
stderr repeat the checks.  On a host without a TPU, or with fewer chips
than the cell asks for, it exits 1 and prints no result.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where
that is set, else ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

DRAIN_S = 60.0
TRACE_S = 10.0  # seconds of the window a traced run traces


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(bench: dict, section: str, cell: str) -> list:
    """The entries of ``section`` that the cell reports."""
    return [e for e in bench[section]
            if cell in e.get("workloads", [cell])]


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s); nothing was run",
              file=sys.stderr)
        sys.exit(1)


def use_compile_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # every program, however quick to compile, so a warm run compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileLog:
    """Backend compile events and persistent-cache hits, with their times,
    from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = []  # (perf_counter, seconds)
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.perf_counter(), secs))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)


def device_block(devices) -> dict:
    import jax

    all_devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": all_devs[0].platform,
            "kind": all_devs[0].device_kind, "count": len(all_devs),
            "memory_peak_bytes": int(peak)}


def bench_anchor(x):
    """The program whose runs put a trace on the loop's clock
    (``bench.trace.align``)."""
    return x + 1


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, cfg: dict = None, mix: dict = None, log=print):
    """One run of one cell; returns (result, check lines, the loop's
    record of the window, the sampled requests that finished).  ``cfg``
    and ``mix`` default to the cell's files."""
    import jax
    import numpy as np

    from bench import check, deploy, loop, readers, stats, traffic
    from bench import trace as trace_mod
    from bench.peaks import peaks

    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = cfg or deploy.load_config(cell["config"])
    mix = mix or traffic.load_mix(cell["traffic"])
    m, dep = cfg["model"], cfg["deployment"]
    ref = check.reference_module(cfg)
    clog = CompileLog()
    # a chip's peaks, or an error for a chip not in the table; none on the
    # CPU, where no device metric is read
    d0 = jax.devices()[0]
    pk = peaks(d0.device_kind) if d0.platform == "tpu" else None

    params = ref.make_params(m, seed)
    jax.block_until_ready(params)
    requests = traffic.make_requests(mix, seed, seconds, m["vocab_size"])
    system, ctl = deploy.build(cfg, mix, params)
    log(f"deployment: server -> device ids {deploy.server_devices(system)}")
    warm = deploy.warm_up(system, requests, dep["rows"])
    anchor = jax.jit(bench_anchor)
    one = jax.block_until_ready(anchor(jax.numpy.zeros((), "int32")))
    watch = check.choose(requests, seed)
    used = sorted({d for s in system.servers.values()
                   for x in jax.tree.leaves(s.pool.tree)
                   for d in x.devices()} | {jax.devices()[0]},
                  key=lambda d: d.id)
    log(f"warm-up: {warm}; {len(clog.compiles)} backend compiles "
        f"({sum(s for _, s in clog.compiles):.1f} s), "
        f"{clog.cache_hits} persistent-cache hits")
    tdir, marks, brackets = None, (), []
    if trace:
        # a stretch of the steady window, not all of it: a trace of the
        # whole window is large, and stopping it stalls the loop
        tdir = tempfile.mkdtemp(prefix="bench_trace_")

        def run_anchor():
            t0 = time.perf_counter()
            jax.block_until_ready(anchor(one))
            brackets.append((t0, time.perf_counter()))

        def start():
            opts = jax.profiler.ProfileOptions()
            # Python's own tracer slowed the loop by seconds at each stop
            opts.python_tracer_level = 0
            t0 = time.perf_counter()
            jax.profiler.start_trace(tdir, profiler_options=opts)
            log(f"trace: start_trace took {time.perf_counter() - t0:.3f} s")
            run_anchor()

        def stop():
            run_anchor()
            jax.profiler.stop_trace()

        t_start = seconds / 3
        marks = ((t_start, start), (min(seconds, t_start + TRACE_S), stop))
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    lr = loop.drive(system, ctl, requests, mix, seconds, DRAIN_S, marks,
                    watch)
    in_window = clog.between(t_window, t_window + lr.window_closed)
    device = device_block(used)
    log("memory peak per device (bytes): " + str({
        d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in used}))
    late = np.asarray(lr.lateness or [0.0])
    log(f"window: {len(lr.records)} requests, {len(lr.rounds)} decode "
        f"rounds, {len(lr.prefills)} prefill rounds; compiles inside the "
        f"window: {in_window}; generator lateness p50 "
        f"{np.percentile(late, 50) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms; engine counters {system.round_stats}")
    del system, ctl, params
    gc.collect()

    recs = check.sampled(lr.records, watch)
    l_in, l_out = deploy.mix_bounds(mix)
    correct, checks, lines = check.check_run(ref, m, seed, recs,
                                             l_in + l_out, l_out,
                                             cfg["check"])

    failed = sum(1 for r in lr.records if not r.done)
    metrics = {}
    if trace:
        xp = sorted(glob.glob(f"{tdir}/plugins/profile/*/*.xplane.pb"))
        dt = trace_mod.load(xp[-1], lr.spans,
                            [(a - lr.t0, b - lr.t0) for a, b in brackets])
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: stretch {dt.stretch[0]:.3f}-{dt.stretch[1]:.3f} s of "
            f"the window; anchor brackets "
            f"{[round((b - a) * 1e3, 3) for a, b in brackets]} ms; the "
            f"second anchor lies {dt.skew_s * 1e3:.3f} ms from where the "
            f"first puts it")
        view = readers.RunView(loop=lr, window=dt.stretch, model=m,
                               deployment=dep, peaks=pk, trace=dt,
                               arch=ref, wan=cfg["wan"])
        for e in cell_metrics(bench, "per_layer", workload):
            v = readers.reader(e["name"])(view)
            if v is not None:
                metrics[e["name"]] = {"value": v, "unit": e["unit"]}
        device.update(busy_s=dt.busy_s(), window_s=dt.window_s)
        breakdown = {"device_ops": dt.top_ops(), "idle_gaps":
                     dt.idle_by_host_span()}
    else:
        names = [e["name"] for e in cell_metrics(bench, "end_to_end",
                                                 workload)]
        units = {e["name"]: e["unit"] for e in bench["end_to_end"]}
        for name, (v, _) in stats.end_to_end(names, lr.records, cfg["wan"],
                                             seconds).items():
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
        if "setup_s" in names:
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result = {"correct": correct, "attempted": len(lr.records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, lines, lr, recs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        ap.error(f"no workload {args.workload!r} in BENCHMARK.json")
    require_chips(int(cell["chips"]))
    use_compile_cache()
    result, lines, _, _ = run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        log=lambda s: print(s, file=sys.stderr, flush=True))
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
