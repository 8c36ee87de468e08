#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

  python3 bench/run.py --workload mul8_paper.fig_grid --seed 7 \\
      --seconds 30 --trace 0

Set-up builds the cell's search (``bench/configs``) and runs one warm-up
sweep of one chunk per Gauss sigma at the cell's own shapes (it compiles,
or loads from the persistent cache in ``.jax_cache``, every program the
window calls).  The window runs whole sweeps of the cell's traffic
(``bench/traffic``) through ``repro.core.sweep.run_sweep_batched`` with the
Pallas backend and the program's default sweep settings (or the mix's),
streaming result shards to disk: a fixed number of chunks, the
configuration's ``window_chunks`` for a window of the benchmark's
``run_seconds``, in proportion to ``--seconds``.  Then the shards are
checked against the plain reference (``check.py``).

``--trace 0`` reports the end-to-end metrics: ``evals_per_s`` (candidate
evaluations of the window's runs over its wall time) and ``setup_s``
(process start to the window's start).  ``--trace 1`` traces a window of
``TRACE_CHUNKS`` chunks with the JAX profiler and reports the per-layer
metrics (``bench/layer_metrics``) with the device's busy time.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``compared``: each number of the check with its limit); the
numbers compared are also the last lines on standard error.  The run exits
with code 3, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, and with code 2 where the program is missing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# run as a script, import the bench package from the root instead of its
# modules from their directory (bench/trace.py is no stdlib trace)
if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
    sys.path[0] = ROOT

from bench import cells, check, trace, traffic, work  # noqa: E402
from bench import reference as R  # noqa: E402

SRC = os.path.join(ROOT, "src")
WORK_DIR = os.path.join(ROOT, "experiments", "tmp", "bench")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")
#: whole chunks in a traced window
TRACE_CHUNKS = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Compilations:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, d, **kw: self._event(e))

    def _event(self, event: str, **kw) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1


class Program:
    """The system under test, driven the way a TPU user drives a sweep."""

    def __init__(self, config: dict, settings: dict):
        from repro.core.evolve import EvolveConfig
        from repro.core.search import SearchConfig
        from repro.core.sweep import SweepConfig

        self.cfg = SearchConfig(
            width=config["width"], kind=config["kind"], n_n=config["n_n"],
            evolve=EvolveConfig(
                generations=config["generations"], lam=config["lam"],
                mutation_rate=config["mutation_rate"], backend="pallas",
                eval_mode=config["eval_mode"]))
        self.settings = dict(settings)
        self.chunk_size = SweepConfig(**self.settings).chunk_size

    def sweep(self, sw: traffic.Sweep, results_dir: str) -> float:
        """One whole sweep into a fresh results directory; wall seconds."""
        import jax
        from repro.core.fitness import ConstraintSpec
        from repro.core.sweep import SweepConfig, run_sweep_batched

        cons = [ConstraintSpec(**c) for c in sw.constraints]
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.sweep"):
            run_sweep_batched(self.cfg, cons, list(sw.seeds), SweepConfig(
                results_dir=results_dir, keep_history="summary",
                **self.settings))
        return time.perf_counter() - t


class Run:
    """What a per-layer reader reads: the reduced trace, its window, and
    the window's counts."""

    def __init__(self, reduced, window, counters, busy_s):
        self.trace = reduced
        self.window = window
        self.counters = counters
        self.busy_s = busy_s
        self.window_s = window.seconds

    def in_window(self, events):
        return self.trace.within(events, self.window)


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def traced_metrics(cell, log_dir, counters):
    """(per-layer metrics, busy_s, window_s, breakdown) of a trace."""
    red = trace.reduce(trace.find_xplane(log_dir))
    window = red.host_span("bench.window")
    if window is None:
        raise RuntimeError("the trace holds no bench.window span")
    busy = trace.busy_seconds(red, window)
    run = Run(red, window, counters, busy)
    metrics = {}
    for meta, read in cell.per_layer:
        value = read(run)
        if value is not None:
            metrics[meta["name"]] = {"value": float(value),
                                     "unit": meta["unit"]}
    gaps = sorted(trace.idle_gaps(red, window), key=lambda g: g[0] - g[1])
    breakdown = {
        "device_ops": trace.top_ops(red, window),
        "idle_gaps": [[trace.host_doing(red, s, e, ("bench.window",)),
                       (e - s) * 1e-9] for s, e in gaps[:10]],
    }
    return metrics, busy, window.seconds, breakdown


def measure(cell: cells.Cell, seed: int, seconds: float, traced: bool,
            device: dict, work_dir: str) -> tuple[dict, dict]:
    """Set-up, window and check of one run: the result object, and the
    check's details."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    compiles = Compilations()
    c = cell.config
    prog = Program(c, cell.mix.get("sweep", {}))
    chunk = prog.chunk_size
    dirs = (os.path.join(work_dir, f"sweep{i}") for i in itertools.count())

    warm = traffic.warmup(cell.mix, seed, chunk)
    t_warm = prog.sweep(warm, next(dirs))
    log(f"warm-up sweep of {len(warm.constraints)} chunk(s): {t_warm:.3f} s, "
        f"{compiles.n} compilations")
    n_chunks = cell.window_chunks(seconds)
    if traced:
        n_chunks = min(n_chunks, TRACE_CHUNKS)
    plan = traffic.sweeps(cell.mix, seed, n_chunks, chunk)
    out_dirs = [next(dirs) for _ in plan]
    log_dir = os.path.join(work_dir, "trace")

    c0 = compiles.n
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for sw, d in zip(plan, out_dirs):
            prog.sweep(sw, d)
    window_s = time.perf_counter() - t0
    if traced:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace written in {time.perf_counter() - t_stop:.1f} s")
    in_window = compiles.n - c0
    runs = sum(sw.runs for sw in plan)
    evals = work.candidate_evaluations(runs, c["lam"], c["generations"])
    log(f"window: {n_chunks} chunks in {len(plan)} sweep(s), {runs} runs, "
        f"{evals} candidate evaluations in {window_s:.3f} s")
    log(f"compilations in the window: {in_window}")
    device = dict(device, memory_peak_bytes=memory_peak())
    del prog
    gc.collect()

    prob = R.Problem(c["kind"], c["width"], c["n_n"])
    t_check = time.perf_counter()
    nums, info = check.compare(prob, c["generations"], c["lam"],
                               c["mutation_rate"], plan, out_dirs, seed)
    log(f"check: {json.dumps(info)} in {time.perf_counter() - t_check:.1f} s")
    limits = c["limits"]
    result = {"correct": check.verdict(nums, limits), "attempted": runs,
              "failed": info["missing"]}
    if traced:
        counters = {"chunks": n_chunks, "generations": c["generations"],
                    "runs": runs, "lam": c["lam"], "n_n": c["n_n"],
                    "n_inputs": 1 << c["n_i"], "evaluations": evals}
        t_read = time.perf_counter()
        metrics, busy_s, traced_s, breakdown = traced_metrics(
            cell, log_dir, counters)
        log(f"trace read in {time.perf_counter() - t_read:.1f} s")
        device.update(busy_s=busy_s, window_s=traced_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = {"evals_per_s": evals / window_s, "setup_s": setup_s}
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}, device=device)
    result["compared"] = {k: {"value": nums[k], "limit": limits[k]}
                          for k in check.NUMBERS}
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"[bench] no program under {SRC}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {json.dumps(device)}")
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"[bench] needs {cell.chips} TPU chip(s), found "
              f"{json.dumps(device)}; no result", file=sys.stderr)
        return 3

    sys.path.insert(0, SRC)
    work_dir = os.path.join(WORK_DIR, str(os.getpid()))
    shutil.rmtree(work_dir, ignore_errors=True)
    try:
        result, info = measure(cell, args.seed, args.seconds,
                               bool(args.trace), device, work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    print("[bench] replay: " + json.dumps(
        {k: info[k] for k in ("ambiguous", "branches", "steered",
                              "open_bests", "golden_above")}),
        file=sys.stderr)
    for name, v in result["compared"].items():
        print(f"[bench] compared {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
