"""Kernel microbenchmarks: fused sim+metrics throughput (the paper's hot
loop), the unfused baseline, the batched constraint-grid sweep engine
vs the serial per-run loop (with a backend axis), and the streaming
results layer (shard spill + read-back rows/s), on this host (CPU: jnp
path; the Pallas kernel is timed in interpret mode only for reference — its
target is TPU).

Script / module mode (CWD-independent):
  python -m benchmarks.kernel_micro \
      [--only eval,gen,pallas,sweep,results,certify,lut]
      [--backend jnp,pallas] [--smoke] [--json BENCH_out.json]

``--smoke`` shrinks every budget to the CI bench-gate size (the
``bench-smoke`` job / ``make bench-check``); ``--json`` writes the metric
dict consumed by ``tools/check_bench.py``.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import golden as G, metrics as M, simulate as S
from repro.core.genome import random_genome
from repro.kernels import ops, ref


def _time(fn, *args, reps=5) -> float:
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def bench_eval_throughput(width: int = 8, lam: int = 8):
    """Candidate-evaluations/s: fused (single pass, what the TPU kernel
    does) vs unfused (sim -> unpack -> 7 metric passes)."""
    gold, spec = G.array_multiplier(width, n_n=400)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(width, "mul"))
    genomes = jax.vmap(lambda k: random_genome(k, spec))(
        jax.random.split(jax.random.PRNGKey(0), lam))

    @jax.jit
    def fused(gs):
        return jax.vmap(
            lambda g: ref.cgp_eval_ref(g, spec, planes, gvals, 256.0))(gs)

    @jax.jit
    def unfused(gs):
        def one(g):
            vals = S.simulate_values(g, spec, planes)       # pass 1
            met = M.metrics_from_values(gvals, vals, spec.n_o)  # pass 2
            wires = S.simulate_planes(g, spec, planes)      # re-sim for p
            p = S.signal_probabilities(wires[spec.n_i:])
            return met, p
        return jax.vmap(one)(gs)

    t_f = _time(fused, genomes)
    t_u = _time(unfused, genomes)
    evals = lam
    return {
        "fused_us_per_eval": 1e6 * t_f / evals,
        "unfused_us_per_eval": 1e6 * t_u / evals,
        "fused_speedup": t_u / t_f,
        "inputs_per_s_fused": evals * spec.n_inputs_total / t_f,
    }


def bench_pallas_interpret(width: int = 6):
    """Interpret-mode cost of the Pallas kernel (correctness path only —
    the performance target is the TPU lowering)."""
    gold, spec = G.array_multiplier(width, n_n=250)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(width, "mul"))
    t_k = _time(lambda: ops.cgp_eval(gold, spec, planes, gvals), reps=3)
    t_r = _time(lambda: ref.cgp_eval_ref(gold, spec, planes, gvals, 256.0),
                reps=3)
    return {"pallas_interpret_ms": 1e3 * t_k, "jnp_ref_ms": 1e3 * t_r}


def bench_generation_rate(width: int = 8, gens: int = 100, lam: int = 8,
                          n_n: int = 400):
    """End-to-end (1+λ) generations/s — the paper's search-speed metric."""
    from repro.core.evolve import EvolveConfig, evolve
    from repro.core.fitness import ConstraintSpec
    from repro.core.search import SearchConfig, problem_arrays
    cfg = SearchConfig(width=width, n_n=n_n,
                       evolve=EvolveConfig(generations=gens, lam=lam))
    gold, spec, planes, gvals, gpower = problem_arrays(cfg)
    thr = jnp.asarray(ConstraintSpec(mae=1.0).thresholds())

    def run(seed):
        return evolve(spec, cfg.evolve, gold, thr, planes, gvals, gpower,
                      jax.random.PRNGKey(seed)).best_fit

    run(0)  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(run(1))
    dt = time.perf_counter() - t0
    return {"generations_per_s": gens / dt,
            "evals_per_s": gens * lam / dt,
            "exhaustive_inputs_per_s": gens * lam * spec.n_inputs_total / dt}


def bench_sweep(width: int = 3, gens: int = 200, lam: int = 4,
                n_seeds: int = 2, backends: tuple = ("jnp", "pallas"),
                dedup_width: int = 6, dedup_gens: int = 60,
                dedup_n_n: int = 300, dedup_mutation_rate: float = 0.0005,
                sampled_width: int = 12, sampled_gens: int = 20,
                sampled_size: int = 1 << 13):
    """Constraint-grid throughput (runs/s): batched engine vs serial loop,
    with a ``backend`` axis over the candidate-evaluation path.

    The grid is 6 constraint configs × ``n_seeds`` seeds; all paths are
    compiled before timing, so the ratios isolate execution throughput (the
    batched engine additionally saves one trace per seed on the cold path).
    The "pallas" legs drive the fused (runs × λ) kernel — on CPU it runs in
    interpret mode, so their runs/s are correctness-path references; the
    jnp-vs-pallas gap worth tracking is on a TPU backend.

    The ``dedup_*`` legs time the phenotype-dedup cache (DESIGN.md §8) on a
    deliberately neutral-mutation-heavy grid — wide cube, big genome, low
    mutation rate, the regime where most offspring share an active subgraph
    with their parent and the cache's skipped kernel dispatches dominate its
    host-side hashing cost.  Emits cached vs uncached effective runs/s and
    the measured cache hit rate.

    The ``sampled_*`` leg times ``eval_mode="sampled"`` (DESIGN.md §9) past
    the exhaustive wall: a width-``sampled_width`` multiplier grid whose
    2^(2w) cube (16.7M rows at width 12) no evolve loop could afford,
    evaluated on a ``sampled_size``-row uniform sample instead.  Runs on the
    jnp backend (the CPU's; the Pallas kernel is exact there too, DESIGN.md
    §9).  Emits ``sampled_runs_per_s``, the key the bench gate tracks.

    The ``async_commit`` leg times the same grid streamed through the
    results layer chunk-by-chunk (history kept, so the shard payload is
    real), sync vs ``async_commit=True`` (DESIGN.md §11) into fresh temp
    dirs.  ``async_commit_speedup`` is the ratio the gate tracks; on a
    CPU-bound smoke box the overlap window is thin, so ~1.0 is expected —
    the key mostly guards against the committer *adding* overhead.
    """
    import dataclasses

    from repro.core.evolve import EvolveConfig
    from repro.core.fitness import ConstraintSpec
    from repro.core.search import SearchConfig, run_search, run_sweep_serial
    from repro.core.sweep import SweepConfig, run_sweep_batched

    cfg = SearchConfig(width=width, n_n=100,
                       evolve=EvolveConfig(generations=gens, lam=lam))
    cons = ([ConstraintSpec(mae=t) for t in (0.3, 0.6, 1.0, 2.0)]
            + [ConstraintSpec(er=e) for e in (30.0, 60.0)])
    seeds = tuple(range(n_seeds))
    n_runs = len(cons) * len(seeds)
    sweep = SweepConfig(chunk_size=n_runs, keep_history=False)

    run_search(cfg, cons[0], 0)                       # compile serial path
    t0 = time.perf_counter()
    run_sweep_serial(cfg, cons, seeds)
    t_serial = time.perf_counter() - t0
    out = {"n_runs": n_runs, "serial_runs_per_s": n_runs / t_serial}

    for backend in backends:
        cfg_b = dataclasses.replace(
            cfg, evolve=dataclasses.replace(cfg.evolve, backend=backend))
        run_sweep_batched(cfg_b, cons, seeds, sweep)  # compile
        t0 = time.perf_counter()
        run_sweep_batched(cfg_b, cons, seeds, sweep)
        t_b = time.perf_counter() - t0
        out[f"batched_{backend}_runs_per_s"] = n_runs / t_b
        out[f"batched_{backend}_speedup"] = t_serial / t_b

    # --- phenotype-dedup legs (DESIGN.md §8): neutral-mutation-heavy grid --
    dcfg = SearchConfig(
        width=dedup_width, n_n=dedup_n_n,
        evolve=EvolveConfig(generations=dedup_gens, lam=lam,
                            mutation_rate=dedup_mutation_rate,
                            backend=backends[0]))
    dcons = cons[:4]  # one σ group (shared default σ): one trace per leg
    dn = len(dcons) * len(seeds)
    for tag, on in (("dedup_off", False), ("dedup", True)):
        sw = SweepConfig(chunk_size=dn, keep_history=False, dedup=on)
        run_sweep_batched(dcfg, dcons, seeds, sw)  # compile
        t0 = time.perf_counter()
        res = run_sweep_batched(dcfg, dcons, seeds, sw)
        t_d = time.perf_counter() - t0
        out[f"{tag}_runs_per_s"] = dn / t_d
        if on:
            out["dedup_speedup"] = (out["dedup_runs_per_s"]
                                    / out["dedup_off_runs_per_s"])
            out["dedup_hit_rate"] = res.dedup_stats["hit_rate"]

    # --- sampled-eval leg (DESIGN.md §9): width past the exhaustive wall --
    _, spec_s = G.array_multiplier(sampled_width, n_n=None)  # auto-sized
    scfg = SearchConfig(
        width=sampled_width, kind="mul", n_n=spec_s.n_n,
        evolve=EvolveConfig(generations=sampled_gens, lam=lam,
                            eval_mode="sampled", sample_size=sampled_size,
                            input_dist="uniform"))
    scons = cons[:2]  # one σ group: one trace for the leg
    sn = len(scons) * len(seeds)
    ssw = SweepConfig(chunk_size=sn, keep_history=False)
    run_sweep_batched(scfg, scons, seeds, ssw)  # compile
    t0 = time.perf_counter()
    run_sweep_batched(scfg, scons, seeds, ssw)
    t_s = time.perf_counter() - t0
    out["sampled_runs_per_s"] = sn / t_s
    out["sampled_inputs_per_s"] = (sn * sampled_gens * lam
                                   * sampled_size / t_s)

    # --- async-commit leg (DESIGN.md §11): overlap shard commits with the
    # next chunk's evaluation; fresh temp dir per timed run so every commit
    # is a real write (a reused dir would skip committed spans on resume)
    import shutil
    import tempfile

    acfg = dataclasses.replace(
        cfg, evolve=dataclasses.replace(cfg.evolve, backend=backends[0]))

    def one_commit_run(async_on):
        d = tempfile.mkdtemp(prefix="bench_async_commit_")
        try:
            sw = SweepConfig(chunk_size=max(2, n_runs // 4),
                             keep_history=True, results_dir=d,
                             async_commit=async_on)
            t0 = time.perf_counter()
            run_sweep_batched(acfg, cons, seeds, sw)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(d, ignore_errors=True)

    one_commit_run(False)  # compile the chunked trace
    t_syncc = one_commit_run(False)
    t_asyncc = one_commit_run(True)
    out["async_commit_runs_per_s"] = n_runs / t_asyncc
    out["async_commit_speedup"] = t_syncc / t_asyncc
    return out


def bench_results(n_runs: int = 2048, gens: int = 256, chunk: int = 128,
                  n_n: int = 100, n_o: int = 8):
    """Streaming results layer: shard spill and read-back rows/s.

    Synthetic run-major buffers at realistic shapes (the per-row payload is
    dominated by ``hist_metrics``: gens × N_METRICS floats) are committed
    chunk-by-chunk through ``SweepResultWriter`` and drained back through
    ``SweepResultReader`` — the host-side path that bounds paper-scale grids
    now that the fused kernel owns the evaluation side.  "summary" read-back
    is the figure-pipeline path (correlations + fronts from grid-order
    summary columns); "history" read-back drains every history shard at
    one-chunk peak memory.
    """
    import tempfile

    from repro.core import metrics as M
    from repro.core.results import SweepResultReader, SweepResultWriter

    rng = np.random.default_rng(0)
    rows_all = {
        "grid_rows": np.arange(n_runs, dtype=np.int32),
        "thresholds": rng.random((n_runs, M.N_METRICS), np.float32),
        "parent_nodes": rng.integers(0, 99, (n_runs, n_n, 3), np.int32),
        "parent_outs": rng.integers(0, 99, (n_runs, n_o), np.int32),
        "best_nodes": rng.integers(0, 99, (n_runs, n_n, 3), np.int32),
        "best_outs": rng.integers(0, 99, (n_runs, n_o), np.int32),
        "best_fit": rng.random(n_runs, np.float32),
        "metrics": rng.random((n_runs, M.N_METRICS), np.float32),
        "metrics_stderr": rng.random((n_runs, M.N_METRICS), np.float32),
        "power_rel": rng.random(n_runs, np.float32),
        "feasible": rng.integers(0, 2, n_runs, np.uint8),
        "certified_mask": rng.integers(0, 2, n_runs, np.uint8),
        "error_mean": rng.random(n_runs, np.float32),
        "error_std": rng.random(n_runs, np.float32),
        "hist_power_rel": rng.random((n_runs, gens), np.float32),
        "hist_fit": rng.random((n_runs, gens), np.float32),
        "hist_metrics": rng.random((n_runs, gens, M.N_METRICS), np.float32),
    }
    grid_meta = [{"constraint": f"mae<={i % 7}%", "seed": i,
                  "gauss_sigma": 256.0} for i in range(n_runs)]
    with tempfile.TemporaryDirectory() as d:
        writer = SweepResultWriter(
            d, grid_fingerprint="bench", grid_meta=grid_meta, n_runs=n_runs,
            gens=gens, n_n=n_n, n_o=n_o, keep_history="summary",
            chunk_size=chunk)
        t0 = time.perf_counter()
        for start in range(0, n_runs, chunk):
            end = min(start + chunk, n_runs)
            writer.write_chunk(
                (start, end),
                {k: v[start:end] for k, v in rows_all.items()})
        t_spill = time.perf_counter() - t0

        reader = SweepResultReader(d)
        t0 = time.perf_counter()
        reader.correlations()
        reader.fronts()
        t_summary = time.perf_counter() - t0
        t0 = time.perf_counter()
        drained = 0
        for rows, hist in reader.iter_history():
            drained += hist["hist_metrics"].shape[0]
        t_hist = time.perf_counter() - t0
        assert drained == n_runs

    row_bytes = sum(v.nbytes for v in rows_all.values()) / n_runs
    return {
        "spill_rows_per_s": n_runs / t_spill,
        "spill_mb_per_s": n_runs * row_bytes / t_spill / 2**20,
        "summary_readback_rows_per_s": n_runs / t_summary,
        "history_readback_rows_per_s": n_runs / t_hist,
        "row_kb": row_bytes / 1024,
    }


def bench_certify(width: int = 8, n_elites: int = 6, rate: float = 0.02,
                  chunk_rows: int = 8192):
    """Exact-verification escalation throughput (DESIGN.md §10).

    Times ``certify.certified_metrics`` over mutated elites of the exact
    golden netlist — the per-elite cost the sweep's escalation driver pays
    when a sampled-feasible candidate is promoted to the exact tier.  Both
    regimes at the same width so the numbers are comparable: the full-cube
    dispatch (one jit'd pass over the whole 2^(2w) cube) and the chunked
    bit-parallel pass forced via a small ``dispatch_rows`` budget (the
    large-width path).
    """
    from repro.core import certify
    from repro.core.mutate import mutate_population

    gold, spec = G.array_multiplier(width, n_n=None)
    pop = mutate_population(jax.random.PRNGKey(0), gold, spec, n_elites,
                            rate)
    nodes, outs = np.asarray(pop.nodes), np.asarray(pop.outs)

    def run_all(dispatch_rows):
        t0 = time.perf_counter()
        for i in range(n_elites):
            certify.certified_metrics(nodes[i], outs[i], spec, "mul", width,
                                      256.0, dispatch_rows=dispatch_rows)
        return time.perf_counter() - t0

    for rows in (certify.DISPATCH_ROWS, chunk_rows):
        certify.certified_metrics(nodes[0], outs[0], spec, "mul", width,
                                  256.0, dispatch_rows=rows)  # compile
    t_full = run_all(certify.DISPATCH_ROWS)
    t_chunked = run_all(chunk_rows)
    return {
        "certify_escalations_per_s": n_elites / t_full,
        "certify_rows_per_s": n_elites * (1 << spec.n_i) / t_full,
        "certify_chunked_escalations_per_s": n_elites / t_chunked,
    }


def bench_lut(m: int = 256, n: int = 256, k: int = 256,
              serve_requests: int = 3, serve_prompt: int = 16,
              serve_gen: int = 8, reps: int = 3):
    """The deployment bridge (DESIGN.md §12): LUT-matmul + approx serving.

    ``lut_matmul_gops`` times the padded Pallas kernel path
    (``kernels.ops.lut_matmul``; interpret mode on CPU — like the ``pallas``
    leg, a reference number, not the TPU story) and ``lut_ref_gops_info``
    the jnp gather oracle (what CPU serving actually dispatches to); both
    count 2·M·N·K ops.  The oracle key carries the ``_info`` suffix so
    ``check_bench`` reports it without gating it: XLA's CPU gather timing
    swings several-× with machine state, and the serving path it feeds is
    already gated end to end by ``serve_approx_tokens_per_s`` — the
    continuous-batching serve loop on a reduced arch with every projection
    matmul routed through an approximate LUT.
    """
    rng = np.random.default_rng(0)
    lut = (np.arange(256)[:, None] * np.arange(256)[None, :]
           + rng.integers(-2, 3, (256, 256))).astype(np.int32)  # approx LUT
    a = jnp.asarray(rng.integers(0, 256, (m, k)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 256, (k, n)), jnp.int32)
    lj = jnp.asarray(lut)
    gops = 2.0 * m * n * k / 1e9
    t_kernel = _time(lambda: ops.lut_matmul(a, b, lj), reps=reps)
    t_ref = _time(lambda: ref.lut_matmul_ref(a, b, lj), reps=reps)

    from repro.launch.serve import serve
    sv = serve("llama3_2_1b", n_requests=serve_requests,
               prompt_len=serve_prompt, gen_len=serve_gen, slots=2,
               reduced=True, approx_lut=lut)
    return {
        "lut_matmul_gops": gops / t_kernel,
        "lut_ref_gops_info": gops / t_ref,
        "serve_approx_tokens_per_s": sv["tok_per_s"],
    }


# --smoke budget overrides per bench: the CI bench-gate size (seconds, not
# minutes, per bench; small enough for every push, big enough to time)
SMOKE = {
    "eval": dict(width=6, lam=4),
    "gen": dict(width=6, gens=40, lam=4, n_n=200),
    "pallas": dict(width=5),
    "sweep": dict(width=2, gens=100, n_seeds=1,
                  dedup_width=6, dedup_gens=30, dedup_n_n=300,
                  sampled_gens=5, sampled_size=2048),
    "results": dict(n_runs=512, gens=128, chunk=64),
    "certify": dict(width=6, n_elites=4, chunk_rows=1024),
    "lut": dict(m=128, n=128, k=128, serve_requests=2, serve_prompt=8,
                serve_gen=4, reps=6),
}


def main(argv=None):
    import argparse
    import functools
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: "
                         "eval,gen,pallas,sweep,results,certify,lut")
    ap.add_argument("--backend", default="jnp,pallas",
                    help="comma list of sweep-engine backends to time "
                         "(--only sweep axis; default: jnp,pallas)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI budget for every bench (the bench-smoke "
                         "job / make bench-check)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (the BENCH_<sha>.json "
                         "consumed by tools/check_bench.py)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    backends = tuple(b for b in args.backend.split(",") if b)
    if unknown := set(backends) - {"jnp", "pallas"}:
        ap.error(f"unknown backend(s): {sorted(unknown)}")
    benches = {"eval": bench_eval_throughput, "gen": bench_generation_rate,
               "pallas": bench_pallas_interpret,
               "sweep": functools.partial(bench_sweep, backends=backends),
               "results": bench_results,
               "certify": bench_certify,
               "lut": bench_lut}
    if only is not None and (unknown := only - set(benches)):
        ap.error(f"unknown bench name(s): {sorted(unknown)} "
                 f"(choose from {sorted(benches)})")
    results = {}
    for name, fn in benches.items():
        if only is not None and name not in only:
            continue
        if args.smoke:
            fn = functools.partial(fn, **SMOKE[name])
        out = fn()
        results[name] = out
        parts = ", ".join(f"{k}={v:.4g}" for k, v in out.items())
        print(f"[{name}] {parts}", flush=True)
    if args.json:
        results["_meta"] = {"smoke": args.smoke, "backends": list(backends)}
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"[json] -> {args.json}", flush=True)


if __name__ == "__main__":
    main()
