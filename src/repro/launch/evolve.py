"""CGP approximation launcher — the paper's experiment as a CLI.

  PYTHONPATH=src python -m repro.launch.evolve --width 8 \
      --constraint "mae=0.5,er=60" --generations 2000 --seeds 3 \
      --out experiments/lib/mae05_er60.json

Multi-host / pod-sharded mode (DESIGN.md §6): launch the SAME command once
per pod with a shared --results-dir and the pod count —

  PYTHONPATH=src python -m repro.launch.evolve --width 8 \
      --constraint "mae=0.5,er=60" --seeds 30 --pods 4 \
      --results-dir /shared/sweep-shards --history summary

Each process executes its own disjoint slice of the chunk plan (pod index
auto-resolved from the mesh/process, or forced with --pod-index) and commits
its shards independently; results are bit-identical to the single-host run
and any pod can be re-launched to resume its slice.
"""
from __future__ import annotations

import argparse
import contextlib
import json

import jax
import numpy as np

from repro.core.fitness import ConstraintSpec
from repro.core.library import save_library
from repro.core.search import SearchConfig, run_sweep_serial
from repro.core.sweep import SweepConfig, run_sweep_batched
from repro.core.evolve import EvolveConfig
from repro.runtime.compile_cache import enable_compile_cache


def parse_constraint(s: str) -> ConstraintSpec:
    kw = {}
    for part in s.split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        k = k.strip()
        if k in ("acc0", "gauss"):
            kw[k] = v.strip().lower() in ("1", "true", "yes", "")
        else:
            kw[k] = float(v)
    return ConstraintSpec(**kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--kind", default="mul", choices=["mul", "add"])
    ap.add_argument("--nodes", type=int, default=400)
    ap.add_argument("--constraint", action="append", required=True,
                    help='e.g. "mae=0.5,er=60" (repeatable)')
    ap.add_argument("--generations", type=int, default=2000)
    ap.add_argument("--lam", type=int, default=8)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"],
                    help="candidate evaluation: pure-jnp or the fused "
                         "(runs x lambda) Pallas kernel (one dispatch per "
                         "generation in the batched engine; interpret on CPU)")
    ap.add_argument("--dedup", action="store_true",
                    help="phenotype-dedup evaluation cache (DESIGN.md "
                         "section 8): evaluate each unique active subgraph "
                         "once per generation and reuse cached results "
                         "across generations.  Execution-only — results are "
                         "bit-identical with or without it")
    ap.add_argument("--dedup-cache-size", type=int, default=1 << 16,
                    help="entry bound of the cross-generation phenotype LRU "
                         "(default: 65536)")
    ap.add_argument("--eval-mode", default="exhaustive",
                    choices=["exhaustive", "sampled"],
                    help="evaluation inputs (DESIGN.md section 9): "
                         "'exhaustive' scores every candidate on the full "
                         "2^(2w) cube (bit-identical to the historic "
                         "engine); 'sampled' scores them on a deterministic "
                         "--sample-size operand sample from --input-dist — "
                         "the only tractable mode past width ~10-12, with "
                         "per-metric standard errors reported")
    ap.add_argument("--sample-size", type=int, default=1 << 14,
                    help="rows per sample (eval-mode=sampled); rounded up "
                         "to a power-of-two word count x 32 lanes "
                         "(default: 16384)")
    ap.add_argument("--input-dist", default="uniform",
                    choices=["uniform", "gaussian", "empirical"],
                    help="operand distribution of the sample (DESIGN.md "
                         "section 9): uniform over [0, 2^w); gaussian "
                         "centered mid-range (sigma = 2^w/6, clipped); or "
                         "empirical — inverse-CDF draws from an activation "
                         "histogram captured off the data pipeline")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="counter-based PRNG seed of the sample stream "
                         "(deterministic + checkpoint-replayable; part of "
                         "the grid fingerprint)")
    ap.add_argument("--certify", action="store_true",
                    help="exact-verification escalation tier (DESIGN.md "
                         "section 10): after each sampled sweep chunk, the "
                         "best elites that satisfy the combined constraint "
                         "on sampled metrics are re-measured EXACTLY over "
                         "the full 2^(2w) cube (one dispatch at small "
                         "widths, a chunked bit-parallel pass at large "
                         "ones), so emitted WCE/ACC0 verdicts are "
                         "guarantees, not estimates.  No-op under "
                         "--eval-mode exhaustive (a census is already "
                         "exact)")
    ap.add_argument("--certify-budget", type=int, default=8,
                    help="base escalations per sweep chunk; the adaptive "
                         "policy ramps the cap toward exact checks as the "
                         "sweep converges (default: 8)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--export-artifacts", default=None, metavar="DIR",
                    help="after the sweep, export per-constraint elite "
                         "circuits from --results-dir as fingerprinted LUT "
                         "artifacts + registry.json (core.artifacts, "
                         "DESIGN.md section 12) into DIR — the input of "
                         "`serve --approx-lut`; equivalent to running "
                         "`python -m repro.launch.export` afterwards")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="runs per jit'd batch of the sweep engine")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="resumable sweep state; rerun with the same grid "
                         "to continue mid-grid")
    ap.add_argument("--results-dir", default=None,
                    help="stream each finished chunk to an on-disk result "
                         "shard (core.results); the shard set is resumable "
                         "and is read back with SweepResultReader")
    ap.add_argument("--history", default=None,
                    choices=["full", "summary", "none"],
                    help="per-generation history mode: 'full' keeps them in "
                         "RAM, 'summary' spills them to --results-dir only "
                         "(flat host memory), 'none' drops them "
                         "(default: full)")
    ap.add_argument("--no-history", action="store_true",
                    help="alias for --history none (kept for compatibility)")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod-shard the sweep: partition the chunk plan "
                         "over N pods and run only this process's slice "
                         "(launch once per pod with a shared --results-dir; "
                         "DESIGN.md section 6)")
    ap.add_argument("--pod-index", type=int, default=None,
                    help="which pod slice this process executes (default: "
                         "resolved from the active mesh / JAX process index)")
    ap.add_argument("--async-commit", action="store_true",
                    help="commit result shards / checkpoints on a bounded "
                         "background thread so the next chunk dispatches "
                         "while the previous one's npz write + fsync runs "
                         "(DESIGN.md section 11).  Execution-only: committed "
                         "bytes, order and crash guarantees are identical "
                         "to the synchronous path")
    ap.add_argument("--migrate-every", type=int, default=0,
                    help="island-model elite migration between pods "
                         "(DESIGN.md section 11): every N chunks of its own "
                         "slice a pod publishes its per-sigma elite genomes "
                         "to --results-dir, and later chunks fold the other "
                         "pods' published elites into their initial "
                         "population under a deterministic merge rule.  "
                         "Result-changing (joins the grid fingerprint when "
                         "on); 0 disables (default), keeping results "
                         "byte-identical to the migration-less engine")
    ap.add_argument("--migrate-timeout", type=float, default=120.0,
                    help="seconds to wait for a lagging pod's migrant file "
                         "before failing (default: 120)")
    ap.add_argument("--serial", action="store_true",
                    help="reference serial loop instead of the batched engine")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record the sweep with the JAX profiler into DIR "
                         "(an .xplane.pb for TensorBoard/XProf): device ops "
                         "(the power model's scan under the scope "
                         "'active_mask') and the sweep's host phases "
                         "(spans sweep.prepare, sweep.dispatch, sweep.fetch, "
                         "sweep.commit, sweep.finish)")
    args = ap.parse_args(argv)
    if args.pods > 1 and not args.results_dir:
        ap.error("--pods > 1 needs a shared --results-dir (the shard set "
                 "is the only cross-pod resume state)")
    if args.serial and args.pods > 1:
        ap.error("--serial is the single-process reference loop; it cannot "
                 "pod-shard the grid (drop --serial or --pods)")
    if args.serial and args.dedup:
        ap.error("--dedup lives in the batched sweep engine; drop --serial")
    if args.serial and args.certify:
        ap.error("--certify's escalation driver lives in the batched sweep "
                 "engine; drop --serial")
    if args.serial and args.async_commit:
        ap.error("--async-commit's background committer lives in the "
                 "batched sweep engine; drop --serial")
    if args.serial and args.migrate_every:
        ap.error("--migrate-every lives in the batched sweep engine; drop "
                 "--serial")
    if args.migrate_every and not args.results_dir:
        ap.error("--migrate-every needs a --results-dir: migrant files "
                 "ride the shared results directory (DESIGN.md section 11)")
    if args.export_artifacts and not args.results_dir:
        ap.error("--export-artifacts reads the sweep back through the "
                 "results layer; it needs a --results-dir")
    if args.export_artifacts and args.serial:
        ap.error("--serial never writes result shards; drop --serial to "
                 "use --export-artifacts")
    if args.export_artifacts and args.kind != "mul":
        ap.error("--export-artifacts builds multiplier LUT artifacts; "
                 "--kind add is not exportable")

    enable_compile_cache()
    cfg = SearchConfig(
        width=args.width, kind=args.kind, n_n=args.nodes,
        evolve=EvolveConfig(generations=args.generations, lam=args.lam,
                            backend=args.backend,
                            eval_mode=args.eval_mode,
                            sample_size=args.sample_size,
                            input_dist=args.input_dist,
                            sample_seed=args.sample_seed,
                            certify=args.certify,
                            certify_budget=args.certify_budget))
    constraints = [parse_constraint(c) for c in args.constraint]
    profiled = (jax.profiler.trace(args.profile_dir) if args.profile_dir
                else contextlib.nullcontext())
    if args.serial:
        with profiled:
            records = run_sweep_serial(cfg, constraints,
                                       seeds=range(args.seeds))
    else:
        mode = args.history or ("none" if args.no_history else "full")
        pod = args.pod_index
        if args.pods > 1 and pod is None:
            # resolve ONCE here so the printed label and the executed slice
            # cannot disagree
            from repro.parallel import ctx
            pod = ctx.default_pod_index(args.pods)
        sweep = SweepConfig(chunk_size=args.chunk_size,
                            checkpoint_dir=args.checkpoint_dir,
                            results_dir=args.results_dir,
                            keep_history=mode,
                            n_pods=args.pods, pod_index=pod,
                            dedup=args.dedup or None,
                            dedup_cache_size=args.dedup_cache_size,
                            async_commit=args.async_commit,
                            migrate_every=args.migrate_every,
                            migrate_timeout=args.migrate_timeout)
        with profiled:
            result = run_sweep_batched(cfg, constraints,
                                       seeds=range(args.seeds), sweep=sweep)
        records = result.records
        tag = f"pod {pod}/{args.pods}: " if args.pods > 1 else ""
        print(f"[evolve] {tag}{result.completed}/{result.n_runs} runs "
              f"@ {result.runs_per_sec:.2f} runs/s", flush=True)
        if args.certify and result.certify_stats is not None:
            st = result.certify_stats
            print(f"[evolve] certify: {st['escalated']} escalations this "
                  f"call, {st['certified_rows']}/{result.n_runs} rows "
                  f"certified exact (budget {st['budget']}/chunk)",
                  flush=True)
        if args.migrate_every and result.migrate_stats is not None:
            # only under --migrate-every, so migration-less stdout stays
            # byte-identical to the pre-§11 CLI
            st = result.migrate_stats
            print(f"[evolve] migrate: {st['published']} epochs published, "
                  f"{st['imported']} elites imported, {st['adopted']} runs "
                  f"adopted a migrant ({st['waited_s']:.1f}s waiting on "
                  f"peers)", flush=True)
        if args.dedup and result.dedup_stats is not None:
            st = result.dedup_stats
            print(f"[evolve] dedup cache: hit rate {st['hit_rate']:.1%} "
                  f"({st['evaluated']}/{st['candidates']} candidates "
                  f"evaluated, {st['lru_hits']} LRU hits, "
                  f"{st['evictions']} evictions)", flush=True)
        if args.results_dir:
            reader = result.reader()
            print(f"[evolve] {len(reader.spans())} result shards "
                  f"({reader.completed}/{reader.n_runs} runs, history mode "
                  f"{reader.keep_history!r}) -> {args.results_dir}",
                  flush=True)
    metric_names = ("mae", "wce", "er", "mre", "avg", "acc0", "gauss")
    for r in records:
        met = {n: round(float(v), 4) for n, v in zip(metric_names, r.metrics)}
        row = {"constraint": r.constraint, "seed": r.seed,
               "power_rel": round(r.power_rel, 4),
               "feasible": r.feasible, "metrics": met}
        if args.eval_mode == "sampled":
            # per-metric standard errors (DESIGN.md §9) — the ±1 SE interval
            # downstream margin-aware thresholds consume
            row["metrics_stderr"] = {
                n: round(float(v), 6)
                for n, v in zip(metric_names, r.metrics_stderr)}
            if args.certify:
                # only under --certify, so sampled-only output stays
                # byte-identical to the pre-§10 CLI
                row["certified"] = r.certified
        print(json.dumps(row), flush=True)
    if args.out:
        save_library(records, args.out)
        print(f"[evolve] wrote {len(records)} circuits -> {args.out}")
    if args.export_artifacts:
        from repro.core.artifacts import export_elites
        registry = export_elites(args.results_dir, args.export_artifacts)
        print(f"[evolve] exported {len(registry['artifacts'])} LUT "
              f"artifact(s) -> {args.export_artifacts} "
              f"(grid {registry['grid_fingerprint'][:12]}...)", flush=True)


if __name__ == "__main__":
    main()
