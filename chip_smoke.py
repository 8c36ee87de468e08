#!/usr/bin/env python3
"""Smoke run of the constraint sweep on a TPU chip: the main path, compiled.

  python chip_smoke.py

The paper's problem at full width — an 8x8 multiplier, 400 CGP nodes, the
exhaustive 2^16 input cube — swept over 2 constraints x 16 seeds with
lambda=8 through ``repro.launch.evolve.main``: one chunk of 32 runs, so
every generation evaluates 256 candidates in one kernel dispatch, streams
result shards and exports LUT artifacts.  The sweep runs twice — the Pallas
kernel and the pure-jnp backend — and the two must agree: genomes and every
metric exactly, MRE (a float32 sum of quotients) to a relative 1e-4, and
the other float32 reductions (power, hence fitness, and the mean and spread
of the signed error) to a relative 1e-5.  The constraints leave MRE
unconstrained, so no trajectory can fork on it.  Each artifact registry
then passes ``export --verify``, and the lowered Pallas step must hold a
``tpu_custom_call`` (the kernel compiled, not interpreted).

Everything runs in this one process: it holds the chip and starts no
child.  It exits non-zero without a TPU, and on any failed phase.  The
seconds and rates it prints are smoke figures, not benchmark numbers.  The
last line of stdout is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

WIDTH, NODES, LAM, SEEDS, CHUNK, GENS = 8, 400, 8, 16, 32, 50
CONSTRAINTS = ("mae=0.5,er=60", "wce=2.0,acc0")
MRE_RTOL = 1e-4
FLOAT_RTOL = 1e-5
#: float32 reductions (power over the gates, moments of the signed error
#: over the cube) that two different programs may reassociate
FLOAT_FIELDS = ("best_fit", "hist_fit", "hist_power_rel", "power_rel",
                "error_mean", "error_std")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def evolve_argv(backend: str, out: str) -> list:
    argv = ["--width", str(WIDTH), "--kind", "mul", "--nodes", str(NODES),
            "--generations", str(GENS), "--lam", str(LAM),
            "--seeds", str(SEEDS), "--chunk-size", str(CHUNK),
            "--backend", backend,
            "--results-dir", os.path.join(out, "shards"),
            "--history", "summary",
            "--export-artifacts", os.path.join(out, "registry")]
    for c in CONSTRAINTS:
        argv += ["--constraint", c]
    return argv


def search_config(backend: str):
    from repro.core.evolve import EvolveConfig
    from repro.core.search import SearchConfig
    return SearchConfig(width=WIDTH, kind="mul", n_n=NODES,
                        evolve=EvolveConfig(generations=GENS, lam=LAM,
                                            backend=backend))


def chunk_args(cfg):
    """The arguments ``run_sweep_batched`` hands its one chunk dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.search import problem_arrays
    from repro.launch.evolve import parse_constraint

    cons = [parse_constraint(c) for c in CONSTRAINTS]
    sigmas = {c.gauss_sigma for c in cons}
    check(len(sigmas) == 1, "constraints must share one gauss sigma")
    gold, spec, planes, gvals, gpower = problem_arrays(cfg)
    grid = [(c, s) for c in cons for s in range(SEEDS)]
    check(len(grid) == CHUNK, "the grid must be exactly one chunk")
    thr = jnp.asarray(np.stack([c.thresholds() for c, _ in grid]))
    keys = jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(s))
                                 for _, s in grid]))
    ecfg = dataclasses.replace(cfg.evolve, gauss_sigma=sigmas.pop(), seed=0)
    return spec, ecfg, gold, thr, planes, gvals, gpower, keys


def compile_chunk(fn, *args):
    """AOT-compile one chunk program; returns (seconds, optimized HLO)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return time.perf_counter() - t0, compiled


def compare(ref_dir: str, got_dir: str, label: str) -> None:
    """Two shard sets of the same grid: genomes, metrics and per-generation
    histories equal; the MRE metric column to MRE_RTOL.

    Power (hence fitness) and the signed-error moments are float32
    reductions that XLA may reassociate differently in two different
    programs, so those columns (FLOAT_FIELDS) are held to FLOAT_RTOL.
    Equal genomes still show that no trajectory forked on them.
    """
    import numpy as np
    from repro.core import metrics as M
    from repro.core.results import SweepResultReader

    ra, rb = SweepResultReader(ref_dir), SweepResultReader(got_dir)
    sa, sb = ra.summary(), rb.summary()
    check(bool(sa["done_mask"].all() and sb["done_mask"].all()),
          f"{label}: incomplete sweep")

    def same(a, b, what):
        a, b = np.asarray(a), np.asarray(b)
        if "metrics" in what:
            keep = [i for i in range(M.N_METRICS) if i != M.MRE]
            check(np.array_equal(a[..., keep], b[..., keep]),
                  f"{label}: {what} differ")
            a, b, rtol, what = a[..., M.MRE], b[..., M.MRE], MRE_RTOL, \
                f"{what} MRE"
        elif what in FLOAT_FIELDS:
            rtol = FLOAT_RTOL
        else:
            check(np.array_equal(a, b), f"{label}: {what} differ")
            return
        err = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
        err = float(np.nanmax(np.where(a == b, 0.0, err), initial=0.0))
        check(err <= rtol, f"{label}: {what} differ by {err:.3g} > {rtol}")
        if err:
            log(f"{label}: {what} max relative difference {err:.3g}")

    for key in sa:
        same(sa[key], sb[key], key)
    for (rows_a, ha), (rows_b, hb) in zip(ra.iter_history(),
                                          rb.iter_history()):
        same(rows_a, rows_b, "history rows")
        for key in ha:
            same(ha[key], hb[key], key)
    log(f"{label}: equal ({ra.n_runs} runs, genomes + histories)")


def smoke_figures(label: str, compile_s: float, wall_s: float) -> None:
    runs = len(CONSTRAINTS) * SEEDS
    log(f"{label}: SMOKE FIGURES, not benchmark numbers: compile "
        f"{compile_s:.2f} s, wall {wall_s:.2f} s, {runs / wall_s:.3f} runs/s, "
        f"{runs * GENS * LAM / wall_s:.1f} evaluations/s")


def run_evolve(backend: str, out: str) -> float:
    """``launch.evolve.main`` into ``out``; its stdout goes to evolve.log
    (the [evolve] status lines are echoed).  Returns wall seconds."""
    from repro.launch import evolve

    os.makedirs(out)
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        evolve.main(evolve_argv(backend, out))
    wall = time.perf_counter() - t0
    with open(os.path.join(out, "evolve.log"), "w") as f:
        f.write(buf.getvalue())
    for line in buf.getvalue().splitlines():
        if line.startswith("[evolve]"):
            log(f"  {line}")
    return wall


def one_chip(out: str) -> None:
    from repro.core import sweep
    from repro.kernels import ops
    from repro.launch import export

    check(not ops.default_interpret(), "Pallas interpret mode is on")
    dirs = {}
    for backend in ("pallas", "jnp"):
        cfg = search_config(backend)
        compile_s, compiled = compile_chunk(sweep.evolve_chunk,
                                            *chunk_args(cfg))
        has_kernel = "tpu_custom_call" in compiled.as_text()
        check(has_kernel == (backend == "pallas"),
              f"{backend}: tpu_custom_call in the chunk program: "
              f"{has_kernel}")
        if backend == "pallas":
            log(f"{backend}: chunk program holds the compiled Mosaic kernel "
                f"(tpu_custom_call)")
        dirs[backend] = os.path.join(out, backend)
        wall_s = run_evolve(backend, dirs[backend])
        smoke_figures(backend, compile_s, wall_s)
        check(export.main(["--verify",
                           os.path.join(dirs[backend], "registry")]) == 0,
              f"{backend}: export --verify failed")

    compare(os.path.join(dirs["jnp"], "shards"),
            os.path.join(dirs["pallas"], "shards"), "jnp vs pallas")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        REPO, "experiments", "tmp", "chip_smoke"),
        help="output directory (emptied first)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        log(f"FAIL: no repro package under {REPO}/src; run this from the "
            f"repository")
        return 2

    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    log(f"device: {dev}")
    if dev["platform"] != "tpu":
        log("FAIL: no TPU found; this script never falls back to the CPU")
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    one_chip(args.out)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
