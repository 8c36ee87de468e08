"""Batched constraint-grid sweep engine vs the serial oracle.

The batched engine must reproduce the serial ``run_search`` loop per run
(same PRNG streams, same evaluation semantics — genomes match bit-for-bit on
CPU), stay invariant under chunking, and resume mid-grid from a checkpoint.
The same guarantees hold per backend: the fused-pallas sweep path is
bit-identical to the serial pallas loop, and (for constraints whose selection
depends only on exact integer partials) to the jnp backend.
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core import metrics as M
from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.search import SearchConfig, run_sweep, run_sweep_serial
from repro.core.sweep import (SweepConfig, plan_chunks, run_sweep_batched,
                              sweep_grid)

CFG = SearchConfig(width=2, kind="add", n_n=40,
                   evolve=EvolveConfig(generations=80, lam=4))
CONSTRAINTS = ([ConstraintSpec(mae=t) for t in (0.5, 1.0, 2.0)]
               + [ConstraintSpec(er=e) for e in (25.0, 50.0)]
               + [ConstraintSpec(mae=1.0, er=50.0)])
SEEDS = (0, 1)
N_RUNS = len(CONSTRAINTS) * len(SEEDS)


@pytest.fixture(scope="module")
def serial_records():
    return run_sweep_serial(CFG, CONSTRAINTS, SEEDS)


@pytest.fixture(scope="module")
def batched_result():
    return run_sweep_batched(CFG, CONSTRAINTS, SEEDS,
                             SweepConfig(chunk_size=N_RUNS))


def _assert_records_match(a, b, exact_genomes=True):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra.constraint == rb.constraint and ra.seed == rb.seed
        if exact_genomes:
            assert (ra.genome_nodes == rb.genome_nodes).all()
            assert (ra.genome_outs == rb.genome_outs).all()
        np.testing.assert_allclose(ra.metrics, rb.metrics,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ra.power_rel, rb.power_rel, rtol=1e-5)
        assert ra.feasible == rb.feasible


@pytest.mark.kernel_diff
def test_batched_matches_serial_per_run(serial_records, batched_result):
    assert N_RUNS >= 12  # ISSUE acceptance: >= 6 configs x 2 seeds
    assert batched_result.completed == N_RUNS
    _assert_records_match(serial_records, batched_result.records)


def test_chunked_equals_unchunked(batched_result):
    # chunk_size 5 forces padding AND multiple chunks over the 12-run grid
    chunked = run_sweep_batched(CFG, CONSTRAINTS, SEEDS,
                                SweepConfig(chunk_size=5))
    _assert_records_match(batched_result.records, chunked.records)
    np.testing.assert_array_equal(batched_result.hist_fit, chunked.hist_fit)


@pytest.mark.kernel_diff
def test_run_sweep_api_is_batched(serial_records):
    recs = run_sweep(CFG, CONSTRAINTS, SEEDS,
                     sweep=SweepConfig(chunk_size=7))
    _assert_records_match(serial_records, recs)


def test_checkpoint_resume_mid_grid(tmp_path, batched_result):
    sweep = SweepConfig(chunk_size=4, checkpoint_dir=str(tmp_path))
    partial = run_sweep_batched(CFG, CONSTRAINTS, SEEDS,
                                dataclasses.replace(sweep, max_chunks=1))
    assert partial.completed == 4 and len(partial.records) == 4
    # the interrupted state is committed; a fresh call continues mid-grid
    resumed = run_sweep_batched(CFG, CONSTRAINTS, SEEDS, sweep)
    assert resumed.completed == N_RUNS
    _assert_records_match(batched_result.records, resumed.records)
    np.testing.assert_array_equal(batched_result.hist_fit, resumed.hist_fit)


def test_checkpoint_ignored_on_grid_change(tmp_path):
    """A checkpoint from a DIFFERENT grid must not poison the sweep."""
    sweep = SweepConfig(chunk_size=4, checkpoint_dir=str(tmp_path))
    run_sweep_batched(CFG, CONSTRAINTS, SEEDS,
                      dataclasses.replace(sweep, max_chunks=1))
    other = run_sweep_batched(CFG, CONSTRAINTS[:2], (5,), sweep)
    assert other.completed == 2
    fresh = run_sweep_batched(CFG, CONSTRAINTS[:2], (5,),
                              SweepConfig(chunk_size=4))
    _assert_records_match(fresh.records, other.records)


def test_histories_consistent(batched_result):
    res = batched_result
    gens = CFG.evolve.generations
    assert res.hist_fit.shape == (N_RUNS, gens)
    assert res.hist_metrics.shape == (N_RUNS, gens, M.N_METRICS)
    # parent fitness is monotone non-increasing wherever finite (1+lambda)
    for r in range(N_RUNS):
        fit = res.hist_fit[r]
        finite = fit[np.isfinite(fit)]
        assert (np.diff(finite) <= 1e-5).all()
    # the last history entry is the returned parent's power
    np.testing.assert_allclose(res.hist_power_rel[:, -1], res.power_rel,
                               rtol=1e-4)


def test_resume_not_shadowed_by_other_grid_checkpoint(tmp_path):
    """A higher-numbered checkpoint of a DIFFERENT grid in the same dir must
    not hide this grid's committed progress (resume scans by fingerprint)."""
    sweep = SweepConfig(chunk_size=4, checkpoint_dir=str(tmp_path))
    run_sweep_batched(CFG, CONSTRAINTS, SEEDS, sweep)        # grid A: step 12
    grid_b = CONSTRAINTS[:4]
    run_sweep_batched(CFG, grid_b, SEEDS,
                      dataclasses.replace(sweep, max_chunks=1))  # B: step 4
    resumed = run_sweep_batched(CFG, grid_b, SEEDS,
                                dataclasses.replace(sweep, max_chunks=1))
    # one more chunk finishes B only if B's step 4 was found under A's step 12
    assert resumed.completed == 8 and resumed.done_mask.all()


@pytest.mark.kernel_diff
def test_sigma_interleaved_grid_matches_serial():
    """Sigma-heterogeneous grids execute sigma-grouped (one compiled program
    per sigma, no padding blowup) but must come back in grid order."""
    cons = [ConstraintSpec(mae=2.0),
            ConstraintSpec(mae=2.0, gauss_sigma=16.0),
            ConstraintSpec(er=50.0),
            ConstraintSpec(er=50.0, gauss_sigma=16.0)]
    serial = run_sweep_serial(CFG, cons, (0,))
    batched = run_sweep_batched(CFG, cons, (0,), SweepConfig(chunk_size=4))
    _assert_records_match(serial, batched.records)
    assert batched.done_mask.all()


def test_plan_chunks_breaks_on_sigma_change():
    sigmas = np.array([1.0, 1.0, 1.0, 2.0, 2.0, 1.0])
    assert plan_chunks(sigmas, 4) == [(0, 3), (3, 5), (5, 6)]
    assert plan_chunks(sigmas, 2) == [(0, 2), (2, 3), (3, 5), (5, 6)]
    assert plan_chunks(np.ones(5), 8) == [(0, 5)]


def test_sweep_grid_order_matches_serial_loop():
    grid = sweep_grid(CONSTRAINTS, SEEDS)
    assert len(grid) == N_RUNS
    assert grid[0] == (CONSTRAINTS[0], 0) and grid[1] == (CONSTRAINTS[0], 1)
    assert grid[2][0] is CONSTRAINTS[1]


# --------------------------------------------------------------------------
# Backend parity (fused pallas kernel path, ISSUE 2)
# --------------------------------------------------------------------------

def _with_backend(backend: str):
    return dataclasses.replace(
        PAR_CFG, evolve=dataclasses.replace(PAR_CFG.evolve, backend=backend))


PAR_CFG = SearchConfig(width=2, kind="add", n_n=40,
                       evolve=EvolveConfig(generations=60, lam=3))
PAR_CONS = [ConstraintSpec(mae=1.0), ConstraintSpec(er=50.0)]
PAR_SEEDS = (0, 1)
PAR_RUNS = len(PAR_CONS) * len(PAR_SEEDS)


def _parity_backends():
    """The CI backend-matrix leg narrows this via REPRO_TEST_BACKEND."""
    env = os.environ.get("REPRO_TEST_BACKEND")
    return (env,) if env in ("jnp", "pallas") else ("jnp", "pallas")


@pytest.mark.kernel_diff
@pytest.mark.parametrize("backend", _parity_backends())
def test_batched_matches_serial_same_backend(backend):
    """Per-backend equivalence oracle: the batched engine reproduces the
    serial loop bit-for-bit with the SAME backend on both sides — for
    "pallas" that pits the fused (runs × λ) kernel dispatch against one
    per-generation λ-population dispatch per run."""
    cfg = _with_backend(backend)
    serial = run_sweep_serial(cfg, PAR_CONS, PAR_SEEDS)
    batched = run_sweep_batched(cfg, PAR_CONS, PAR_SEEDS,
                                SweepConfig(chunk_size=3))  # ragged chunks
    assert batched.completed == PAR_RUNS
    _assert_records_match(serial, batched.records)


@pytest.mark.kernel_diff
@pytest.mark.skipif(os.environ.get("REPRO_TEST_BACKEND") == "jnp",
                    reason="cross-backend test; runs in the pallas CI leg")
def test_sweep_backend_parity_with_resume(tmp_path):
    """run_sweep(backend="pallas") matches backend="jnp" per-run, including
    through a mid-grid checkpoint resume of the pallas sweep.  The grid is
    mae/er-constrained, so selection depends only on exact integer partials
    and the evolved genomes must match bit-for-bit across backends."""
    want = run_sweep_batched(_with_backend("jnp"), PAR_CONS, PAR_SEEDS,
                             SweepConfig(chunk_size=2))
    sweep = SweepConfig(chunk_size=2, checkpoint_dir=str(tmp_path))
    cfg_p = _with_backend("pallas")
    partial = run_sweep_batched(cfg_p, PAR_CONS, PAR_SEEDS,
                                dataclasses.replace(sweep, max_chunks=1))
    assert partial.completed == 2 and len(partial.records) == 2
    resumed = run_sweep_batched(cfg_p, PAR_CONS, PAR_SEEDS, sweep)
    assert resumed.completed == PAR_RUNS
    _assert_records_match(want.records, resumed.records)
    np.testing.assert_array_equal(want.hist_fit, resumed.hist_fit)
