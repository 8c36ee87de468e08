"""Evolution loop: fitness semantics, selection monotonicity, search gains."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics as M
from repro.core.evolve import (EvolveConfig, _eval_pallas,
                               _eval_pop_pallas, evolve)
from repro.core.fitness import ConstraintSpec, feasible, fitness
from repro.core.search import SearchConfig, problem_arrays, run_search


def test_fitness_infeasible_is_inf():
    thr = jnp.asarray(ConstraintSpec(mae=1.0).thresholds())
    bad = jnp.zeros((M.N_METRICS,)).at[M.MAE].set(2.0)
    good = jnp.zeros((M.N_METRICS,)).at[M.MAE].set(0.5)
    assert np.isinf(float(fitness(jnp.float32(10.0), bad, thr)))
    assert float(fitness(jnp.float32(10.0), good, thr)) == 10.0


def test_boolean_constraints_lower_bounded():
    thr = jnp.asarray(ConstraintSpec(acc0=True).thresholds())
    v = jnp.zeros((M.N_METRICS,))
    assert not bool(feasible(v, thr))            # acc0 = 0 -> infeasible
    assert bool(feasible(v.at[M.ACC0].set(1.0), thr))


def test_layout_field_accepts_only_genome_major():
    assert EvolveConfig().layout == "genome_major"
    for layout in ("cube_major", "auto"):
        with pytest.raises(ValueError, match="layout"):
            EvolveConfig(layout=layout)


@pytest.mark.parametrize("eval_fn", [_eval_pallas, _eval_pop_pallas])
def test_pallas_refuses_a_sharded_cube(eval_fn):
    """The kernel evaluates the whole cube on one chip: an axis to combine
    cube shards over is refused before anything is traced."""
    with pytest.raises(ValueError, match="whole cube"):
        eval_fn(None, None, None, None, 256.0, "model")


def _run(width=3, gens=400, lam=6, con=None, seed=0, n_n=100):
    cfg = SearchConfig(width=width, n_n=n_n,
                       evolve=EvolveConfig(generations=gens, lam=lam))
    gold, spec, planes, gvals, gpower = problem_arrays(cfg)
    con = con or ConstraintSpec(mae=2.0)
    thr = jnp.asarray(con.thresholds())
    res = evolve(spec, cfg.evolve, gold, thr, planes, gvals, gpower,
                 jax.random.PRNGKey(seed))
    return res, gpower


def test_parent_fitness_monotone_nonincreasing():
    res, _ = _run()
    fit = np.asarray(res.hist_fit)
    fit = np.where(np.isinf(fit), np.nan, fit)
    diffs = np.diff(fit[np.isfinite(fit)])
    assert (diffs <= 1e-5).all()


def test_evolution_reduces_power_under_loose_constraint():
    res, gpower = _run(gens=800, con=ConstraintSpec(mae=5.0), seed=1)
    assert float(res.hist_power_rel[-1]) < 0.98, (
        "no power reduction found in 800 generations")


def test_final_circuit_respects_constraints():
    con = ConstraintSpec(mae=2.0, er=80.0)
    cfg = SearchConfig(width=3, n_n=100,
                       evolve=EvolveConfig(generations=400, lam=6))
    rec, res = run_search(cfg, con, seed=0)
    assert rec.feasible
    assert rec.metrics[M.MAE] <= 2.0 + 1e-4
    assert rec.metrics[M.ER] <= 80.0 + 1e-4


def test_acc0_constraint_is_maintained():
    con = ConstraintSpec(mae=5.0, acc0=True)
    cfg = SearchConfig(width=3, n_n=100,
                       evolve=EvolveConfig(generations=300, lam=6))
    rec, _ = run_search(cfg, con, seed=2)
    assert rec.feasible and rec.metrics[M.ACC0] == 1.0


@pytest.mark.kernel_diff
def test_pallas_backend_matches_jnp_backend():
    cfg = SearchConfig(width=3, n_n=80,
                       evolve=EvolveConfig(generations=60, lam=3,
                                           backend="pallas"))
    gold, spec, planes, gvals, gpower = problem_arrays(cfg)
    thr = jnp.asarray(ConstraintSpec(mae=2.0).thresholds())
    r1 = evolve(spec, cfg.evolve, gold, thr, planes, gvals, gpower,
                jax.random.PRNGKey(0))
    ecfg2 = dataclasses.replace(cfg.evolve, backend="jnp")
    r2 = evolve(spec, ecfg2, gold, thr, planes, gvals, gpower,
                jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(r1.hist_fit),
                               np.asarray(r2.hist_fit), rtol=1e-5)


def test_library_roundtrip(tmp_path):
    from repro.core import library as L
    cfg = SearchConfig(width=3, n_n=80,
                       evolve=EvolveConfig(generations=100, lam=4))
    rec, _ = run_search(cfg, ConstraintSpec(mae=3.0), seed=0)
    path = str(tmp_path / "lib.json")
    L.save_library([rec], path)
    lib = L.load_library(path)
    assert len(lib) == 1
    best = L.select_best(lib, mae=3.0)
    assert best is not None
    g = L.record_to_genome(best)
    assert g.nodes.shape == (80, 3)
    lut = L.multiplier_lut(g, __import__(
        "repro.core.genome", fromlist=["CGPSpec"]).CGPSpec(6, 6, 80))
    assert lut.shape == (8, 8)


def test_pareto_front():
    from repro.core.pareto import pareto_front, pareto_points, hypervolume_2d
    pts = np.array([[1, 5], [2, 3], [3, 4], [4, 1], [5, 5], [2.5, 3]])
    m = pareto_front(pts)
    assert set(map(tuple, pts[m])) == {(1, 5), (2, 3), (4, 1)}
    hv = hypervolume_2d(pts, (6, 6))
    assert hv > 0
    # adding a dominated point must not change the hypervolume
    hv2 = hypervolume_2d(np.vstack([pts, [5, 5.5]]), (6, 6))
    assert abs(hv - hv2) < 1e-9
