"""Sampled + distribution-weighted evaluation mode (DESIGN.md §9).

Three contracts:

  * the EXHAUSTIVE path is bit-identical to the pre-§9 engine — same input
    arrays byte-for-byte, same grid fingerprint (checkpoints/shards written
    before the sampled mode existed still resume), zero reported stderr;
  * the SAMPLED path is deterministic (pure function of the stream identity)
    and statistically sound: sampled MAE/ER land within the reported
    confidence interval of the exhaustive truth for >= 95% of sample seeds,
    and tighten as sample_size grows toward 2^(2w);
  * the mode unlocks widths the cube cannot reach: a width-12 multiplier
    evolve step completes on CPU under eval_mode="sampled" (the exhaustive
    cube would be 16.7M rows/genome).
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import golden as G
from repro.core import metrics as M
from repro.core import sampling, simulate
from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.genome import CGPSpec, random_genome
from repro.core.search import SearchConfig, problem_arrays
from repro.core.sweep import grid_fingerprint, sweep_grid


# ------------------------- stream determinism -----------------------------

def test_effective_sample_size_rounds_to_pow2_words():
    assert sampling.effective_sample_size(1) == 32
    assert sampling.effective_sample_size(33) == 64
    assert sampling.effective_sample_size(1000) == 1024
    assert sampling.effective_sample_size(16384) == 16384
    with pytest.raises(ValueError):
        sampling.effective_sample_size(0)


@pytest.mark.parametrize("dist", sampling.INPUT_DISTS)
def test_sampled_operands_deterministic_and_in_range(dist):
    a1, b1 = sampling.sampled_operands(6, 2048, dist, sample_seed=7)
    a2, b2 = sampling.sampled_operands(6, 2048, dist, sample_seed=7)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    a3, _ = sampling.sampled_operands(6, 2048, dist, sample_seed=8)
    assert (a1 != a3).any(), "seed must change the stream"
    assert a1.min() >= 0 and a1.max() < 64
    assert a1.shape == (2048,)
    # operand streams are disjoint — a and b are not the same draw
    assert (a1 != b1).any()


def test_stream_fingerprint_keys_every_axis():
    base = sampling.stream_fingerprint(8, 4096, "uniform", 0)
    assert base == sampling.stream_fingerprint(8, 4096, "uniform", 0)
    # nominal sizes that materialize the same rows share the fingerprint
    assert base == sampling.stream_fingerprint(8, 4000, "uniform", 0)
    for other in (sampling.stream_fingerprint(9, 4096, "uniform", 0),
                  sampling.stream_fingerprint(8, 8192, "uniform", 0),
                  sampling.stream_fingerprint(8, 4096, "gaussian", 0),
                  sampling.stream_fingerprint(8, 4096, "uniform", 1)):
        assert other != base


def test_pack_sample_planes_roundtrip():
    """Packed sample planes decode back to the operand integers with the
    exhaustive-cube bit layout (a = planes [0, w), b = planes [w, 2w))."""
    w = 5
    a, b = sampling.sampled_operands(w, 256, "uniform", sample_seed=3)
    planes = sampling.pack_sample_planes(a, b, w)
    assert planes.shape == (2 * w, len(a) // 32)
    vals = np.asarray(simulate.unpack_values(jnp.asarray(planes[:w])))
    np.testing.assert_array_equal(vals, a)
    vals_b = np.asarray(simulate.unpack_values(jnp.asarray(planes[w:])))
    np.testing.assert_array_equal(vals_b, b)


def test_golden_circuit_exact_on_sample():
    """Simulating the golden netlist on sampled planes reproduces the
    integer golden values — the sample pair is internally consistent."""
    cfg = SearchConfig(width=4, kind="mul", n_n=80,
                       evolve=EvolveConfig(eval_mode="sampled",
                                           sample_size=1024,
                                           input_dist="gaussian"))
    gold, spec, planes, gvals, _ = problem_arrays(cfg)
    cvals = simulate.simulate_values(gold, spec, planes)
    np.testing.assert_array_equal(np.asarray(cvals), np.asarray(gvals))


def test_empirical_histogram_deterministic():
    h1 = sampling.empirical_histogram(4, seed=0, n_batches=2)
    h2 = sampling.empirical_histogram(4, seed=0, n_batches=2)
    np.testing.assert_array_equal(h1, h2)
    assert h1.sum() > 0 and h1.shape == (16,)


# --------------------- exhaustive-path bit-identity -----------------------

def test_exhaustive_problem_arrays_bit_identical_to_seed():
    """eval_mode="exhaustive" (the default) builds byte-for-byte the same
    evaluation inputs as the pre-§9 direct construction."""
    cfg = SearchConfig(width=3, kind="mul", n_n=60)
    assert cfg.evolve.eval_mode == "exhaustive"
    _, spec, planes, gvals, _ = problem_arrays(cfg)
    np.testing.assert_array_equal(
        np.asarray(planes), np.asarray(simulate.input_planes(spec.n_i)))
    np.testing.assert_array_equal(
        np.asarray(gvals), G.golden_values(3, "mul"))
    assert np.asarray(planes).tobytes() == np.asarray(
        simulate.input_planes(spec.n_i)).tobytes()


def test_exhaustive_grid_fingerprint_unchanged_from_seed():
    """Exhaustive grids hash the exact pre-§9 ident dict — no eval keys —
    so checkpoints/shard manifests written before this PR still resume."""
    cfg = SearchConfig(width=3, kind="mul", n_n=60,
                       evolve=EvolveConfig(generations=50, lam=4))
    grid = sweep_grid([ConstraintSpec(mae=1.0)], (0, 1))
    ecfg = cfg.evolve
    legacy_ident = {
        "width": cfg.width, "kind": cfg.kind, "n_n": cfg.n_n,
        "generations": ecfg.generations, "lam": ecfg.lam,
        "mutation_rate": ecfg.mutation_rate, "backend": ecfg.backend,
        "migrate_every": ecfg.migrate_every,
        "keep_history": True,
        "grid": [(con.describe(), con.gauss_sigma, seed)
                 for con, seed in grid],
        "thresholds": hashlib.sha256(
            np.stack([con.thresholds() for con, _ in grid]).tobytes()
        ).hexdigest(),
    }
    legacy = hashlib.sha256(json.dumps(
        legacy_ident, sort_keys=True, default=float).encode()).hexdigest()
    assert grid_fingerprint(cfg, grid, "full") == legacy

    scfg = SearchConfig(width=3, kind="mul", n_n=60,
                        evolve=EvolveConfig(generations=50, lam=4,
                                            eval_mode="sampled"))
    assert grid_fingerprint(scfg, grid, "full") != legacy


def test_sampled_fingerprint_tracks_stream_identity():
    def fp(**kw):
        cfg = SearchConfig(width=4, kind="mul", n_n=80,
                           evolve=EvolveConfig(generations=10, lam=2,
                                               eval_mode="sampled", **kw))
        return grid_fingerprint(cfg, sweep_grid([ConstraintSpec(mae=1.0)],
                                                (0,)), "none")
    base = fp()
    assert base == fp()
    assert fp(sample_seed=1) != base
    assert fp(sample_size=1 << 15) != base
    assert fp(input_dist="gaussian") != base


def test_evolve_config_validation():
    with pytest.raises(ValueError):
        EvolveConfig(eval_mode="bogus")
    with pytest.raises(ValueError):
        EvolveConfig(input_dist="bogus")
    with pytest.raises(ValueError):
        EvolveConfig(sample_size=0)


# ----------------------- CI / convergence property ------------------------

def _metric_pair(gvals, cvals, n_o, sampled):
    p = M.error_partials(jnp.asarray(gvals), jnp.asarray(cvals), 256.0,
                         n_bits=n_o)
    met = np.asarray(M.finalize_metrics(p, n_o, 256.0))
    se = np.asarray(M.metric_stderr(p, n_o)) if sampled else None
    return met, se


def test_sampled_metrics_converge_to_exhaustive_within_ci():
    """Property (ISSUE 7): sampled MAE/ER land inside the reported
    z=2.576 (99%) confidence interval of the exhaustive truth for >= 95%
    of sample seeds, and the CI tightens as sample_size grows."""
    w, n_n = 4, 80
    spec = CGPSpec(n_i=2 * w, n_o=2 * w, n_n=n_n)
    genome = random_genome(jax.random.PRNGKey(5), spec)
    # exhaustive truth
    full_planes = simulate.input_planes(spec.n_i)
    gvals_full = G.golden_values(w, "mul")
    cvals_full = np.asarray(simulate.simulate_values(genome, spec,
                                                     full_planes))
    truth, _ = _metric_pair(gvals_full, cvals_full, spec.n_o, sampled=False)

    z = 2.576
    n_seeds = 20
    mae_devs = {}
    for size in (512, 4096):
        covered = 0
        devs = []
        for seed in range(n_seeds):
            planes, gvals = sampling.sample_problem(w, "mul", size,
                                                    "uniform", seed)
            cvals = np.asarray(simulate.simulate_values(
                genome, spec, jnp.asarray(planes)))
            met, se = _metric_pair(gvals, cvals, spec.n_o, sampled=True)
            ok = True
            for m in (M.MAE, M.ER):
                half = z * max(float(se[m]), 1e-9)
                ok &= abs(float(met[m]) - float(truth[m])) <= half
            covered += ok
            devs.append(abs(float(met[M.MAE]) - float(truth[M.MAE])))
        assert covered / n_seeds >= 0.95, \
            f"size {size}: only {covered}/{n_seeds} seeds inside the CI"
        mae_devs[size] = float(np.mean(devs))
    # convergence toward the exhaustive truth as sample_size -> 2^(2w)
    assert mae_devs[4096] < mae_devs[512]


def test_stderr_matches_numpy_oracle():
    rng = np.random.default_rng(11)
    g = rng.integers(0, 255, size=2048).astype(np.int32)
    c = np.clip(g - rng.integers(0, 9, size=2048), 0, None).astype(np.int32)
    p = M.error_partials(jnp.asarray(g), jnp.asarray(c), 256.0, n_bits=8)
    se = np.asarray(M.metric_stderr(p, 8))
    se_np = M.metrics_stderr_np(g, c, 8)
    np.testing.assert_allclose(se, se_np, rtol=1e-4, atol=1e-7)
    # extreme-value / indicator metrics report no CLT interval
    assert se[M.WCE] == 0 and se[M.ACC0] == 0 and se[M.GAUSS] == 0


def test_sampled_partials_combine_like_cube_shards():
    """Sample shards reuse the cube-shard psum/pmax contract unchanged:
    combining per-shard partials (incl. the new second-moment rows) equals
    the unsharded partials of the concatenated sample."""
    planes, gvals = sampling.sample_problem(4, "mul", 2048, "uniform", 0)
    spec = CGPSpec(n_i=8, n_o=8, n_n=60)
    genome = random_genome(jax.random.PRNGKey(2), spec)
    cvals = np.asarray(simulate.simulate_values(genome, spec,
                                                jnp.asarray(planes)))
    whole = M.error_partials(jnp.asarray(gvals), jnp.asarray(cvals), 256.0,
                             n_bits=8)
    half = len(gvals) // 2
    shards = [M.error_partials(jnp.asarray(gvals[i:j]),
                               jnp.asarray(cvals[i:j]), 256.0, n_bits=8)
              for i, j in ((0, half), (half, len(gvals)))]
    for name in M.MetricPartials._fields:
        if name == "moments":  # the kernel's moments call alone has them
            continue
        a, b = (getattr(s, name) for s in shards)
        comb = np.maximum(a, b) if name == "wce_max" else a + b
        np.testing.assert_allclose(np.asarray(comb),
                                   np.asarray(getattr(whole, name)),
                                   rtol=1e-6,
                                   err_msg=f"shard combine: {name}")


# ----------------------- width-12: breaking the wall ----------------------

def test_width12_sampled_evolve_completes_on_cpu():
    """A width-12 multiplier evolve run completes under eval_mode="sampled"
    (exhaustive would need a 16.7M-row cube per candidate), with per-metric
    confidence intervals reported."""
    from repro.core.sweep import SweepConfig, run_sweep_batched
    gold, spec = G.array_multiplier(12, n_n=None)  # auto-sized netlist
    cfg = SearchConfig(
        width=12, kind="mul", n_n=spec.n_n,
        evolve=EvolveConfig(generations=3, lam=2, eval_mode="sampled",
                            sample_size=2048, input_dist="uniform"))
    res = run_sweep_batched(cfg, [ConstraintSpec(mae=2.0)], (0,),
                            SweepConfig(chunk_size=1, keep_history="none"))
    assert res.completed == 1
    rec = res.records[0]
    assert rec.metrics.shape == (M.N_METRICS,)
    assert np.isfinite(rec.metrics).all()
    assert rec.metrics_stderr.shape == (M.N_METRICS,)
    assert np.isfinite(rec.metrics_stderr).all()
