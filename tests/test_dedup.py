"""Phenotype-dedup evaluation cache (DESIGN.md §8).

Three layers, matching the §8 contract:

  1. digest properties — ``phenotype_digests`` is invariant under exactly
     the transformations that leave the active subgraph intact (neutral
     mutations, position shifts, unary second-fan-in junk) and sensitive to
     every change that touches it;
  2. the ``PhenotypeLRU`` container — strict entry bound, eviction order,
     honest counters;
  3. the acceptance bar — ``run_sweep_batched`` with ``dedup`` on is
     BIT-identical to the fused step with it off: records, history arrays,
     and the streamed result shards, with a non-trivial measured hit rate.
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core import gates
from repro.core.evalcache import CacheStats, PhenotypeLRU
from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.genome import (CGPSpec, Genome, active_mask_np,
                               phenotype_digest, phenotype_digests,
                               random_genome)
from repro.core.search import SearchConfig
from repro.core.sweep import SweepConfig, run_sweep_batched

AND, OR = 2, 3
INV = 1  # one-input gate (gates.ONE_INPUT[INV] == 1)

CFG = SearchConfig(width=2, kind="add", n_n=40,
                   evolve=EvolveConfig(generations=40, lam=4))
CONSTRAINTS = [ConstraintSpec(mae=0.5), ConstraintSpec(er=50.0)]
SEEDS = (0, 1)
N_RUNS = len(CONSTRAINTS) * len(SEEDS)


def _genome(spec, nodes, outs):
    full = np.zeros((spec.n_n, 3), np.int32)
    full[: len(nodes)] = np.asarray(nodes, np.int32)
    return Genome(nodes=full, outs=np.asarray(outs, np.int32))


# --------------------------------------------------------------------------
# 1. digest properties
# --------------------------------------------------------------------------

SPEC = CGPSpec(n_i=2, n_o=1, n_n=4)


def test_digest_deterministic():
    g = _genome(SPEC, [[0, 1, AND]], [SPEC.n_i + 0])
    assert phenotype_digest(g, SPEC) == phenotype_digest(g, SPEC)
    assert len(phenotype_digest(g, SPEC)) == 16


def test_inactive_mutation_is_neutral():
    # node 0 = AND(in0, in1) is the only active node; nodes 1..3 are junk
    base = _genome(SPEC, [[0, 1, AND]], [SPEC.n_i + 0])
    mask = active_mask_np(base.nodes[None], base.outs[None], SPEC)[0]
    assert mask[SPEC.n_i + 0] and not mask[SPEC.n_i + 1 :].any()
    mutated = np.array(base.nodes)
    mutated[2] = [1, SPEC.n_i + 0, OR]  # legal but inactive
    assert (phenotype_digest(Genome(mutated, base.outs), SPEC)
            == phenotype_digest(base, SPEC))


def test_position_shift_same_phenotype_same_digest():
    # the same AND(in0, in1) subgraph living at node 0 vs node 2
    a = _genome(SPEC, [[0, 1, AND]], [SPEC.n_i + 0])
    b = _genome(SPEC, [[1, 0, OR], [0, 0, INV], [0, 1, AND]], [SPEC.n_i + 2])
    assert phenotype_digest(a, SPEC) == phenotype_digest(b, SPEC)


def test_active_change_changes_digest():
    a = _genome(SPEC, [[0, 1, AND]], [SPEC.n_i + 0])
    for nodes, outs in [
        ([[0, 1, OR]], [SPEC.n_i + 0]),    # function change
        ([[1, 0, AND]], [SPEC.n_i + 0]),   # commutative swap NOT folded
        ([[0, 0, AND]], [SPEC.n_i + 0]),   # fan-in change
        ([[0, 1, AND]], [0]),              # output rewired to an input
    ]:
        assert (phenotype_digest(_genome(SPEC, nodes, outs), SPEC)
                != phenotype_digest(a, SPEC))


def test_unary_second_fanin_ignored():
    a = _genome(SPEC, [[0, 0, INV]], [SPEC.n_i + 0])
    b = _genome(SPEC, [[0, 1, INV]], [SPEC.n_i + 0])
    assert gates.ONE_INPUT[INV] == 1
    assert phenotype_digest(a, SPEC) == phenotype_digest(b, SPEC)


def test_batched_digests_match_single():
    import jax

    spec = CGPSpec(n_i=4, n_o=4, n_n=30)
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    genomes = [random_genome(k, spec) for k in keys]
    nodes = np.stack([np.asarray(g.nodes) for g in genomes])
    outs = np.stack([np.asarray(g.outs) for g in genomes])
    batched = phenotype_digests(nodes, outs, spec)
    assert batched == [phenotype_digest(g, spec) for g in genomes]


# --------------------------------------------------------------------------
# 2. the LRU container
# --------------------------------------------------------------------------

def test_lru_bound_and_eviction_order():
    lru = PhenotypeLRU(max_entries=2)
    lru.put("a", 1)
    lru.put("b", 2)
    assert lru.get("a") == 1      # refresh "a": "b" is now least recent
    lru.put("c", 3)
    assert len(lru) == 2
    assert "b" not in lru and lru.get("b") is None
    assert lru.get("a") == 1 and lru.get("c") == 3
    assert lru.stats.evictions == 1 and lru.stats.inserts == 3


def test_lru_counters_and_hit_rate():
    st = CacheStats(candidates=10, evaluated=3)
    assert st.hit_rate == pytest.approx(0.7)
    assert CacheStats().hit_rate == 0.0
    d = st.as_dict()
    assert d["candidates"] == 10 and d["hit_rate"] == pytest.approx(0.7)
    assert d["overwrites"] == 0
    with pytest.raises(ValueError):
        PhenotypeLRU(max_entries=0)


def test_lru_overwrite_not_counted_as_insert():
    """Regression (ISSUE 7): ``put`` on an existing key used to bump
    ``inserts``, breaking the inserts == live entries + evictions
    accounting the hit-rate reports are sanity-checked against."""
    lru = PhenotypeLRU(max_entries=2)
    lru.put("a", 1)
    lru.put("a", 2)          # overwrite, NOT an insert
    lru.put("b", 3)
    lru.put("c", 4)          # evicts "a"
    st = lru.stats
    assert st.inserts == 3
    assert st.overwrites == 1
    assert st.evictions == 1
    # counter consistency: every insert is either still live or was evicted
    assert st.inserts == len(lru) + st.evictions
    assert lru.get("c") == 4 and lru.get("a") is None
    assert st.as_dict()["overwrites"] == 1


# --------------------------------------------------------------------------
# 3. acceptance: dedup on == dedup off, bit for bit
# --------------------------------------------------------------------------

def _sweep(dedup, results_dir=None):
    return run_sweep_batched(
        CFG, CONSTRAINTS, SEEDS,
        SweepConfig(chunk_size=N_RUNS, dedup=dedup, results_dir=results_dir))


@pytest.fixture(scope="module")
def off_on(tmp_path_factory):
    dirs = [str(tmp_path_factory.mktemp(f"dedup_{tag}"))
            for tag in ("off", "on")]
    return (_sweep(False, dirs[0]), _sweep(True, dirs[1]), dirs)


def test_dedup_records_bit_identical(off_on):
    off, on, _ = off_on
    assert on.completed == off.completed == N_RUNS
    for ra, rb in zip(off.records, on.records):
        assert ra.constraint == rb.constraint and ra.seed == rb.seed
        assert np.array_equal(ra.genome_nodes, rb.genome_nodes)
        assert np.array_equal(ra.genome_outs, rb.genome_outs)
        assert np.array_equal(ra.metrics, rb.metrics)
        assert ra.power_rel == rb.power_rel
        assert ra.feasible == rb.feasible


def test_dedup_arrays_and_history_bit_identical(off_on):
    off, on, _ = off_on
    for field in ("thresholds", "metrics", "power_rel", "feasible",
                  "best_fit", "hist_power_rel", "hist_fit", "hist_metrics",
                  "done_mask"):
        a, b = getattr(off, field), getattr(on, field)
        assert np.array_equal(a, b), field


def test_dedup_shards_bit_identical(off_on):
    off, on, dirs = off_on
    shards = sorted(f for f in os.listdir(dirs[0]) if f.endswith(".npz"))
    assert shards == sorted(f for f in os.listdir(dirs[1])
                            if f.endswith(".npz")) and shards
    for name in shards:
        with np.load(os.path.join(dirs[0], name)) as za, \
                np.load(os.path.join(dirs[1], name)) as zb:
            assert sorted(za.files) == sorted(zb.files)
            for key in za.files:
                assert np.array_equal(za[key], zb[key]), (name, key)


def test_dedup_hit_rate_nontrivial(off_on):
    off, on, _ = off_on
    assert off.dedup_stats is None
    st = on.dedup_stats
    assert st["candidates"] == N_RUNS * CFG.evolve.lam \
        * CFG.evolve.generations
    assert 0.0 < st["hit_rate"] < 1.0
    assert st["evaluated"] + st["lru_hits"] + st["dup_hits"] \
        == st["candidates"]
    assert st["hit_rate"] > 0.2  # neutral-heavy by construction


def test_dedup_knob_inherits_evolve_config():
    cfg = dataclasses.replace(
        CFG, evolve=dataclasses.replace(CFG.evolve, generations=2,
                                        dedup=True))
    res = run_sweep_batched(cfg, CONSTRAINTS[:1], (0,),
                            SweepConfig(chunk_size=1))
    assert res.dedup_stats is not None  # SweepConfig.dedup=None defers


def test_dedup_cache_size_validated():
    with pytest.raises(ValueError):
        SweepConfig(dedup_cache_size=0)
