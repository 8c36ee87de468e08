"""Async commit pipeline + island migration determinism (DESIGN.md §11).

The background committer is execution-only: a sweep with ``async_commit=True``
must produce BYTE-identical shards, manifest and summaries to the synchronous
path, across the dedup and sampled/certify engine variants.  Migration is
result-changing but deterministic: ``migrate_every=0`` stays byte-identical
to the migration-less engine, and a migrating multi-pod sweep produces the
same bytes regardless of pod launch order (the import schedule is pinned by
the chunk plan, the merge rule is content-based).  The crash-consistency
half of the §11 harness lives in ``test_faults.py``.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import migrate as migrate_mod
from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.results import SweepResultReader
from repro.core.search import SearchConfig
from repro.core.sweep import (SweepConfig, grid_fingerprint,
                              run_sweep_batched, sweep_grid)

CFG = SearchConfig(width=2, kind="add", n_n=40,
                   evolve=EvolveConfig(generations=40, lam=3))
CONSTRAINTS = [ConstraintSpec(mae=1.0), ConstraintSpec(mae=2.0),
               ConstraintSpec(er=50.0)]
SEEDS = (0, 1)
N_RUNS = len(CONSTRAINTS) * len(SEEDS)  # chunk_size 2 -> 3 chunks


def _backend():
    env = os.environ.get("REPRO_TEST_BACKEND")
    return env if env in ("jnp", "pallas") else "jnp"


def _cfg(**evolve_kw):
    ev = dataclasses.replace(CFG.evolve, backend=_backend(), **evolve_kw)
    return dataclasses.replace(CFG, evolve=ev)


def _sweep(results_dir, cfg=None, **kw):
    sweep = SweepConfig(chunk_size=2, keep_history="summary",
                        results_dir=str(results_dir), **kw)
    return run_sweep_batched(cfg or _cfg(), CONSTRAINTS, SEEDS, sweep)


def _dir_bytes(d, prefix=("shard_", "migrants_", "manifest")):
    return {f: open(os.path.join(d, f), "rb").read()
            for f in os.listdir(d) if f.startswith(prefix)}


def _assert_dirs_identical(a, b):
    da, db = _dir_bytes(str(a)), _dir_bytes(str(b))
    assert sorted(da) == sorted(db)
    for name in da:
        assert da[name] == db[name], f"bytes differ: {name}"


# --------------------------------------------------------------------------
# Async commit: execution-only
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dedup", [False, True])
def test_async_commit_bit_identical(tmp_path, dedup):
    """async_commit=True commits the same files, byte for byte, as the
    synchronous path — shards, manifest and reader summaries — with the
    dedup engine variant on either side."""
    sync_d, async_d = tmp_path / "sync", tmp_path / "async"
    r_sync = _sweep(sync_d, dedup=dedup or None)
    r_async = _sweep(async_d, dedup=dedup or None, async_commit=True)
    assert r_sync.completed == r_async.completed == N_RUNS
    _assert_dirs_identical(sync_d, async_d)
    sa = SweepResultReader(str(sync_d)).summary()
    sb = SweepResultReader(str(async_d)).summary()
    for key in sa:
        np.testing.assert_array_equal(sa[key], sb[key])


def test_async_commit_sampled_certify_bit_identical(tmp_path):
    """The §9 sampled + §10 certify engine path is equally committer-
    agnostic (the escalation rewrites happen before the commit is handed
    over)."""
    cfg = _cfg(eval_mode="sampled", sample_size=256, certify=True,
               certify_budget=2)
    r_sync = _sweep(tmp_path / "sync", cfg=cfg)
    r_async = _sweep(tmp_path / "async", cfg=cfg, async_commit=True)
    assert r_sync.certify_stats == r_async.certify_stats
    _assert_dirs_identical(tmp_path / "sync", tmp_path / "async")


def test_async_commit_checkpoint_resume(tmp_path):
    """Checkpoints committed by the background worker are valid resume
    points: an interrupted async sweep continues from them and finishes
    with the same results as an uninterrupted synchronous one."""
    ck, res = str(tmp_path / "ck"), tmp_path / "plain"
    want = _sweep(res)
    sweep = SweepConfig(chunk_size=2, keep_history="summary",
                        checkpoint_dir=ck, async_commit=True)
    part = run_sweep_batched(_cfg(), CONSTRAINTS, SEEDS,
                             dataclasses.replace(sweep, max_chunks=2))
    assert 0 < part.completed < N_RUNS
    full = run_sweep_batched(_cfg(), CONSTRAINTS, SEEDS, sweep)
    assert full.completed == N_RUNS
    np.testing.assert_array_equal(full.metrics, want.metrics)
    np.testing.assert_array_equal(full.power_rel, want.power_rel)


def test_commit_depth_validated():
    with pytest.raises(ValueError, match="commit_depth"):
        SweepConfig(commit_depth=0)


# --------------------------------------------------------------------------
# Migration: off == byte-identical, on == deterministic
# --------------------------------------------------------------------------

def test_migration_off_fingerprint_and_bytes_unchanged(tmp_path):
    """migrate_every=0 (the default) leaves the grid fingerprint AND the
    committed bytes exactly those of the migration-less engine — the
    acceptance bit-identity of ISSUE 9."""
    grid = sweep_grid(CONSTRAINTS, SEEDS)
    base = grid_fingerprint(_cfg(), grid, "summary")
    assert grid_fingerprint(_cfg(), grid, "summary", migrate=None) == base
    on = grid_fingerprint(_cfg(), grid, "summary",
                          migrate={"every": 1, "n_pods": 1, "chunk_size": 2,
                                   "top_k": migrate_mod.MIGRATE_TOP_K})
    assert on != base
    _sweep(tmp_path / "a")
    _sweep(tmp_path / "b", migrate_every=0)
    _assert_dirs_identical(tmp_path / "a", tmp_path / "b")
    with open(tmp_path / "a" / "manifest.json") as f:
        assert json.load(f)["grid_fingerprint"] == base


def test_migration_config_validated():
    with pytest.raises(ValueError, match="results_dir"):
        SweepConfig(migrate_every=1)
    with pytest.raises(ValueError, match="migrate_every"):
        SweepConfig(migrate_every=-1)


def test_migration_single_pod_deterministic(tmp_path):
    """A migrating sweep re-run into a fresh directory reproduces every
    byte — shards AND migrant files — and reports its counters."""
    r1 = _sweep(tmp_path / "a", migrate_every=1)
    r2 = _sweep(tmp_path / "b", migrate_every=1)
    assert r1.completed == r2.completed == N_RUNS
    _assert_dirs_identical(tmp_path / "a", tmp_path / "b")
    assert r1.migrate_stats == r2.migrate_stats
    assert r1.migrate_stats["published"] == 3  # 3 chunks, period 1
    # epochs >= 1 import the previous epoch's published elites
    assert r1.migrate_stats["imported"] > 0


def test_migration_compose_dedup_bit_identical(tmp_path):
    """§8 dedup is still execution-only under migration: the folded seeded
    path produces identical bytes with the phenotype cache on or off."""
    _sweep(tmp_path / "plain", migrate_every=1)
    _sweep(tmp_path / "dedup", migrate_every=1, dedup=True)
    _assert_dirs_identical(tmp_path / "plain", tmp_path / "dedup")


def _two_pod_migrating(d, order):
    """Drive a 2-pod migrating sweep epoch-interleaved inside one process:
    each launch runs at most one epoch (max_chunks == period), so the pods
    alternate like concurrently-progressing processes would."""
    kw = dict(migrate_every=1, migrate_timeout=30.0, n_pods=2)
    done = {}
    for _ in range(4):  # 3 chunks split [2, 1] -> at most 4 single-epoch legs
        for pod in order:
            res = _sweep(d, pod_index=pod, max_chunks=1, **kw)
            done[pod] = res
            if all(r.completed == N_RUNS for r in done.values()) \
                    and len(done) == 2:
                return done
    return done


def test_migration_two_pods_pod_order_independent(tmp_path):
    """Two pods sharing a results_dir converge to the same bytes no matter
    which pod runs first — the import set is plan-pinned, the merge rule
    content-based (ISSUE 9 acceptance)."""
    a = _two_pod_migrating(tmp_path / "p01", order=(0, 1))
    b = _two_pod_migrating(tmp_path / "p10", order=(1, 0))
    assert a[0].completed == N_RUNS and b[0].completed == N_RUNS
    _assert_dirs_identical(tmp_path / "p01", tmp_path / "p10")
    # every pod published its complete epochs; elites flowed between pods
    names = os.listdir(tmp_path / "p01")
    assert any(n.startswith("migrants_pod0_") for n in names)
    assert any(n.startswith("migrants_pod1_") for n in names)


def test_migration_missing_peer_times_out(tmp_path):
    """An importer whose peer never published fails loudly (never silently
    skips the import — that would fork the deterministic results)."""
    # pod 0 owns plan positions {0, 1}: position 1 is epoch 1 and must wait
    # for pod 1's epoch-0 file, which no process ever writes
    with pytest.raises(RuntimeError, match="migrant file"):
        _sweep(tmp_path, pod_index=0, n_pods=2, migrate_every=1,
               migrate_timeout=0.3)


def test_migration_rejects_foreign_fingerprint(tmp_path):
    """A stale migrant file of a DIFFERENT grid sharing the directory is a
    config error, not silently-imported data."""
    mgr = migrate_mod.MigrationManager(str(tmp_path), pod=1, pod_lens=[2, 2],
                                       period=1, fingerprint="aaaa")
    mgr.maybe_publish(0, {"sigma": np.zeros((0,), np.float32),
                          "nodes": np.zeros((0, 4, 3), np.int32),
                          "outs": np.zeros((0, 2), np.int32),
                          "power_rel": np.zeros((0,), np.float32),
                          "digest": np.zeros((0, 16), np.uint8)})
    # pod_lens [0, 2]: only pod 1 publishes epoch 0, so the reader goes
    # straight to the stale file instead of waiting on a pod-0 one
    reader = migrate_mod.MigrationManager(str(tmp_path), pod=0,
                                          pod_lens=[0, 2], period=1,
                                          fingerprint="bbbb", timeout=1.0)
    with pytest.raises(ValueError, match="fingerprint"):
        reader.candidates(0, 0.0)
