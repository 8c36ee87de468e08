"""Differential parity tests for the cube-major evaluation layout and the
kernel-layout tuning subsystem (DESIGN.md §7).

The cube-major grid (cube axis outer, genome axis inner, the same
VMEM-resident accumulator) must be BIT-identical to the
genome-major grid — including the float32 ``rel_sum`` row, because both
layouts accumulate each genome's cube blocks in the same ascending order —
and bit-identical to the serial jnp oracle on every integer-exact field,
across widths × ragged R × block sizes.  Layout is a pure execution knob:
a sweep checkpointed under one layout resumes under the other with
identical results, and the cube-shard psum/pmax contract (DESIGN.md §6.4)
holds on the transposed grid too.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess

from repro.core import golden as G, simulate as S
from repro.core.genome import CGPSpec, Genome, random_genome
from repro.kernels import cgp_sim, ops, ref, tune

pytestmark = pytest.mark.kernel_diff

# bit-identical across layouts / kernels / the jnp oracle
EXACT_FIELDS = ("abs_sum", "wce_max", "err_count", "sgn_sum", "acc0_bad",
                "hist", "count")


def _stacked_genomes(spec: CGPSpec, R: int, seed: int = 0) -> Genome:
    return jax.vmap(lambda k: random_genome(k, spec))(
        jax.random.split(jax.random.PRNGKey(seed), R))


@pytest.mark.parametrize("width,n_n,block,R,sigma", [
    (2, 40, 8, 3, 256.0),    # sub-word cube (W = 1 block), ragged R
    (4, 120, 2, 5, 32.0),    # many cube blocks, ragged R (pad width 8)
    (4, 120, 8, 8, 48.0),    # W == bw, R exactly on the pad boundary
    (4, 120, 4, 9, 256.0),   # R just past the pad boundary
    (8, 150, 512, 2, 256.0),  # paper-scale cube, lane-aligned block
    (8, 150, 1024, 3, 256.0),  # (8, 128) wire blocks
    (8, 150, 2048, 2, 32.0),   # the whole cube, (16, 128)
])
def test_cube_major_bit_identical_to_genome_major(width, n_n, block, R,
                                                  sigma):
    """Raw accumulator outputs match across layouts bit-for-bit (ALL four
    arrays, including the float32 rel_sum row of ``sums``: identical
    per-genome block order), with the genome-axis pad path forced."""
    spec = CGPSpec(n_i=2 * width, n_o=2 * width, n_n=n_n)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(width, "mul"))
    genomes = _stacked_genomes(spec, R, seed=width * 100 + R)
    kw = dict(n_i=spec.n_i, n_n=spec.n_n, n_o=spec.n_o, gauss_sigma=sigma,
              block_words=block, r_tile=8)
    gm = cgp_sim.cgp_sim_metrics_batched(
        genomes.nodes, genomes.outs, planes, gvals, layout="genome_major",
        **kw)
    cm = cgp_sim.cgp_sim_metrics_batched(
        genomes.nodes, genomes.outs, planes, gvals, layout="cube_major",
        **kw)
    for got, want, name in zip(cm, gm, ("sums", "wce", "hist", "pops")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"cube vs genome major: {name}")

    # ... and against the serial jnp oracle per genome (exact fields)
    pc, popc = ops.cgp_eval_batched(genomes, spec, planes, gvals,
                                    gauss_sigma=sigma, block_words=block,
                                    layout="cube_major")
    for i in range(R):
        gi = jax.tree.map(lambda x: x[i], genomes)
        pr, popr = ref.cgp_eval_ref(gi, spec, planes, gvals, sigma)
        for name in EXACT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(pc, name)[i]),
                np.asarray(getattr(pr, name)),
                err_msg=f"cube-major vs jnp oracle: {name} @ genome {i}")
        np.testing.assert_allclose(np.asarray(pc.rel_sum[i]),
                                   np.asarray(pr.rel_sum), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(popc[i]), np.asarray(popr))


def test_rejects_unknown_layout():
    spec = CGPSpec(n_i=4, n_o=4, n_n=10)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(2, "mul"))
    g = _stacked_genomes(spec, 1)
    with pytest.raises(ValueError, match="layout"):
        cgp_sim.cgp_sim_metrics_batched(
            g.nodes, g.outs, planes, gvals, n_i=spec.n_i, n_n=spec.n_n,
            n_o=spec.n_o, layout="auto")  # "auto" resolves upstream only


# --------------------------------------------------------------------------
# Sweep-level parity: cross-layout checkpoint resume
# --------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get("REPRO_TEST_BACKEND") == "jnp",
                    reason="layout is a pallas-path knob; runs in the "
                           "pallas CI legs")
def test_checkpoint_resume_across_layouts(tmp_path):
    """A mid-sweep checkpoint written under genome-major resumes under
    cube-major (and the reverse) with results bit-identical to a
    single-layout sweep: layout is NOT part of the grid fingerprint."""
    from repro.core.evolve import EvolveConfig
    from repro.core.fitness import ConstraintSpec
    from repro.core.search import SearchConfig
    from repro.core.sweep import SweepConfig, run_sweep_batched

    cfg = SearchConfig(width=2, kind="add", n_n=40,
                       evolve=EvolveConfig(generations=40, lam=3,
                                           backend="pallas"))
    cons = [ConstraintSpec(mae=1.0), ConstraintSpec(er=50.0)]
    seeds = (0, 1)
    want = run_sweep_batched(cfg, cons, seeds,
                             SweepConfig(chunk_size=2,
                                         layout="genome_major"))

    for first, second in (("genome_major", "cube_major"),
                          ("cube_major", "genome_major")):
        ckpt = str(tmp_path / f"{first}-to-{second}")
        partial = run_sweep_batched(
            cfg, cons, seeds, SweepConfig(chunk_size=2, checkpoint_dir=ckpt,
                                          layout=first, max_chunks=1))
        assert partial.completed == 2
        resumed = run_sweep_batched(
            cfg, cons, seeds, SweepConfig(chunk_size=2, checkpoint_dir=ckpt,
                                          layout=second))
        assert resumed.completed == want.n_runs
        for ra, rb in zip(want.records, resumed.records):
            assert ra.constraint == rb.constraint and ra.seed == rb.seed
            assert (ra.genome_nodes == rb.genome_nodes).all()
            assert (ra.genome_outs == rb.genome_outs).all()
            assert ra.feasible == rb.feasible
        np.testing.assert_array_equal(want.hist_fit, resumed.hist_fit)


# --------------------------------------------------------------------------
# Cube-shard psum/pmax contract on the transposed grid (DESIGN.md §6.4)
# --------------------------------------------------------------------------

def test_sharded_cube_major_psum_contract():
    """Under input-space sharding the cube-major kernel combines per-genome
    accumulators across the mesh axis exactly like genome-major: integer
    fields bit-identical to the unsharded dispatch, the float rel_sum /
    rel_sq columns reassociation-close, and the two sharded layouts
    bit-identical to each other (identical shard-local block order +
    identical psum order)."""
    out = run_subprocess("""
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import golden as G, simulate as S
from repro.core.genome import CGPSpec, random_genome
from repro.kernels import cgp_sim

mesh = jax.make_mesh((2,), ('model',))
spec = CGPSpec(n_i=8, n_o=8, n_n=60)
planes = S.input_planes(spec.n_i)
gvals = jnp.asarray(G.golden_values(4, 'mul'))
genomes = jax.vmap(lambda k: random_genome(k, spec))(
    jax.random.split(jax.random.PRNGKey(1), 5))
kw = dict(n_i=spec.n_i, n_n=spec.n_n, n_o=spec.n_o, gauss_sigma=32.0,
          block_words=2, r_tile=8)
want = cgp_sim.cgp_sim_metrics_batched(
    genomes.nodes, genomes.outs, planes, gvals, layout='cube_major', **kw)

def sharded(layout):
    def local(nodes, outs, pln, gv):
        return cgp_sim.cgp_sim_metrics_batched_sharded(
            nodes, outs, pln, gv, axis_name='model', layout=layout, **kw)
    fn = jax.shard_map(local, mesh=mesh,
                   in_specs=(P(), P(), P(None, 'model'), P('model')),
                   out_specs=(P(), P(), P(), P()), check_vma=False)
    return fn(genomes.nodes, genomes.outs, planes, gvals)

got_cm, got_gm = sharded('cube_major'), sharded('genome_major')
FLOAT = [cgp_sim.REL_SUM, cgp_sim.REL_SQ]
for w, g, name in zip(want, got_cm, ('sums', 'wce', 'hist', 'pops')):
    w, g = np.asarray(w), np.asarray(g)
    if name == 'sums':
        np.testing.assert_allclose(g[:, FLOAT], w[:, FLOAT], rtol=1e-5)
        exact = [i for i in range(w.shape[1]) if i not in FLOAT]
        np.testing.assert_array_equal(g[:, exact], w[:, exact],
                                      err_msg=name)
    else:
        np.testing.assert_array_equal(g, w, err_msg=name)
for a, b, name in zip(got_cm, got_gm, ('sums', 'wce', 'hist', 'pops')):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                  err_msg='sharded layouts differ: ' + name)
print('SHARDED-CUBE-MAJOR-OK')
""", devices=2)
    assert "SHARDED-CUBE-MAJOR-OK" in out


# --------------------------------------------------------------------------
# Tuning subsystem (kernels/tune.py): table, resolution, the "auto" path
# --------------------------------------------------------------------------

def test_autotune_writes_table_and_resolves(tmp_path):
    path = str(tmp_path / "table.json")
    entry = tune.autotune(2, 3, n_n=20, reps=1, path=path)
    assert entry["layout"] in tune.LAYOUTS
    assert set(entry["seconds"]) == {
        v.key() for v in tune.default_variants(1, True)}
    with open(path) as f:
        table = json.load(f)
    assert table["version"] == tune.TABLE_VERSION
    assert tune.table_key(2, 3, entry["backend"]) in table["entries"]
    # exact hit
    v = tune.resolve_variant(2, 3, entry["backend"], path)
    assert dataclasses.astuple(v) == (
        entry["layout"], entry["block_words"], entry["r_tile"])
    # nearest-R fallback (same width+backend)
    assert tune.resolve_variant(2, 100, entry["backend"], path) == v
    # misses fall back to the conservative default
    assert tune.resolve_variant(9, 3, entry["backend"], path) \
        == tune.KernelVariant()
    assert tune.resolve_layout(2, 3, "some_other_backend", path) \
        == tune.DEFAULT_LAYOUT


def test_layout_auto_resolves_through_tuning_table(tmp_path, monkeypatch):
    """ops.cgp_eval_batched(layout="auto") dispatches the layout the tuning
    table picked for this (width, R, backend)."""
    path = str(tmp_path / "table.json")
    spec = CGPSpec(n_i=4, n_o=4, n_n=10)
    backend = tune.backend_key(True)  # interpret mode on this host
    tune.save_entry(2, 3, backend,
                    {"layout": "cube_major", "block_words": 1, "r_tile": 1},
                    path)
    monkeypatch.setenv(tune.TABLE_ENV, path)

    seen = []
    real = cgp_sim.cgp_sim_metrics_batched

    def recorder(*args, **kw):
        seen.append(kw.get("layout"))
        return real(*args, **kw)

    monkeypatch.setattr(cgp_sim, "cgp_sim_metrics_batched", recorder)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(2, "mul"))
    genomes = _stacked_genomes(spec, 3)
    ops.cgp_eval_batched(genomes, spec, planes, gvals, block_words=1)
    assert seen == ["cube_major"]

    # with no table behind the env var, "auto" falls back to genome-major
    monkeypatch.setenv(tune.TABLE_ENV, str(tmp_path / "absent.json"))
    seen.clear()
    ops.cgp_eval_batched(genomes, spec, planes, gvals, block_words=1)
    assert seen == ["genome_major"]
