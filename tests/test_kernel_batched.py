"""Differential tests for the fused (runs × λ) batched CGP-evaluation kernel.

Three-way comparison per genome: ``cgp_sim_metrics_batched`` (genome axis on
the Pallas grid) vs the per-genome ``cgp_sim_metrics`` vs the pure-jnp oracle
``ref.cgp_eval_ref`` — across widths, gauss sigmas, block sizes and ragged R
(R not a multiple of the genome-axis pad width).  All integer-valued metric
partials and the per-gate popcounts must be BIT-identical (the exact
sums are rounded once to float32 on every path); ``rel_sum`` is a float32
division reduction that XLA may reassociate differently across program
shapes, so it gets allclose.

Also: the exhaustive ``_gate_eval`` truth-table property test and the
interpret-mode auto-detect regression test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import gates, golden as G, simulate as S
from repro.core.genome import CGPSpec, Genome, random_genome
from repro.kernels import cgp_sim, ops, ref

pytestmark = pytest.mark.kernel_diff

# bit-identical across batched kernel / per-genome kernel / jnp oracle
EXACT_FIELDS = ("abs_sum", "wce_max", "err_count", "sgn_sum", "acc0_bad",
                "hist", "count")


def _stacked_genomes(spec: CGPSpec, R: int, seed: int = 0) -> Genome:
    return jax.vmap(lambda k: random_genome(k, spec))(
        jax.random.split(jax.random.PRNGKey(seed), R))


@pytest.mark.parametrize("width,n_n,block,R,sigma", [
    (2, 40, 8, 3, 256.0),    # sub-word cube (W = 1), ragged R
    (2, 40, 1, 1, 32.0),     # degenerate single-genome batch
    (4, 120, 2, 5, 32.0),    # many cube blocks, ragged R (pad width 8)
    (4, 120, 8, 8, 48.0),    # W == bw, R exactly on the pad boundary
    (4, 120, 4, 9, 256.0),   # R just past the pad boundary
    (8, 150, 512, 2, 256.0),  # paper-scale cube, lane-aligned block
    (8, 150, 1024, 2, 256.0),  # (8, 128) wire blocks
    (8, 150, None, 2, 256.0),  # the default: the whole cube, (16, 128)
])
def test_batched_kernel_differential(width, n_n, block, R, sigma):
    spec = CGPSpec(n_i=2 * width, n_o=2 * width, n_n=n_n)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(width, "mul"))
    genomes = _stacked_genomes(spec, R, seed=width * 10 + R)

    # r_tile=8 forced: interpret mode would otherwise auto-select 1 and the
    # ragged-R rows above would never hit the genome-axis pad/slice path
    pb, popb = ops.cgp_eval_batched(genomes, spec, planes, gvals,
                                    gauss_sigma=sigma, block_words=block,
                                    r_tile=8)
    assert popb.shape == (R, n_n)
    for i in range(R):
        gi = jax.tree.map(lambda x: x[i], genomes)
        ps, pops = ops.cgp_eval(gi, spec, planes, gvals, gauss_sigma=sigma,
                                block_words=block)
        pr, popr = ref.cgp_eval_ref(gi, spec, planes, gvals, sigma)
        for name in EXACT_FIELDS:
            got = np.asarray(getattr(pb, name)[i])
            np.testing.assert_array_equal(
                got, np.asarray(getattr(ps, name)),
                err_msg=f"batched vs per-genome kernel: {name} @ genome {i}")
            np.testing.assert_array_equal(
                got, np.asarray(getattr(pr, name)),
                err_msg=f"batched kernel vs jnp oracle: {name} @ genome {i}")
        np.testing.assert_allclose(np.asarray(pb.rel_sum[i]),
                                   np.asarray(pr.rel_sum), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(popb[i]), np.asarray(pops))
        np.testing.assert_array_equal(np.asarray(popb[i]), np.asarray(popr))


@pytest.mark.parametrize("r_tile,R", [
    (8, 5),   # ragged: pad rows recompute the last genome, sliced off
    (8, 8),   # exactly one pad tile, no pad rows
    (4, 9),   # ragged just past a tile boundary
    (1, 3),   # no padding at all (the interpret-mode ops default)
])
def test_batched_raw_rows_match_per_genome_call(r_tile, R):
    """The raw (R, ·) accumulator rows equal R independent per-genome calls —
    including ``rel_sum``: identical kernel, identical block walk.  Covers
    ragged R against the genome-axis pad width ``r_tile``."""
    spec = CGPSpec(n_i=8, n_o=8, n_n=60)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(4, "mul"))
    genomes = _stacked_genomes(spec, R, seed=3)
    batched = cgp_sim.cgp_sim_metrics_batched(
        genomes.nodes, genomes.outs, planes, gvals, n_i=spec.n_i,
        n_n=spec.n_n, n_o=spec.n_o, gauss_sigma=32.0, block_words=4,
        r_tile=r_tile)
    for i in range(R):
        single = cgp_sim.cgp_sim_metrics(
            genomes.nodes[i], genomes.outs[i], planes, gvals, n_i=spec.n_i,
            n_n=spec.n_n, n_o=spec.n_o, gauss_sigma=32.0, block_words=4)
        for got, want, name in zip(batched, single,
                                   ("sums", "wce", "hist", "pops")):
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want),
                                          err_msg=f"{name} @ genome {i}")


# ----------------------------- _gate_eval ------------------------------------

_LANES = np.arange(32, dtype=np.uint64)
_A_BITS = (_LANES & 1).astype(np.int64)          # lane l: a = l & 1
_B_BITS = ((_LANES >> 1) & 1).astype(np.int64)   # lane l: b = (l >> 1) & 1


def _plane(bits: np.ndarray) -> jax.Array:
    """Pack 32 bits (lane-indexed) into one int32 word."""
    word = (bits.astype(np.uint64) << _LANES).sum() & np.uint64(0xFFFFFFFF)
    return jnp.asarray(np.array([word], np.uint32).view(np.int32)[0])


def _unpack(word) -> np.ndarray:
    return (np.asarray(word).view(np.uint32) >> _LANES.astype(np.uint32)) & 1


def test_gate_eval_all_16_truth_tables_exhaustive():
    """Every possible 4-bit truth table, over all 4 input-bit combinations
    (packed into one word so every combination is evaluated at once)."""
    a, b = _plane(_A_BITS), _plane(_B_BITS)
    packed_lo = sum(tt << (4 * tt) for tt in range(8))
    packed_hi = sum(tt << (4 * (tt - 8)) for tt in range(8, 16))
    for tt in range(16):
        packed, slot = (packed_lo, tt) if tt < 8 else (packed_hi, tt - 8)
        out = cgp_sim._gate_eval(jnp.int32(slot), a, b, tt_packed=packed)
        got = _unpack(out)
        want = (tt >> (_A_BITS + 2 * _B_BITS)) & 1
        np.testing.assert_array_equal(got, want, err_msg=f"truth table {tt}")


def test_gate_eval_library_gates_match_core_gates_tables():
    """The default TT_PACKED path reproduces core.gates truth tables for all
    library gates over all 4 input combinations."""
    a, b = _plane(_A_BITS), _plane(_B_BITS)
    for func in range(gates.N_FUNCS):
        got = _unpack(cgp_sim._gate_eval(jnp.int32(func), a, b))
        want = (gates.TRUTH_TABLES[func] >> (_A_BITS + 2 * _B_BITS)) & 1
        np.testing.assert_array_equal(got, want,
                                      err_msg=gates.GATE_NAMES[func])


@settings(max_examples=32, deadline=None)
@given(st.integers(0, gates.N_FUNCS - 1),
       st.integers(-(2 ** 31), 2 ** 31 - 1),
       st.integers(-(2 ** 31), 2 ** 31 - 1))
def test_gate_eval_random_words_match_numpy_oracle(func, wa, wb):
    a = np.array(wa, np.int64).astype(np.int32)
    b = np.array(wb, np.int64).astype(np.int32)
    got = np.asarray(cgp_sim._gate_eval(jnp.int32(func), jnp.asarray(a),
                                        jnp.asarray(b)))
    want = gates.gate_output_np(np.array(func), a, b)
    assert got == want, (gates.GATE_NAMES[func], hex(a & 0xFFFFFFFF))


# ----------------------- interpret auto-detect fix ---------------------------

def test_interpret_default_pinned_once(monkeypatch):
    """Regression (ISSUE 2): the interpret-mode default is resolved ONCE per
    process and cached.  A backend report that changes afterwards (e.g. a
    ``jax.config`` platform update between traces) must neither flip the
    mode of later traces nor even be consulted again during tracing —
    per-call resolution would bake inconsistent modes into cached traces."""
    saved = ops._INTERPRET_DEFAULT
    try:
        monkeypatch.setattr(ops, "_on_tpu", lambda: False)
        ops._INTERPRET_DEFAULT = None
        assert ops.default_interpret() is True
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # report flips
        assert ops.default_interpret() is True             # still pinned

        def boom():
            raise AssertionError("interpret default re-resolved in a trace")

        monkeypatch.setattr(ops, "_on_tpu", boom)
        spec = CGPSpec(n_i=4, n_o=4, n_n=10)
        planes = S.input_planes(spec.n_i)
        gvals = jnp.asarray(G.golden_values(2, "mul"))
        g = random_genome(jax.random.PRNGKey(0), spec)

        @jax.jit
        def probe(nodes, outs):
            partials, _ = ops.cgp_eval(Genome(nodes, outs), spec, planes,
                                       gvals)
            return partials.abs_sum

        probe(g.nodes, g.outs)  # raises iff cgp_eval re-resolves in-trace
    finally:
        ops._INTERPRET_DEFAULT = saved
