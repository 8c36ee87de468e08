"""The CGP kernel's integer partials are exact past float32's 2^24.

At widths 9 and 10 a genome's Σ|d|, Σd and Σd² pass 2^24 (Σd² passes
2^48), so a float32 total of byte slices would round them.
Random genomes run in interpret mode with small ``block_words`` so that
their cube spans many blocks and every combine carries; each partial is
held against an int64 NumPy oracle, integers exactly.  Then the final
measurement (``core.sweep.characterize_chunk``), now one kernel dispatch,
against ``metrics_np`` and float64 moments, on random genomes and on
errors that are a constant offset.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gates, golden as G, metrics as M, simulate as S
from repro.core.genome import CGPSpec, Genome, random_genome
from repro.core.power import circuit_cost_from_probs
from repro.core.sweep import characterize_chunk, finish_moments
from repro.kernels import cgp_sim, ops


def _genomes(spec: CGPSpec, R: int, seed: int) -> Genome:
    return jax.vmap(lambda k: random_genome(k, spec))(
        jax.random.split(jax.random.PRNGKey(seed), R))


def _wires_np(nodes: np.ndarray, spec: CGPSpec) -> np.ndarray:
    """(n_wires, 2^n_i) bool: every wire on every input, gate by gate."""
    xs = np.arange(1 << spec.n_i)
    wires = np.zeros((spec.n_wires, xs.size), bool)
    for i in range(spec.n_i):
        wires[i] = (xs >> i) & 1
    tt = np.asarray(gates.TRUTH_TABLES)
    for k, (a, b, f) in enumerate(nodes):
        wires[spec.n_i + k] = (tt[f] >> (wires[a] + 2 * wires[b])) & 1
    return wires


def _oracle(nodes, outs, spec, gold, edges):
    """int64 partials of one genome: Python ints where they pass 2^63."""
    wires = _wires_np(nodes, spec)
    cand = sum(wires[o].astype(np.int64) << k for k, o in enumerate(outs))
    d = gold.astype(np.int64) - cand
    nz = d != 0
    idx = np.searchsorted(edges, d, side="right")
    return {"abs": int(np.abs(d).sum()), "sgn": int(d.sum()),
            "sq": sum(int(v) * int(c) for v, c in
                      zip(*np.unique(d * d, return_counts=True))),
            "err": int(nz.sum()), "wce": int(np.abs(d).max()),
            "acc0": int(((gold == 0) & (cand != 0)).sum()),
            "hist": np.bincount(idx[nz], minlength=len(edges) + 1),
            "pops": wires[spec.n_i:].sum(axis=1), "count": d.size}


def _value(digits) -> int:
    return sum(int(v) << (cgp_sim.DIGIT_BITS * k)
               for k, v in enumerate(np.asarray(digits)))


@pytest.mark.parametrize("width,n_n,block", [
    (9, 24, 8),     # 1,024 blocks of 256 rows
    (10, 24, 16),   # 2,048 blocks of 512 rows
    (10, 24, 512),  # 64 blocks of 16,384 rows: block sums near 2^22
])
def test_kernel_partials_exact_past_2_24(width, n_n, block):
    spec = CGPSpec(n_i=2 * width, n_o=2 * width, n_n=n_n)
    planes = S.input_planes(spec.n_i)
    gold = G.golden_values(width, "mul")
    genomes = _genomes(spec, 3, seed=width * 100 + block)
    sums, wce, hist, pops = cgp_sim.cgp_sim_metrics_batched(
        genomes.nodes, genomes.outs, planes, jnp.asarray(gold),
        n_i=spec.n_i, n_n=spec.n_n, n_o=spec.n_o, block_words=block,
        r_tile=1, moments=True, interpret=True)
    p = ops._partials_from_sums(sums, wce, hist)
    abs_d, sgn_d, sq_d = ops.exact_sums(p.moments)
    edges = M.gauss_bin_edges(256.0)
    for i in range(3):
        want = _oracle(np.asarray(genomes.nodes[i]),
                       np.asarray(genomes.outs[i]), spec, gold, edges)
        # the float32 byte-split totals would have rounded these
        assert want["abs"] > 1 << 24 and want["sq"] > 1 << 48
        assert _value(abs_d[i]) == want["abs"]
        assert _value(sgn_d[i]) == want["sgn"]
        assert _value(sq_d[i]) == want["sq"]
        row = np.asarray(sums[i]).astype(np.int64)
        assert row[cgp_sim.ERR_CNT] == want["err"]
        assert row[cgp_sim.ACC0_BAD] == want["acc0"]
        assert row[cgp_sim.COUNT] == want["count"]
        assert int(wce[i, 0]) == want["wce"]
        np.testing.assert_array_equal(np.asarray(hist[i]).astype(np.int64),
                                      want["hist"])
        np.testing.assert_array_equal(np.asarray(pops[i]).astype(np.int64),
                                      want["pops"])
        # decoded once to float32: the exact totals, rounded once
        assert float(p.abs_sum[i]) == float(np.float32(want["abs"]))
        assert float(p.sgn_sum[i]) == float(np.float32(want["sgn"]))


def test_kernel_refuses_what_it_cannot_hold_exactly():
    spec = CGPSpec(n_i=8, n_o=8, n_n=10)
    g = _genomes(spec, 1, seed=0)
    planes = S.input_planes(8)
    gold = jnp.asarray(G.golden_values(4, "mul"))
    call = lambda **kw: cgp_sim.cgp_sim_metrics_batched(
        g.nodes, g.outs, planes, gold, n_i=8, n_n=10, interpret=True, **kw)
    with pytest.raises(ValueError, match="outputs"):
        call(n_o=25)
    wide = jnp.zeros((8, (1 << 24) // 32 * 2), jnp.int32)
    with pytest.raises(ValueError, match="rows"):
        cgp_sim.cgp_sim_metrics_batched(
            g.nodes, g.outs, wide, jnp.zeros(wide.shape[1] * 32, jnp.int32),
            n_i=8, n_n=10, n_o=8, interpret=True)


def _characterize_case(width: int, seed: int, offset: int = 0,
                       bump: bool = False):
    """characterize_chunk on the exact circuit and, without an offset, 3
    random genomes, against a golden vector shifted by ``offset`` (and one
    row by 1 more with ``bump``): the exact circuit's error is then that
    constant offset.  Returns the standard deviations."""
    gold_g, spec = G.array_multiplier(width)
    planes = S.input_planes(spec.n_i)
    gold = G.golden_values(width, "mul").astype(np.int64) + offset
    if bump:
        gold[gold.size // 3] += 1
    rand = _genomes(spec, 0 if offset else 3, seed=seed)
    nodes = jnp.concatenate([gold_g.nodes[None], rand.nodes])
    outs = jnp.concatenate([gold_g.outs[None], rand.outs])
    R = nodes.shape[0]
    thr = jnp.full((R, M.N_METRICS), jnp.inf).at[:, M.ACC0:].set(-jnp.inf)
    met, _, prel, feas, count, moments = characterize_chunk(
        spec, 256.0, nodes, outs, thr, planes, jnp.asarray(gold, jnp.int32),
        jnp.float32(1.0))
    mean, std = finish_moments(count, moments)
    for i in range(R):
        g = Genome(nodes[i], outs[i])
        wires = S.simulate_planes(g, spec, planes)
        cand = np.asarray(S.unpack_values(wires[g.outs])).astype(np.int64)
        np.testing.assert_allclose(
            np.asarray(met[i]), M.metrics_np(gold, cand, spec.n_o),
            rtol=1e-5, atol=1e-9, err_msg=f"metrics @ genome {i}")
        d = (gold - cand).astype(np.float64)
        assert mean[i] == np.float32(d.mean())
        assert std[i] == np.float32(d.std()), (i, std[i], d.std())
        probs = S.signal_probabilities(wires[spec.n_i:])
        want = circuit_cost_from_probs(g, spec, probs).power
        np.testing.assert_allclose(float(prel[i]), float(want), rtol=1e-6)
        assert bool(feas[i])
    return std


@pytest.mark.parametrize("width", [4, 5, 6, 7, 8, 10])
def test_characterize_chunk_matches_numpy_oracle(width):
    _characterize_case(width, seed=width)


@pytest.mark.parametrize("width,offset", [(6, 2000), (8, -60000),
                                          (10, 1 << 19)])
def test_characterize_chunk_constant_offset(width, offset):
    """The exact circuit against golden + offset: the error is the offset
    on every row, its standard deviation exactly 0 (a one-pass float32
    E[d²] - mean² would leave rounding noise of the offset's size); one
    row one further off gives sqrt((n - 1) / n²)."""
    assert _characterize_case(width, seed=1, offset=offset)[0] == 0.0
    n = 1 << (2 * width)
    std = _characterize_case(width, seed=2, offset=offset, bump=True)[0]
    assert std == np.float32(np.sqrt((n - 1) / n ** 2))
