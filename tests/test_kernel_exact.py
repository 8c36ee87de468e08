"""The CGP kernel's integer partials are exact past float32's 2^24.

At widths 9 and 10 a genome's Σ|d|, Σd and Σd² pass 2^24 (Σd² passes
2^48), so a float32 total of byte slices would round them.
Random genomes run in interpret mode with small ``block_words`` so that
their cube spans many blocks and every combine carries; each partial is
held against an int64 NumPy oracle, integers exactly.  The same oracle
holds every sublane-dense wire block, ``(1, L)`` to ``(16, 128)``, and
their integer outputs are bit-identical.  Then the final
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


def _assert_exact(out, genomes, spec, gold, moments) -> list[dict]:
    """A kernel call's outputs against the oracle, integers exactly: the
    carried digits of the exact sums, the counts, WCE, the histogram and
    the per-gate popcounts.  Returns the oracle's partials."""
    C = cgp_sim
    sums, wce, hist, pops = (np.asarray(a) for a in out)
    digits = lambda row, at, n: _value(row[at:at + n].astype(np.int64))
    wants = []
    for i in range(sums.shape[0]):
        want = _oracle(np.asarray(genomes.nodes[i]),
                       np.asarray(genomes.outs[i]), spec, gold,
                       M.gauss_bin_edges(256.0))
        row = sums[i]
        assert digits(row, C.ABS_DIGITS, C.SUM_DIGITS) == want["abs"]
        assert 2 * digits(row, C.POS_DIGITS, C.SUM_DIGITS) - want["abs"] \
            == want["sgn"]
        if moments:
            assert digits(row, C.SQ_DIGITS_AT, C.SQ_DIGITS) == want["sq"]
        assert int(row[C.ERR_CNT]) == want["err"]
        assert int(row[C.ACC0_BAD]) == want["acc0"]
        assert int(row[C.COUNT]) == want["count"]
        assert int(wce[i, 0]) == want["wce"]
        np.testing.assert_array_equal(hist[i].astype(np.int64), want["hist"])
        np.testing.assert_array_equal(pops[i].astype(np.int64), want["pops"])
        wants.append(want)
    return wants


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
    wants = _assert_exact((sums, wce, hist, pops), genomes, spec, gold,
                          moments=True)
    for i, want in enumerate(wants):
        # the float32 byte-split totals would have rounded these
        assert want["abs"] > 1 << 24 and want["sq"] > 1 << 48
        assert _value(abs_d[i]) == want["abs"]
        assert _value(sgn_d[i]) == want["sgn"]
        assert _value(sq_d[i]) == want["sq"]
        # decoded once to float32: the exact totals, rounded once
        assert float(p.abs_sum[i]) == float(np.float32(want["abs"]))
        assert float(p.sgn_sum[i]) == float(np.float32(want["sgn"]))


#: the float32 sums, whose order follows the block shape
FLOAT_SUMS = [cgp_sim.REL_SUM, cgp_sim.SQ_SUM, cgp_sim.REL_SQ]


@pytest.mark.parametrize("moments", [False, True])
@pytest.mark.parametrize("width,n_o,blocks", [
    # a cube under 128 words: one row of L = block words (S = 1)
    pytest.param(5, 10, (8, 32), id="S1"),
    # (1, 128), (4, 128), (8, 128), (16, 128); with the moments the
    # kernel carries, and blocks past 512 words are refused
    pytest.param(8, 16, (128, 512, 1024, 2048), id="w8-mul"),
    # the product's high byte (n_o 8): the moments' digits stay exact
    # over the whole cube, so the dense blocks take them too
    pytest.param(8, 8, (512, 1024, 2048), id="w8-high-byte"),
])
def test_dense_wire_blocks_exact_and_bit_identical(width, n_o, blocks,
                                                   moments):
    """Every wire block shape: the oracle's integers exactly, integer
    outputs bit-identical across blocks, the float32 sums equal up to their
    summation order."""
    spec = CGPSpec(n_i=2 * width, n_o=n_o, n_n=16)
    planes = S.input_planes(spec.n_i)
    gold = G.golden_values(width, "mul") >> (2 * width - n_o)
    genomes = _genomes(spec, 2, seed=width + n_o)
    W = planes.shape[1]
    first = None
    for bw in blocks:
        call = lambda: cgp_sim.cgp_sim_metrics_batched(
            genomes.nodes, genomes.outs, planes, jnp.asarray(gold),
            n_i=spec.n_i, n_n=spec.n_n, n_o=spec.n_o, block_words=bw,
            r_tile=1, moments=moments, interpret=True)
        if bw > cgp_sim.default_block_words(W, n_o, moments):
            with pytest.raises(ValueError, match="pass 2"):
                call()
            continue
        out = [np.asarray(a) for a in call()]
        _assert_exact(out, genomes, spec, gold, moments)
        ints = [np.delete(out[0], FLOAT_SUMS, axis=1)] + out[1:]
        if first is None:
            first = ints, out[0][:, FLOAT_SUMS]
            continue
        for got, want in zip(ints, first[0]):
            np.testing.assert_array_equal(got, want, err_msg=bw)
        # float32 sums of 2^16 terms, in another order
        np.testing.assert_allclose(out[0][:, FLOAT_SUMS], first[1],
                                   rtol=1e-4)


def test_block_follows_the_cube_and_the_carry():
    """The default block is the largest the digit bound allows: the whole
    2,048-word width-8 cube, which needs no carry, as (16, 128); 1,024
    words (8, 128) where the kernel carries; 512 (4, 128) with the
    moments.  A 2,048-word block is refused where the kernel carries."""
    assert cgp_sim.default_block_words(2048, 16, False) == 2048
    assert cgp_sim.default_block_words(1 << 19, 24, False) == 1024
    assert cgp_sim.default_block_words(2048, 16, True) == 512
    assert cgp_sim.default_block_words(1 << 19, 24, True) == 512
    assert cgp_sim.default_block_words(32, 10, False) == 32
    assert [cgp_sim.wire_shape(b) for b in (32, 128, 512, 1024, 2048)] == [
        (1, 32), (1, 128), (4, 128), (8, 128), (16, 128)]
    spec = CGPSpec(n_i=16, n_o=16, n_n=4)
    g = _genomes(spec, 1, seed=0)
    call = lambda planes, gold, **kw: cgp_sim.cgp_sim_metrics_batched(
        g.nodes, g.outs, planes, gold, n_i=16, n_n=4, n_o=16,
        block_words=2048, interpret=True, **kw)
    planes = S.input_planes(16)
    gold = jnp.asarray(G.golden_values(8, "mul"))
    assert int(call(planes, gold)[0][0, cgp_sim.COUNT]) == 1 << 16
    with pytest.raises(ValueError, match="block_words=2048"):
        call(planes, gold, moments=True)
    wide = jnp.zeros((16, 8192), jnp.int32)  # a width-9 cube: it carries
    with pytest.raises(ValueError, match="block_words=2048"):
        call(wide, jnp.zeros(8192 * 32, jnp.int32))


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
