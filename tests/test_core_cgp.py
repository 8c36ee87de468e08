"""Core CGP engine: gates, genomes, golden circuits, simulation, metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import gates, golden as G, metrics as M, simulate as S
from repro.core.genome import (CGPSpec, Genome, active_mask, active_mask_np,
                               critical_path_ps, random_genome,
                               validate_genome)
from repro.core.mutate import mutate
from repro.core.power import circuit_cost_from_probs


# ----------------------------- gates ----------------------------------------

def test_truth_tables_match_python_semantics():
    a = np.array([0, 1, 0, 1], dtype=np.int32)
    b = np.array([0, 0, 1, 1], dtype=np.int32)
    expect = {
        gates.BUF: a, gates.INV: 1 - a, gates.AND: a & b, gates.OR: a | b,
        gates.XOR: a ^ b, gates.NAND: 1 - (a & b), gates.NOR: 1 - (a | b),
        gates.XNOR: 1 - (a ^ b),
    }
    for f, want in expect.items():
        tt = gates.TRUTH_TABLES[f]
        got = (tt >> (a + 2 * b)) & 1
        assert (got == want).all(), gates.GATE_NAMES[f]


def test_tt_packed_consistent():
    for f in range(gates.N_FUNCS):
        assert (gates.TT_PACKED >> (4 * f)) & 0xF == gates.TRUTH_TABLES[f]


# ----------------------------- golden circuits ------------------------------

@pytest.mark.parametrize("width", [2, 3, 4, 6, 8])
def test_array_multiplier_exact(width):
    g, spec = G.array_multiplier(width)
    vals = np.asarray(S.simulate_values(g, spec))
    assert (vals == G.golden_values(width, "mul")).all()


@pytest.mark.parametrize("width", [2, 3, 5, 8])
def test_ripple_adder_exact(width):
    g, spec = G.ripple_carry_adder(width)
    vals = np.asarray(S.simulate_values(g, spec))
    assert (vals == G.golden_values(width, "add")).all()


def test_packed_sim_matches_numpy_oracle():
    spec = CGPSpec(n_i=8, n_o=8, n_n=60)
    for seed in range(5):
        g = random_genome(jax.random.PRNGKey(seed), spec)
        jv = np.asarray(S.simulate_values(g, spec))
        nv = S.simulate_values_np(g, spec)
        assert (jv == nv).all(), seed


# ----------------------------- metrics --------------------------------------

@given(st.integers(0, 2**31 - 1), st.integers(2, 24))
@settings(max_examples=15, deadline=None)
def test_metrics_match_numpy_oracle(seed, n_o):
    rng = np.random.default_rng(seed)
    n = 128
    hi = 1 << n_o
    g = rng.integers(0, hi, n).astype(np.int32)
    c = rng.integers(0, hi, n).astype(np.int32)
    got = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(c),
                                           n_o, gauss_sigma=16.0))
    want = M.metrics_np(g, c, n_o, gauss_sigma=16.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_o", [11, 20, 24])
def test_metrics_oracle_wide_operands_long_slices(n_o):
    """Width-10/12 value ranges on full-cube-sized slices: the regime where
    the historic byte-split _exact_sum silently overflowed float32 (its
    hi-column sums exceed 2^24) and the per-bit popcount path must take
    over (metrics.py ``_exact_sum``)."""
    rng = np.random.default_rng(n_o)
    n = 1 << 16
    hi = 1 << n_o
    g = rng.integers(0, hi, n).astype(np.int32)
    c = rng.integers(0, hi, n).astype(np.int32)
    got = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(c),
                                           n_o, gauss_sigma=256.0))
    want = M.metrics_np(g, c, n_o, gauss_sigma=256.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the raw sum itself: exact to <= n_bits ulp even at worst-case values
    v = rng.integers(hi // 2, hi, n).astype(np.int32)
    got_sum = float(M._exact_sum(jnp.asarray(v), n_o))
    want_sum = float(v.astype(np.int64).sum())
    assert abs(got_sum - want_sum) <= n_o * np.spacing(np.float32(want_sum))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_metric_invariants(seed):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, 256, 64).astype(np.int32)
    c = rng.integers(0, 256, 64).astype(np.int32)
    m = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(c), 8))
    assert m[M.MAE] <= m[M.WCE] + 1e-5          # mean |e| <= max |e|
    assert 0.0 <= m[M.ER] <= 100.0
    assert m[M.AVG] <= m[M.MAE] + 1e-5          # |mean e| <= mean |e|
    if (g == c).all():
        assert m[M.ER] == 0 and m[M.WCE] == 0


def test_metrics_zero_for_identical():
    g = np.arange(256, dtype=np.int32)
    m = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(g), 8))
    assert (m[:5] == 0).all() and m[M.ACC0] == 1 and m[M.GAUSS] == 1


def test_finalize_metrics_empty_shard_no_nan():
    """Regression (ISSUE 7): count == 0 (an empty sampled/ragged shard
    partition) used to finalize to 0/0 = NaN vectors that poison fitness
    comparisons (NaN compares false against every threshold)."""
    p = M.error_partials(jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
                         16.0)
    assert int(p.count) == 0
    m = np.asarray(M.finalize_metrics(p, 8, 16.0))
    assert np.isfinite(m).all()
    assert (m[:5] == 0).all()           # all-zero sums / max(n, 1)
    se = np.asarray(M.metric_stderr(p, 8))
    assert np.isfinite(se).all() and (se == 0).all()


def test_metrics_np_gauss_slack_matches_finalize():
    """Regression (ISSUE 7): the NumPy oracle hard-coded gauss_slack = 1.0
    while ``finalize_metrics`` accepts a slack parameter — differential
    tests at non-default slack silently diverged.  The GAUSS verdict must
    agree between oracle and jnp path across the slack range, and the
    slack must actually flip the verdict somewhere."""
    rng = np.random.default_rng(3)
    g = rng.integers(0, 256, 512).astype(np.int32)
    # concentrated small errors: violates a tight N(0, 4) envelope head-on
    c = (g - rng.integers(1, 4, 512)).clip(0).astype(np.int32)
    sigma = 4.0
    verdicts = []
    for slack in (0.5, 1.0, 10.0, 1e4):
        p = M.error_partials(jnp.asarray(g), jnp.asarray(c), sigma)
        got = np.asarray(M.finalize_metrics(p, 8, sigma, gauss_slack=slack))
        want = M.metrics_np(g, c, 8, gauss_sigma=sigma, gauss_slack=slack)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=f"slack={slack}")
        verdicts.append(got[M.GAUSS])
    assert min(verdicts) == 0.0 and max(verdicts) == 1.0, \
        "slack sweep must flip the GAUSS verdict"


def test_acc0_detects_violation():
    g = np.zeros(64, dtype=np.int32)
    c = np.zeros(64, dtype=np.int32)
    c[3] = 7
    m = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(c), 8))
    assert m[M.ACC0] == 0


def test_gauss_envelope():
    """Paper Eq. (7): the error histogram (zeros excluded, scaled to all 2^n
    inputs) must stay below the N(0,σ) envelope — so only a SMALL set of
    inputs may carry errors, and large errors only in Gaussian-tail numbers."""
    rng = np.random.default_rng(0)
    n = 4096
    g = rng.integers(100, 200, n).astype(np.int32)
    # 10% of inputs carry small gaussian errors -> fits the sigma=16 envelope
    c = g.copy()
    idx = rng.choice(n, n // 10, replace=False)
    c[idx] = (g[idx] - np.clip(rng.normal(0, 4, idx.size).round(),
                               -40, 40)).astype(np.int32)
    m = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(c), 8,
                                         gauss_sigma=16.0))
    assert m[M.GAUSS] == 1
    # errors on EVERY input must violate (center bins exceed envelope mass)
    c2 = (g + rng.integers(1, 8, n)).astype(np.int32)
    m2 = np.asarray(M.metrics_from_values(jnp.asarray(g), jnp.asarray(c2), 8,
                                          gauss_sigma=4.0))
    assert m2[M.GAUSS] == 0


# ----------------------------- genome ops -----------------------------------

def _bruteforce_mask(nodes, outs, spec):
    """Wires reachable from the outputs, by a depth-first walk."""
    want = np.zeros(spec.n_wires, bool)
    stack = list(outs)
    while stack:
        w = stack.pop()
        if want[w]:
            continue
        want[w] = True
        if w >= spec.n_i:
            a, b, f = nodes[w - spec.n_i]
            stack.append(int(a))
            if not gates.ONE_INPUT[f]:
                stack.append(int(b))
    return want


def test_active_mask_vs_bruteforce():
    spec = CGPSpec(n_i=6, n_o=4, n_n=40)
    for seed in range(5):
        g = random_genome(jax.random.PRNGKey(seed), spec)
        got = np.asarray(active_mask(g, spec))
        want = _bruteforce_mask(np.asarray(g.nodes), np.asarray(g.outs), spec)
        assert (got == want).all(), seed


KINDS = ("random", "unary", "sparse", "mixed")


def _paper_batch(kind, spec, n=256):
    """``n`` genomes at ``spec``: random, rich in one-input gates, sparse
    (outputs mostly on primary inputs), or a third of each."""
    rng = np.random.default_rng(KINDS.index(kind))
    keys = jax.random.split(jax.random.PRNGKey(int(rng.integers(2**31))), n)
    g = jax.vmap(lambda k: random_genome(k, spec))(keys)
    nodes, outs = np.array(g.nodes), np.array(g.outs)
    third = n // 3
    unary = {"unary": range(n), "mixed": range(third, 2 * third)}
    sparse = {"sparse": range(n), "mixed": range(2 * third, n)}
    for r in unary.get(kind, ()):  # four gates in five take one input
        pick = rng.random(spec.n_n) < 0.8
        nodes[r, pick, 2] = rng.choice(np.flatnonzero(gates.ONE_INPUT),
                                       pick.sum())
    for r in sparse.get(kind, ()):  # every other one keeps an output on a node
        outs[r] = rng.integers(0, spec.n_i, spec.n_o)
        if r % 2:
            outs[r, rng.integers(spec.n_o)] = rng.choice(
                [spec.n_wires - 1, rng.integers(spec.n_i, spec.n_wires)])
    return nodes, outs


@pytest.mark.parametrize("kind", KINDS)
def test_active_mask_vmapped_paper_shape(kind):
    """``vmap(active_mask)`` over 256 genomes at the paper's shape equals the
    host twin and the walk, gene for gene."""
    spec = CGPSpec(n_i=16, n_o=16, n_n=400)
    nodes, outs = _paper_batch(kind, spec)
    got = np.asarray(jax.jit(jax.vmap(lambda g: active_mask(g, spec)))(
        Genome(jnp.asarray(nodes), jnp.asarray(outs))))
    assert got.shape == (256, spec.n_wires) and got.dtype == bool
    np.testing.assert_array_equal(got, active_mask_np(nodes, outs, spec))
    for r in range(len(nodes)):
        np.testing.assert_array_equal(
            got[r], _bruteforce_mask(nodes[r], outs[r], spec), err_msg=str(r))


@given(st.integers(0, 2**31 - 1), st.floats(0.01, 0.5))
@settings(max_examples=25, deadline=None)
def test_mutation_preserves_legality(seed, rate):
    spec = CGPSpec(n_i=8, n_o=8, n_n=30)
    key = jax.random.PRNGKey(seed)
    g = random_genome(key, spec)
    for i in range(3):
        g = mutate(jax.random.fold_in(key, i), g, spec, rate)
    assert validate_genome(g, spec)


def test_critical_path_positive_and_monotone():
    g, spec = G.array_multiplier(4)
    d_mult = float(critical_path_ps(g, spec))
    a, spec_a = G.ripple_carry_adder(4)
    d_add = float(critical_path_ps(a, spec_a))
    assert d_mult > d_add > 0  # multiplier is deeper than adder


# ----------------------------- power model ----------------------------------

def test_power_drops_when_outputs_truncated():
    g, spec = G.array_multiplier(4)
    planes = S.input_planes(spec.n_i)
    wires = S.simulate_planes(g, spec, planes)
    probs = S.signal_probabilities(wires[spec.n_i:], spec.n_inputs_total)
    full = circuit_cost_from_probs(g, spec, probs)
    # truncate: lowest two outputs wired to a constant-0 node -> fewer active
    import jax.numpy as jnp
    nodes = g.nodes
    const0_idx = spec.n_i  # node 0 made XOR(in0,in0) = 0
    nodes = nodes.at[0].set(jnp.asarray([0, 0, gates.XOR], jnp.int32))
    trunc = Genome(nodes, g.outs.at[0].set(const0_idx).at[1].set(const0_idx))
    wires_t = S.simulate_planes(trunc, spec, planes)
    probs_t = S.signal_probabilities(wires_t[spec.n_i:], spec.n_inputs_total)
    cut = circuit_cost_from_probs(trunc, spec, probs_t)
    assert float(cut.power) < float(full.power)
    assert int(cut.n_active) < int(full.n_active)
    assert float(cut.area) < float(full.area)
