"""The plain reference evaluates the input cube in blocks (``block_plan``):
cut into several blocks and groups of genomes, it reads what it reads in
one block, and at width 12 what NumPy reads straight from the products."""
import numpy as np
import pytest

from bench import reference as R
from bench.tests.measure_reference import offspring

#: metrics whose every partial is an integer: they match to the last bit
EXACT = [R.MAE, R.WCE, R.ER, R.AVG, R.ACC0, R.GAUSS]
RTOL = 1e-12


def _budget(prob: R.Problem, genomes: int, words: int) -> int:
    return sum(R.call_bytes(prob, genomes, words).values())


@pytest.mark.parametrize("split", ["groups", "groups_and_blocks"])
@pytest.mark.parametrize("kind, width, n_n", [
    ("mul", 5, 150), ("mul", 6, 200), ("mul", 7, 280), ("mul", 8, 400),
    ("add", 5, 40), ("add", 6, 50), ("add", 7, 60), ("add", 8, 100)])
def test_blocks_agree_with_one_block(kind, width, n_n, split):
    prob = R.Problem(kind, width, n_n, gauss_sigma=8.0)
    nodes, outs = offspring(prob, 5, 0.02, 11 * width + len(kind))
    words = prob.n_inputs // 32
    budget = (_budget(prob, 2, words) if split == "groups"
              else _budget(prob, 1, words // 8))
    plan = R.block_plan(prob, len(nodes), budget)
    # groups of 2 pad the batch of 5 with a copy; groups of 1 need none
    assert (plan.genomes, plan.blocks, plan.padded) == (
        (2, 1, 6) if split == "groups" else (1, 8, 5))
    assert R.block_plan(prob, len(nodes)).blocks == 1

    one = R.evaluate(prob, nodes, outs)
    cut = R.evaluate(prob, nodes, outs, budget=budget)
    # the golden genome reads no error, the offspring some
    assert not one.metrics[0, :R.ACC0].any()
    assert one.metrics[1:, R.ER].any()
    np.testing.assert_array_equal(cut.metrics[:, EXACT], one.metrics[:, EXACT])
    np.testing.assert_allclose(cut.metrics[:, R.MRE], one.metrics[:, R.MRE],
                               rtol=RTOL)
    np.testing.assert_array_equal(cut.ones, one.ones)
    np.testing.assert_array_equal(cut.hist, one.hist)
    np.testing.assert_array_equal(cut.active, one.active)
    assert cut.power_key == one.power_key
    np.testing.assert_array_equal(cut.power, one.power)
    np.testing.assert_array_equal(cut.error_mean, one.error_mean)
    np.testing.assert_allclose(cut.error_std, one.error_std, rtol=RTOL)


def test_blocks_keep_the_control_in_its_dtype():
    """In bfloat16 the combination of blocks is a bfloat16 sum too: it
    reads far from float64, cut or not."""
    prob = R.Problem("mul", 6, 200)
    nodes, outs = offspring(prob, 4, 0.02, 3)
    budget = _budget(prob, 1, prob.n_inputs // 32 // 4)
    exact = R.evaluate(prob, nodes, outs)
    for low in (R.evaluate(prob, nodes, outs, R.BF16),
                R.evaluate(prob, nodes, outs, R.BF16, budget)):
        gap = np.abs(low.power - exact.power) / exact.power
        assert gap.max() > 1e-3


def _products(width: int):
    x = np.arange(1 << (2 * width), dtype=np.int64)
    return (x & ((1 << width) - 1)) * (x >> width)


def test_width12_against_the_products():
    """The golden 12x12 multiplier, and the golden one with output bit k
    rewired to a constant-0 gate, against every metric worked out in NumPy
    from the 2^24 products (cut into 16 blocks to hold this test's
    memory)."""
    prob = R.Problem("mul", 12, 900)
    nodes, outs = R.golden_genome("mul", 12, 900)
    zero = int(np.flatnonzero((nodes[:, 2] == R.BUF) & (nodes[:, 0] == 0)
                              & (np.arange(900) > 700))[0])
    bits = [5, 23]
    all_nodes = np.repeat(nodes[None], 1 + len(bits), 0)
    all_outs = np.repeat(outs[None], 1 + len(bits), 0)
    for i, k in enumerate(bits, 1):
        all_nodes[i, zero] = (0, 0, R.XOR)
        all_outs[i, k] = prob.n_i + zero
    budget = _budget(prob, 1, 1 << 15)
    assert R.block_plan(prob, 3, budget).blocks == 16
    ev = R.evaluate(prob, all_nodes, all_outs, budget=budget)

    gold = _products(12)
    n, pct = gold.size, 100.0 / (1 << prob.n_o)
    edges, mass = R.gauss_bins(prob.gauss_sigma)
    for i, k in enumerate([None] + bits):
        d = gold & (1 << k) if k is not None else np.zeros_like(gold)
        cand = gold - d
        hist = np.bincount(np.digitize(d[d != 0], edges), minlength=10)
        want = [d.sum() / n * pct, d.max() * pct,
                100.0 * np.count_nonzero(d) / n,
                100.0 * (d / np.maximum(gold, 1)).sum() / n,
                abs(d.sum() / n) * pct,
                float(not np.any((gold == 0) & (cand != 0))),
                float(np.all(hist <= mass * n))]
        np.testing.assert_allclose(ev.metrics[i], want, rtol=RTOL, atol=0)
        np.testing.assert_array_equal(ev.metrics[i, EXACT],
                                      np.array(want)[EXACT])
        np.testing.assert_array_equal(ev.hist[i], hist)
        assert ev.error_mean[i] == d.sum() / n
        np.testing.assert_allclose(ev.error_std[i], d.std(), rtol=RTOL,
                                   atol=0)
        # every output gate is set by as many inputs as set its product bit
        for j, wire in enumerate(all_outs[i]):
            want_ones = 0 if j == k else int(((gold >> j) & 1).sum())
            assert ev.ones[i, wire - prob.n_i] == want_ones, (k, j)
    assert ev.metrics[1, R.WCE] == (1 << 5) * pct
    assert ev.metrics[2, R.WCE] == (1 << 23) * pct


@pytest.mark.parametrize("width, n_n, batch", [
    (12, 900, 32), (12, 900, 8), (12, 900, 1), (8, 400, 32), (8, 400, 128),
    (8, 400, 200)])
def test_plan_fits_the_budget(width, n_n, batch):
    """From the plan alone: no call holds more than ``BLOCK_BYTES``, the
    blocks tile the cube, and through width 8 each genome's cube is one
    block (a batch of up to 128 one call)."""
    prob = R.Problem("mul", width, n_n)
    plan = R.block_plan(prob, batch)
    held = R.call_bytes(prob, plan.genomes, plan.words)
    assert sum(held.values()) <= R.BLOCK_BYTES
    assert plan.blocks * plan.words * 32 == prob.n_inputs
    assert plan.padded >= batch and plan.padded % plan.genomes == 0
    if width <= 8:
        assert plan.blocks == 1
        assert plan.genomes == min(plan.padded, 128)
    else:
        assert (plan.genomes, plan.blocks) == (1, 4)
        assert plan.padded == batch
