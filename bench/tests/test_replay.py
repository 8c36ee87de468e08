"""Which bests a replay leaves open to float rounding.

The program keeps as its best the parent at the last strict fall of its
float32 fitness.  Where a taken offspring's float64 power lies within the
tie tolerance of its parent's, float32 may see a fall or not: the replay
leaves both bests open.  A sure fall closes them; a genome alike to its
(non-golden) parent ties exactly and opens nothing.  Genomes alike in what
the power model reads of every gate tie exactly, whatever their wiring.
"""
import numpy as np

from bench import reference as R

RTOL = 1e-5


def _genome(tag: int):
    return np.full((4, 3), tag, np.int32), np.full(2, tag, np.int32)


def _golden_run(power=100.0):
    n, o = _genome(0)
    return R._Run(n, o, b"gold", power, np.zeros(R.N_METRICS), power,
                  (n, o, power), [], bests=((n, o, power),))


def _take(run, tag, power, key=None):
    n, o = _genome(tag)
    return run.take(n, o, key or bytes([tag]), np.zeros(R.N_METRICS), power,
                    100.0, RTOL)


def _open(run):
    return [int(b[0][0, 0]) for b in run.bests]


def test_sure_fall_closes_the_bests():
    run = _take(_golden_run(), 1, 99.0)
    assert _open(run) == [1]
    assert int(run.best[0][0, 0]) == 1


def test_fall_within_rounding_leaves_both_open():
    run = _take(_take(_golden_run(), 1, 99.0), 2, 99.0 * (1 - RTOL / 10))
    assert _open(run) == [1, 2]
    # float64 alone takes the strict fall
    assert int(run.best[0][0, 0]) == 2
    # then a sure fall closes them again
    assert _open(_take(run, 3, 98.0)) == [3]


def test_equal_power_of_other_gates_is_open():
    run = _take(_take(_golden_run(), 1, 99.0), 2, 99.0)
    assert _open(run) == [1, 2]
    assert int(run.best[0][0, 0]) == 1


def test_alike_genome_ties_exactly():
    run = _take(_golden_run(), 1, 99.0, key=b"a")
    assert _open(_take(run, 2, 99.0, key=b"a")) == [1]


def test_golden_alike_offspring_is_open():
    # the golden parent is measured by another program than its offspring
    run = _take(_golden_run(), 1, 100.0, key=b"gold")
    assert _open(run) == [0, 1]


def test_same_finds_an_open_best():
    run = _take(_take(_golden_run(), 1, 99.0), 2, 99.0)
    n2, o2 = _genome(2)
    n1, o1 = _genome(1)
    n0, o0 = _genome(0)
    assert run.same(n2, o2, n1, o1) and run.same(n2, o2, n2, o2)
    assert not run.same(n2, o2, n0, o0)
    assert not run.same(n1, o1, n1, o1)


def test_power_key_ties_other_wiring():
    # an active XOR with its fan-ins swapped: another phenotype, the same
    # reading of every gate by the power model, so the same power key
    prob = R.Problem("add", 4, 24)
    nodes, outs = R.golden_genome(prob.kind, prob.width, prob.n_n)
    k = int(np.flatnonzero(nodes[:, 2] == R.XOR)[0])
    swapped = nodes.copy()
    swapped[k, :2] = nodes[k, 1::-1]
    ev = R.evaluate(prob, np.stack([nodes, swapped]), np.stack([outs, outs]))
    assert ev.power_key[0] == ev.power_key[1]
    assert ev.power[0] == ev.power[1]
    assert R.phenotype(nodes, outs, ev.active[0]) != \
        R.phenotype(swapped, outs, ev.active[1])
    # a gate of another function reads differently
    other = nodes.copy()
    other[k, 2] = R.XNOR
    assert R.evaluate(prob, other[None], outs[None]).power_key[0] \
        != ev.power_key[0]
