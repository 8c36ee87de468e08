"""Plain reference of the circuit search, written from its specification.

Nothing here imports the program under test.  It restates, in float64
NumPy, what one run of the paper's (1+lambda) search must produce:

* the golden circuit: the textbook array multiplier or ripple-carry adder,
  laid out gate by gate as a CGP genome and padded with BUF(0) nodes;
* exhaustive evaluation of a genome over the whole 2^n_i input cube
  (bit-parallel on int32 words, jitted: integers only), in blocks of the
  cube that fit a fixed device budget (``BLOCK_BYTES``), the error metrics
  of paper Eq. 1-7 in percent, the uniform-input activity power model and
  the signed-error moments, combined from the blocks' partial results;
* Eq. 8/9 fitness, (1+lambda) selection with neutral drift, and point
  mutation drawn from the per-run ``PRNGKey(seed)`` stream, so that a run can
  be replayed generation by generation.

``dtype`` selects the precision of every floating-point quantity (terms and
sums alike).  float64 is the reference; ``bfloat16`` is the control that a
comparison must refuse.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import ml_dtypes
import numpy as np

BUF, INV, AND, OR, XOR, NAND, NOR, XNOR = range(8)
N_FUNCS = 8
#: truth table of each gate code: bit k is the output for inputs a + 2b == k
TRUTH = np.array([0b1010, 0b0101, 0b1000, 0b1110, 0b0110, 0b0111, 0b0001,
                  0b1001], np.int64)
#: gates whose second fan-in is ignored (it never becomes active)
ONE_INPUT = np.array([1, 1, 0, 0, 0, 0, 0, 0], bool)
#: activity model constants per gate code (FreePDK45-calibrated proxy):
#: switching energy in fJ per toggle and leakage in nW, stated in float32
SWITCH_FJ = np.float32([1.20, 0.70, 1.40, 1.40, 2.10, 1.00, 1.00, 2.10])
LEAK_NW = np.float32([18.0, 10.0, 22.0, 22.0, 36.0, 16.0, 16.0, 36.0])
LEAK_SCALE = 1e-3  # leakage weighed below dynamic power, as at 45 nm
F_CLK_GHZ = 1.0

MAE, WCE, ER, MRE, AVG, ACC0, GAUSS = range(7)
N_METRICS = 7
GAUSS_SIDE = 4  # histogram bins of width sigma over +-4 sigma, plus tails

BF16 = np.dtype(ml_dtypes.bfloat16)


# --------------------------------------------------------------------------
# Golden circuits
# --------------------------------------------------------------------------

class _Net:
    def __init__(self, n_i: int):
        self.n_i = n_i
        self.nodes: list[tuple[int, int, int]] = []

    def gate(self, func: int, a: int, b: int | None = None) -> int:
        self.nodes.append((a, a if b is None else b, func))
        return self.n_i + len(self.nodes) - 1

    def half_adder(self, a, b):
        return self.gate(XOR, a, b), self.gate(AND, a, b)

    def full_adder(self, a, b, c):
        s1 = self.gate(XOR, a, b)
        s = self.gate(XOR, s1, c)
        c1 = self.gate(AND, a, b)
        c2 = self.gate(AND, s1, c)
        return s, self.gate(OR, c1, c2)


def golden_genome(kind: str, width: int, n_n: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(nodes (n_n, 3), outs (n_o,)) int32 of the exact circuit.

    Inputs: operand a on wires 0..w-1 and b on w..2w-1, LSB first.  The
    adder is a half adder and a chain of full adders with the carry on a
    BUF; the multiplier ANDs the partial products a_j & b_i and reduces them
    row by row (half adders where a position has two terms, full adders
    where it has three), emitting one product bit per row.
    """
    w = width
    net = _Net(2 * w)
    if kind == "add":
        s, c = net.half_adder(0, w)
        outs = [s]
        for i in range(1, w):
            s, c = net.full_adder(i, w + i, c)
            outs.append(s)
        outs.append(net.gate(BUF, c))
    elif kind == "mul":
        pp = [[net.gate(AND, j, w + i) for j in range(w)] for i in range(w)]
        outs = [pp[0][0]]
        row = pp[0][1:]
        for i in range(1, w):
            new_row, carry = [], None
            for j in range(w):
                acc = row[j] if j < len(row) else None
                p = pp[i][j]
                if acc is None and carry is None:
                    s = p
                elif acc is None:
                    s, carry = net.half_adder(p, carry)
                elif carry is None:
                    s, carry = net.half_adder(p, acc)
                else:
                    s, carry = net.full_adder(p, acc, carry)
                new_row.append(s)
            outs.append(new_row[0])
            row = new_row[1:] + ([carry] if carry is not None else [])
        outs.extend(row)
        while len(outs) < 2 * w:
            outs.append(net.gate(XOR, 0, 0))  # constant 0
    else:
        raise ValueError(f"unknown circuit kind {kind!r}")
    if len(net.nodes) > n_n:
        raise ValueError(f"the golden {kind}{w} needs {len(net.nodes)} "
                         f"nodes, more than n_n={n_n}")
    nodes = net.nodes + [(0, 0, BUF)] * (n_n - len(net.nodes))
    return np.array(nodes, np.int32), np.array(outs, np.int32)


def golden_values(kind: str, width: int, start: int = 0,
                  stop: int | None = None) -> np.ndarray:
    """int64 exact result for every input x = a + 2^w * b of the cube, or
    of its inputs ``start`` to ``stop``."""
    x = np.arange(start, 1 << (2 * width) if stop is None else stop,
                  dtype=np.int64)
    a, b = x & ((1 << width) - 1), x >> width
    return a * b if kind == "mul" else a + b


# --------------------------------------------------------------------------
# Exhaustive evaluation
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Problem:
    kind: str
    width: int
    n_n: int
    gauss_sigma: float = 256.0

    @property
    def n_i(self) -> int:
        return 2 * self.width

    @property
    def n_o(self) -> int:
        return 2 * self.width if self.kind == "mul" else self.width + 1

    @property
    def n_inputs(self) -> int:
        return 1 << self.n_i


#: device bytes that one simulation call may hold: its wire array and the
#: unpacked output bits and values together (``call_bytes``).  1 GiB, a
#: sixteenth of a v5e chip: a width-8 cube (2^16 rows) holds 128 genomes of
#: 400 nodes in one call, and a width-12 cube (2^24 rows) of a 900-node
#: genome is cut into four blocks of 2^22 rows.
BLOCK_BYTES = 1 << 30
#: host threads that reduce the blocks of a cube to partial results, while
#: the device simulates the next (NumPy releases the interpreter lock in
#: its loops); at most twice as many blocks wait at once
HOST_THREADS = 8

#: input rows 0-4 of every word: bit l of row i is bit i of l
_LOW_PLANES = np.packbits(
    ((np.arange(32) >> np.arange(5)[:, None]) & 1).astype(np.uint8),
    axis=1, bitorder="little").view("<i4")[:, 0]


def call_bytes(prob: Problem, genomes: int, words: int) -> dict[str, int]:
    """Bytes of each int32 buffer of one simulation call of ``genomes``
    genomes on ``words`` input words (32 rows of the cube each)."""
    return {"wires": 4 * genomes * (prob.n_i + prob.n_n) * words,
            "bits": 4 * genomes * prob.n_o * words * 32,
            "values": 4 * genomes * words * 32}


@dataclass(frozen=True)
class BlockPlan:
    """How B genomes are simulated: in groups of ``genomes`` (the batch
    padded to ``padded``, a multiple of it), each on the cube in
    ``blocks`` blocks of ``words`` input words."""
    genomes: int
    words: int
    blocks: int
    padded: int


def block_plan(prob: Problem, batch: int, budget: int = BLOCK_BYTES
               ) -> BlockPlan:
    """The largest blocks whose buffers fit ``budget`` together.

    The batch, padded to a power of two so that few shapes compile, is
    halved into groups of genomes first, and only one genome's cube is cut
    into blocks of words: a group changes no number, a word block the order
    of the float sums over the cube.  So through width 8 every genome is
    one block, whatever the batch."""
    if prob.n_i < 5:
        raise ValueError("the reference packs at least 32 inputs per word")
    if prob.n_o > 31:
        raise ValueError("an output word of more than 31 bits is no int32")
    genomes, words = 1 << max(batch - 1, 0).bit_length(), prob.n_inputs // 32
    while sum(call_bytes(prob, genomes, words).values()) > budget:
        if genomes > 1:
            genomes //= 2
        elif words > 1:
            words //= 2
        else:
            raise ValueError(f"no block of one word fits {budget} bytes")
    return BlockPlan(genomes, words, prob.n_inputs // 32 // words,
                     -(-batch // genomes) * genomes)


@functools.lru_cache(maxsize=None)
def _simulator(n_i: int, n_n: int, n_o: int, words: int):
    """Jitted bit-parallel simulation of B genomes on the block of the cube
    that starts at input word ``first`` (integers only, so the device it
    runs on cannot change its result)."""
    import jax
    import jax.numpy as jnp

    low = jnp.asarray(_LOW_PLANES)[:, None]

    @jax.jit
    def run(nodes, outs, first):
        B = nodes.shape[0]
        rows = jnp.arange(B)
        # bit l of word k of row i is bit i of the input 32 k + l
        word = first + jnp.arange(words, dtype=jnp.int32)
        high = -((word >> jnp.arange(n_i - 5, dtype=jnp.int32)[:, None]) & 1)
        planes = jnp.concatenate([jnp.broadcast_to(low, (5, words)), high])
        tt = jnp.asarray(TRUTH, jnp.int32)[nodes[:, :, 2]]     # (B, n_n)
        wires = jnp.zeros((B, n_i + n_n, words), jnp.int32)
        wires = wires.at[:, :n_i].set(planes)

        def gate(wires, k):
            a = wires[rows, nodes[:, k, 0]]
            b = wires[rows, nodes[:, k, 1]]
            sel = [-((tt[:, k, None] >> j) & 1) for j in range(4)]
            out = ((~a & ~b & sel[0]) | (a & ~b & sel[1])
                   | (~a & b & sel[2]) | (a & b & sel[3]))
            return wires.at[:, n_i + k].set(out), None

        wires, _ = jax.lax.scan(gate, wires, jnp.arange(n_n))
        ones = jax.lax.population_count(wires[:, n_i:]).sum(axis=-1)
        out_planes = wires[rows[:, None], outs]                # (B, n_o, W)
        bits = (out_planes[..., None] >> jnp.arange(32)) & 1
        values = (bits << jnp.arange(n_o)[None, :, None, None]).sum(axis=1)
        return ones, values.reshape(B, words * 32)

    return run


def simulate_blocks(prob: Problem, nodes: np.ndarray, outs: np.ndarray,
                    plan: BlockPlan):
    """Simulate B genomes on the whole cube, block by block, as ``plan``
    (``block_plan`` of B) cuts it.

    Yields (genomes: the slice of the batch, block: its index on the cube,
    ones (g, n_n) int64: how many inputs of the block set each gate's
    output, values (g, rows) int32: the output word of each input of the
    block, the inputs ``block * rows`` on)."""
    nodes, outs = np.asarray(nodes, np.int32), np.asarray(outs, np.int32)
    B = len(nodes)
    pad = plan.padded - B
    nodes = np.concatenate([nodes, np.repeat(nodes[:1], pad, 0)])
    outs = np.concatenate([outs, np.repeat(outs[:1], pad, 0)])
    run = _simulator(prob.n_i, prob.n_n, prob.n_o, plan.words)
    for block in range(plan.blocks):
        for g in range(0, plan.padded, plan.genomes):
            group = slice(g, g + plan.genomes)
            real = slice(g, min(g + plan.genomes, B))
            ones, values = run(nodes[group], outs[group],
                               np.int32(block * plan.words))
            k = real.stop - real.start
            # copied here, so that this thread frees the host buffer of the
            # device's values: left to the host threads, the buffers of a
            # batch of 32 width-12 genomes filled a v5e host's 40 GiB
            yield (real, block, np.asarray(ones, np.int64)[:k],
                   np.asarray(values)[:k].copy())


def active_gates(prob: Problem, nodes: np.ndarray, outs: np.ndarray
                 ) -> np.ndarray:
    """(B, n_n) bool: gates that some primary output depends on."""
    nodes = np.asarray(nodes, np.int64)
    B, n_i = nodes.shape[0], prob.n_i
    act = np.zeros((B, n_i + prob.n_n), bool)
    rows = np.arange(B)
    act[rows[:, None], np.asarray(outs, np.int64)] = True
    for k in range(prob.n_n - 1, -1, -1):
        on = act[:, n_i + k]
        act[rows, nodes[:, k, 0]] |= on
        act[rows, nodes[:, k, 1]] |= on & ~ONE_INPUT[nodes[:, k, 2]]
    return act[:, n_i:]


def _sum(x: np.ndarray, dtype) -> np.ndarray:
    """Sum over the last axis with every partial sum held in ``dtype``."""
    return np.add.reduce(x.astype(dtype), axis=-1, dtype=dtype)


def gauss_bins(sigma: float) -> tuple[np.ndarray, np.ndarray]:
    """Bin edges of the Gauss_sigma histogram and the N(0, sigma) mass of
    each of its bins, the two open tails included."""
    edges = np.arange(-GAUSS_SIDE, GAUSS_SIDE + 1, dtype=np.float64) * sigma
    cdf = np.array([0.5 * (1 + math.erf(e / (sigma * math.sqrt(2))))
                    for e in edges])
    return edges, np.concatenate([[cdf[0]], np.diff(cdf), [1 - cdf[-1]]])


@dataclass
class Evaluation:
    """What the search reads of B evaluated genomes (float arrays in the
    evaluation's dtype, widened to float64)."""
    metrics: np.ndarray    # (B, N_METRICS)
    power: np.ndarray      # (B,) absolute activity power
    error_mean: np.ndarray  # (B,)
    error_std: np.ndarray  # (B,)
    active: np.ndarray | None = None  # (B, n_n) bool: the active gates
    #: per genome, the bytes of what the power model reads of each gate in
    #: place: active or not, its function, and its signal count up to
    #: complement (p and 1 - p switch alike).  Genomes alike here have the
    #: same power to the last bit in any program, whatever their wiring.
    power_key: list | None = None
    ones: np.ndarray | None = None  # (B, n_n) int64: inputs setting a gate
    hist: np.ndarray | None = None  # (B, 2 GAUSS_SIDE + 2) int64: of d != 0


def _block_partials(gold: np.ndarray, cand: np.ndarray, edges: np.ndarray,
                    dtype) -> tuple:
    """What one block of the cube gives of g genomes: the inputs in error,
    the largest |d|, whether no input of golden 0 reads non-zero, and the
    Gauss histogram (integers), and the sums of |d|, d, |d| / max(golden, 1)
    and M2, the squared deviations about the block's own mean (``dtype``).
    """
    f = lambda x: np.asarray(x, dtype)
    diff = gold - cand
    ad = np.abs(diff)
    nz = diff != 0
    idx = np.searchsorted(edges, diff, side="right")
    hist = np.stack([np.bincount(i[m], minlength=len(edges) + 1)
                     for i, m in zip(idx, nz)])
    s = _sum(diff, dtype)
    mean = s / f(diff.shape[1])
    sums = (_sum(ad, dtype), s, _sum(f(ad) / f(np.maximum(gold, 1)), dtype),
            _sum((f(diff) - mean[:, None]) ** 2, dtype))
    return (nz.sum(axis=1), ad.max(axis=1),
            ~np.any((gold == 0) & (cand != 0), axis=1), hist, sums)


def evaluate(prob: Problem, nodes: np.ndarray, outs: np.ndarray,
             dtype=np.float64, budget: int = BLOCK_BYTES) -> Evaluation:
    """Metrics (paper Eq. 1-7, magnitudes in percent of 2^n_o), power and
    signed-error moments of B genomes over the exhaustive cube.

    The cube is evaluated in the blocks of ``block_plan``: the device
    simulates a block while ``HOST_THREADS`` threads reduce earlier ones to
    partial results (``_block_partials``), and no (B, 2^n_i) array is kept.
    Counts, the largest error and the gates' signal counts are integers.
    The float sums are held in ``dtype`` in each block's partial and in
    their combination.  The variance combines each block's mean and M2 by
    the parallel form of Chan, Golub and LeVeque,
    M2 = sum M2_b + sum rows_b (mean_b - mean)^2: every term is
    non-negative, so float64 keeps it within a few ulps a block, where the
    sum of d^2 itself (up to 2^72 at width 12) would not be exact.  With
    one block every number is the whole cube's one-pass value."""
    nodes = np.asarray(nodes)
    B, n = len(nodes), prob.n_inputs
    plan = block_plan(prob, B, budget)
    rows = plan.words * 32
    f = lambda x: np.asarray(x, dtype)
    edges, mass = gauss_bins(prob.gauss_sigma)
    ones = np.zeros((B, prob.n_n), np.int64)
    wrong, wce = np.zeros(B, np.int64), np.zeros(B, np.int64)
    acc0 = np.ones(B, bool)
    hist = np.zeros((B, len(edges) + 1), np.int64)
    # per genome and block, in dtype: the sums of |d|, d, |d| / max(g, 1),
    # and M2 about the block's mean
    sums = np.zeros((4, B, plan.blocks), dtype)
    waiting = collections.deque()

    def take():
        at, block, future = waiting.popleft()
        b_wrong, b_wce, b_acc0, b_hist, b_sums = future.result()
        wrong[at] += b_wrong
        wce[at] = np.maximum(wce[at], b_wce)
        acc0[at] &= b_acc0
        hist[at] += b_hist
        for total, part in zip(sums, b_sums):
            total[at, block] = part

    with ThreadPoolExecutor(HOST_THREADS) as pool:
        gold, at_block = None, None
        for at, block, block_ones, cand in simulate_blocks(prob, nodes, outs,
                                                           plan):
            if block != at_block:
                gold, at_block = golden_values(
                    prob.kind, prob.width, block * rows,
                    (block + 1) * rows)[None, :], block
            ones[at] += block_ones
            waiting.append((at, block, pool.submit(
                _block_partials, gold, cand, edges, dtype)))
            if len(waiting) > 2 * HOST_THREADS:
                take()
        while waiting:
            take()

    abs_sum, d_sum, rel_sum, m2 = sums
    out_pct = f(100.0) / f(1 << prob.n_o)
    mean = _sum(d_sum, dtype) / f(n)
    metrics = np.stack([
        _sum(abs_sum, dtype) / f(n) * out_pct,
        f(wce) * out_pct,
        f(100.0) * f(wrong) / f(n),
        f(100.0) * _sum(rel_sum, dtype) / f(n),
        np.abs(mean) * out_pct,
        f(acc0),
        f(np.all(hist <= f(mass) * f(n), axis=1)),
    ], axis=1)
    spread = f(rows) * (d_sum / f(rows) - mean[:, None]) ** 2
    var = (_sum(m2, dtype) + _sum(spread, dtype)) / f(n)

    p = f(ones) / f(n)                                        # (B, n_n)
    act = active_gates(prob, nodes, outs)
    func = nodes[:, :, 2]
    dyn = f(act) * f(2.0) * p * (f(1.0) - p) * f(SWITCH_FJ)[func]
    leak = f(act) * f(LEAK_NW)[func]
    power = _sum(dyn, dtype) * f(F_CLK_GHZ) + _sum(leak, dtype) * f(LEAK_SCALE)

    read = np.where(act, func * (n + 1) + np.minimum(ones, n - ones), -1)
    return Evaluation(*(np.asarray(x, np.float64) for x in
                        (metrics, power, mean, np.sqrt(var))), active=act,
                      power_key=[r.tobytes() for r in read], ones=ones,
                      hist=hist)


def feasible(metrics: np.ndarray, thresholds: np.ndarray) -> np.ndarray:
    """Eq. 9: every magnitude metric at or under its threshold, and ACC0 and
    GAUSS at or over their required level (1 = must hold, -inf = free)."""
    lower = np.zeros(N_METRICS, bool)
    lower[[ACC0, GAUSS]] = True
    ok = np.where(lower, metrics >= thresholds, metrics <= thresholds)
    return ok.all(axis=-1)


def fitness(ev: Evaluation, thresholds: np.ndarray) -> np.ndarray:
    """Eq. 8: power where feasible, +inf elsewhere."""
    return np.where(feasible(ev.metrics, thresholds), ev.power, np.inf)


# --------------------------------------------------------------------------
# Replay of whole runs
# --------------------------------------------------------------------------

def mutation_draws(prob: Problem, seed: int, generations: int, lam: int,
                   rate: float) -> dict[str, np.ndarray]:
    """Every random draw of one run, from ``PRNGKey(seed)``.

    Per generation the run key splits into (next key, mutation key); the
    mutation key splits into lambda offspring keys, and each of those into
    six: gene masks for nodes and outputs (Bernoulli(rate)) and fresh
    in0 / in1 / function / output genes, fan-ins uniform below the node's
    own wire index.  An offspring is the parent with its masked genes
    replaced.  The draws do not depend on selection, so one call gives them
    for the whole run.
    """
    import jax
    new_nodes, mask_nodes, new_outs, mask_outs = jax.device_get(
        _drawer(prob.n_i, prob.n_n, prob.n_o, generations, lam, rate)(
            jax.random.PRNGKey(seed)))
    return {"new_nodes": new_nodes, "mask_nodes": mask_nodes,
            "new_outs": new_outs, "mask_outs": mask_outs}


@functools.lru_cache(maxsize=None)
def _drawer(n_i: int, n_n: int, n_o: int, generations: int, lam: int,
            rate: float):
    import jax
    import jax.numpy as jnp

    hi = n_i + jnp.arange(n_n, dtype=jnp.int32)

    def offspring(k):
        k_sel_n, k_sel_o, k_in0, k_in1, k_fn, k_out = jax.random.split(k, 6)
        genes = jnp.stack([jax.random.randint(k_in0, (n_n,), 0, hi),
                           jax.random.randint(k_in1, (n_n,), 0, hi),
                           jax.random.randint(k_fn, (n_n,), 0, N_FUNCS)],
                          axis=-1).astype(jnp.int32)
        return (genes, jax.random.bernoulli(k_sel_n, rate, (n_n, 3)),
                jax.random.randint(k_out, (n_o,), 0, n_i + n_n,
                                   dtype=jnp.int32),
                jax.random.bernoulli(k_sel_o, rate, (n_o,)))

    def generation(key, _):
        key, k_mut = jax.random.split(key)
        return key, jax.vmap(offspring)(jax.random.split(k_mut, lam))

    return jax.jit(lambda key: jax.lax.scan(generation, key, None,
                                            length=generations)[1])


@dataclass
class Replay:
    """A replayed run: final parent and best genomes, the best fitness, the
    parent's history, the generations at which float64 left more than one
    selection open (``ambiguous``), the most runs followed at once
    (``branches``), the forks at which the run that was kept took
    another selection than float64 alone makes (``steered``), and how many
    bests float rounding left open at the end (``open_bests``); whether
    offspring alike to the golden parent tied with it (``golden_ties``)."""
    parent_nodes: np.ndarray
    parent_outs: np.ndarray
    best_nodes: np.ndarray
    best_outs: np.ndarray
    best_fit: float
    hist_power_rel: np.ndarray   # (gens,)
    hist_metrics: np.ndarray     # (gens, N_METRICS)
    hist_fit: np.ndarray         # (gens,)
    ambiguous: int
    branches: int
    steered: list   # Fork per generation steered by the program's record
    open_bests: int = 1
    golden_ties: bool = True  # an offspring alike to the golden parent ties


def at_threshold(metrics: np.ndarray, thresholds: np.ndarray,
                 rtol: float) -> np.ndarray:
    """(B,) bool: a magnitude metric lies within ``rtol`` of its threshold,
    where float32 and float64 may decide feasibility apart."""
    magnitude = np.ones(N_METRICS, bool)
    magnitude[[ACC0, GAUSS]] = False
    close = np.abs(metrics - thresholds) <= rtol * np.abs(thresholds)
    return np.any(magnitude & np.isfinite(thresholds) & close, axis=-1)


def phenotype(nodes: np.ndarray, outs: np.ndarray, active: np.ndarray
              ) -> bytes:
    """The active gates in place, a one-input gate's unused fan-in left
    out: genomes alike here evaluate alike to the last bit."""
    key = np.where(active[:, None], nodes, -1)
    key[:, 1] = np.where(ONE_INPUT[nodes[:, 2]], -1, key[:, 1])
    return key.tobytes() + outs.tobytes()


def selections(fits: np.ndarray, power: np.ndarray, edge: np.ndarray,
               keys: list, parent_fit: float, parent_key: bytes,
               rtol: float, alike_ties: bool = True) -> list[int]:
    """Every selection that float rounding within ``rtol`` could make:
    offspring indices, and -1 for keeping the parent.

    The rule is the first offspring of least fitness, taken when at or
    under the parent's.  An offspring at a threshold (``edge``) may have
    the fitness of its power or +inf.  Equal fitness of genomes with other
    active gates, or gates elsewhere, is left open: float32 sums the same
    terms in another order.  Feasible genomes alike in power
    (``keys``: ``Evaluation.power_key`` with feasibility and ``edge``) tie
    exactly, unless
    ``alike_ties`` is false: then an offspring alike to its parent ranks
    above it, as one measured by another program may.
    """
    lo = np.where(edge, power, fits)
    hi = np.where(edge, np.inf, fits)
    cut = min(hi.min(), parent_fit) * (1 + rtol)
    out = [j for j in range(len(fits))
           if np.isfinite(lo[j]) and lo[j] <= cut
           and keys[j] not in keys[:j]
           and (alike_ties or keys[j] != parent_key)]
    surely_taken = [not edge[j] and (
        (alike_ties and keys[j] == parent_key)
        or (keys[j] != parent_key and fits[j] < parent_fit * (1 - rtol)))
        for j in range(len(fits))]
    if not any(surely_taken):
        out.append(-1)
    return out


@dataclass(frozen=True)
class Fork:
    """A generation at which a replay took ``chosen`` (an offspring index,
    -1 for the parent) where float64 alone takes ``plain``: the first
    offspring of least fitness, at or under the parent's."""
    generation: int
    chosen: int
    plain: int
    options: tuple
    parent_nodes: np.ndarray
    parent_outs: np.ndarray


@dataclass
class _Run:
    nodes: np.ndarray
    outs: np.ndarray
    key: bytes
    fit: float
    metrics: np.ndarray
    power: float
    best: tuple          # (nodes, outs, fitness) by float64 alone
    hist: list           # (relative power, metrics, fitness) per generation
    trail: tuple = ()    # Forks taken on the way
    #: every (nodes, outs, fitness) that float rounding leaves open as the
    #: best: parents since the last sure fall of the fitness whose own
    #: fall was within the rounding
    bests: tuple = ()
    golden: bool = True  # the parent is the golden one, measured apart

    def take(self, nodes, outs, key, metrics, power, g_power, rtol) -> "_Run":
        """This run after an offspring replaced its parent (so the
        offspring was feasible, its fitness its power).

        The best is the parent at the last strict fall of the fitness.
        A fall by more than ``rtol`` is sure; a smaller one float32 may
        make or not, unless the genomes are alike, which tie exactly.  The
        golden parent is measured by another program than its offspring,
        so an offspring alike to it ties only within the rounding."""
        best = (nodes, outs, power) if power < self.best[2] else self.best
        new = (nodes, outs, power)
        if power < self.fit * (1 - rtol):
            bests = (new,)
        elif key != self.key or self.golden:
            bests = self.bests + (new,)
        else:
            bests = self.bests
        return _Run(nodes, outs, key, power, metrics, power, best,
                    self.hist + [(power / g_power, metrics, power)],
                    self.trail, bests, False)

    def keep(self, g_power) -> "_Run":
        return dataclasses.replace(
            self, hist=self.hist + [(self.power / g_power, self.metrics,
                                     self.fit)])

    def best_as(self, nodes, outs) -> tuple | None:
        """The open best that is ``nodes``/``outs``, if one is."""
        return next((b for b in self.bests if np.array_equal(b[0], nodes)
                     and np.array_equal(b[1], outs)), None)

    def same(self, nodes, outs, best_nodes, best_outs) -> bool:
        return (np.array_equal(self.nodes, nodes)
                and np.array_equal(self.outs, outs)
                and self.best_as(best_nodes, best_outs) is not None)


def replay(prob: Problem, thresholds: np.ndarray, seed: int,
           generations: int, lam: int, rate: float, program: dict,
           tie_rtol: float, max_runs: int = 64) -> Replay:
    """Replay one run from the golden parent (``_replay``).

    The program measures the golden parent by another program than its
    offspring, so in float32 an offspring alike to the golden one may tie
    with it or rank above it, the same way in every run of a problem.  The
    replay takes the first (``golden_ties``), and where that does not end
    on the program's genomes, the second.
    """
    first = None
    for golden_ties in (True, False):
        rp = _replay(prob, thresholds, seed, generations, lam, rate,
                     program, tie_rtol, max_runs, golden_ties)
        if _ends_as(rp, program):
            return rp
        first = first or rp
    return first


def _ends_as(rp: Replay, program: dict) -> bool:
    return all(np.array_equal(getattr(rp, f), program[f]) for f in
               ("parent_nodes", "parent_outs", "best_nodes", "best_outs"))


def _evaluate_alike_once(prob: Problem, nodes: np.ndarray, outs: np.ndarray,
                         measured: dict) -> tuple[list, Evaluation]:
    """``Evaluation.power_key`` and ``evaluate`` of B genomes, each phenotype
    evaluated once: alike genomes evaluate alike to the last bit, so what
    was measured of a phenotype in ``measured`` is taken from there."""
    active = active_gates(prob, nodes, outs)
    keys = [phenotype(n, o, a) for n, o, a in zip(nodes, outs, active)]
    new = list({k: i for i, k in reversed(list(enumerate(keys)))
                if k not in measured}.values())
    if new:
        ev = evaluate(prob, nodes[new], outs[new])
        for j, i in enumerate(new):
            measured[keys[i]] = (ev.metrics[j], ev.power[j],
                                 ev.power_key[j])
    return [measured[k][2] for k in keys], Evaluation(
        np.stack([measured[k][0] for k in keys]),
        np.array([measured[k][1] for k in keys]), None, None, active)


def _replay(prob: Problem, thresholds: np.ndarray, seed: int,
            generations: int, lam: int, rate: float, program: dict,
            tie_rtol: float, max_runs: int, golden_ties: bool) -> Replay:
    """Replay one run from the golden parent.

    Each generation evaluates the lambda offspring; the first offspring of
    least fitness replaces the parent when its fitness is at or below the
    parent's (neutral drift), and becomes the best when strictly below the
    best (where the fall is within ``tie_rtol``, float32 may make it the
    best or not: ``_Run.take``).  Where float64 leaves more than one
    selection open
    (``selections``), the program's float32 may have made any of them: the
    replay follows each (up to ``max_runs`` at once) while its relative
    power stays within ``tie_rtol`` of the program's recorded parent
    (``program["hist_power_rel"]``), and returns the one that ends on the
    program's final genomes if one does, else the one nearest the record,
    with the best that the program returned if it is open, else the best by
    float64 alone.
    """
    d = mutation_draws(prob, seed, generations, lam, rate)
    nodes, outs = golden_genome(prob.kind, prob.width, prob.n_n)
    gold = evaluate(prob, nodes[None], outs[None])
    g_power = gold.power[0]
    fit0 = fitness(gold, thresholds)[0]
    record = program["hist_power_rel"]
    runs = [_Run(nodes, outs, gold.power_key[0] + b"\1\0", fit0,
                 gold.metrics[0], g_power, (nodes, outs, fit0), [],
                 bests=((nodes, outs, fit0),))]
    ambiguous, widest = 0, 1
    measured: dict[bytes, tuple] = {}
    for g in range(generations):
        o_nodes = np.concatenate([np.where(d["mask_nodes"][g],
                                           d["new_nodes"][g], r.nodes[None])
                                  for r in runs])
        o_outs = np.concatenate([np.where(d["mask_outs"][g],
                                          d["new_outs"][g], r.outs[None])
                                 for r in runs])
        power_keys, ev = _evaluate_alike_once(prob, o_nodes, o_outs,
                                              measured)
        fits = fitness(ev, thresholds)
        edge = at_threshold(ev.metrics, thresholds, tie_rtol)
        keys = [k + bytes([int(np.isfinite(f)), int(e)])
                for k, f, e in zip(power_keys, fits, edge)]
        nxt = []
        for b, r in enumerate(runs):
            at = slice(b * lam, (b + 1) * lam)
            options = selections(fits[at], ev.power[at], edge[at], keys[at],
                                 r.fit, r.key, tie_rtol,
                                 golden_ties or not r.golden)
            ambiguous += len(options) > 1
            plain = int(np.argmin(fits[at]))
            plain = plain if fits[at][plain] <= r.fit else -1
            for j in options:
                k = b * lam + j
                run = (r.keep(g_power) if j < 0 else
                       r.take(o_nodes[k], o_outs[k], keys[k],
                              ev.metrics[k], ev.power[k], g_power,
                              tie_rtol))
                if j != plain:
                    run.trail += (Fork(g, j, plain, tuple(options), r.nodes,
                                       r.outs),)
                nxt.append(run)
        gap = lambda r: abs(r.hist[-1][0] - record[g])
        nxt.sort(key=gap)
        kept = [r for r in nxt if gap(r) <= tie_rtol * abs(record[g])]
        runs, seen = [], set()
        for r in kept or nxt[:1]:
            sig = (r.nodes.tobytes(), r.outs.tobytes()) + tuple(
                b[0].tobytes() + b[1].tobytes() for b in r.bests)
            if sig not in seen and len(runs) < max_runs:
                seen.add(sig)
                runs.append(r)
        widest = max(widest, len(runs))
    end = next((r for r in runs if r.same(
        program["parent_nodes"], program["parent_outs"],
        program["best_nodes"], program["best_outs"])), runs[0])
    best = end.best_as(program["best_nodes"], program["best_outs"]) \
        or end.best
    hp, hm, hf = (np.array(x) for x in zip(*end.hist))
    return Replay(end.nodes, end.outs, best[0], best[1], float(best[2]),
                  hp, hm, hf, ambiguous, widest, list(end.trail),
                  len(end.bests), golden_ties)
