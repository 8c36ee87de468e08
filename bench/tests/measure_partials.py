#!/usr/bin/env python3
"""Hold the CGP kernel's integer partials of width-12 genomes against the
plain reference's, on the device this process finds.

  python3 bench/tests/measure_partials.py --seed 7

The golden 12x12 multiplier (900 nodes; ``--width``/``--nodes`` for a
smaller one), 30 offspring mutated from it at rate 0.01 and the golden
circuit with output bit 5 tied to 0 (its last, spare node made XOR(a, a))
go through the program's kernel in one dispatch
(``kernels.ops.cgp_eval_batched`` with the exact moments, as the sweep's
final measurement calls it) and through the reference's simulation
(``reference.simulate_blocks``), reduced here in Python integers: Σ|d|,
Σd, Σd², the inputs in error, the largest |d|, the inputs of golden 0
that read non-zero, the Gauss histogram and each gate's count of ones.
Prints one JSON line: per number, how many genomes differ (all must be
0), the largest sums, and the seconds of the kernel call after its
compilation.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import reference as R  # noqa: E402
from bench.tests.measure_reference import PROB, RATE, offspring  # noqa: E402

TIED_BIT = 5
XOR = int(np.flatnonzero(R.TRUTH == 0b0110)[0])
CHUNK = 1 << 10      # rows per int64 partial sum of d^2 (< 2^58)


def genomes(prob: R.Problem, seed: int) -> tuple[np.ndarray, np.ndarray]:
    nodes, outs = offspring(prob, 31, RATE, seed)
    tied_n, tied_o = nodes[0].copy(), outs[0].copy()
    tied_n[-1] = (0, 0, XOR)  # XOR(a, a) = 0, on a node the netlist spares
    tied_o[TIED_BIT] = prob.n_i + prob.n_n - 1
    return (np.concatenate([nodes, tied_n[None]]),
            np.concatenate([outs, tied_o[None]]))


def reference_partials(prob: R.Problem, nodes: np.ndarray,
                       outs: np.ndarray) -> list[dict]:
    edges, _ = R.gauss_bins(prob.gauss_sigma)
    plan = R.block_plan(prob, len(nodes))
    rows = plan.words * 32
    got = [{"abs": 0, "sgn": 0, "sq": 0, "err": 0, "wce": 0, "acc0": 0,
            "hist": np.zeros(len(edges) + 1, np.int64),
            "ones": np.zeros(prob.n_n, np.int64)} for _ in nodes]
    for at, block, ones, values in R.simulate_blocks(prob, nodes, outs,
                                                     plan):
        gold = R.golden_values(prob.kind, prob.width, block * rows,
                               (block + 1) * rows).astype(np.int64)
        for i, cand in zip(range(at.start, at.stop), values):
            d = gold - cand.astype(np.int64)
            g = got[i]
            g["abs"] += int(np.abs(d).sum())
            g["sgn"] += int(d.sum())
            g["sq"] += sum(int(s) for s in (d * d).reshape(-1, CHUNK).sum(1))
            g["err"] += int((d != 0).sum())
            g["wce"] = max(g["wce"], int(np.abs(d).max()))
            g["acc0"] += int(((gold == 0) & (cand != 0)).sum())
            idx = np.searchsorted(edges, d, side="right")
            g["hist"] += np.bincount(idx[d != 0], minlength=len(edges) + 1)
            g["ones"] += ones[i - at.start]
    return got


def kernel_partials(prob: R.Problem, nodes: np.ndarray, outs: np.ndarray
                    ) -> tuple[list[dict], float]:
    import jax
    import jax.numpy as jnp
    from repro.core import golden as G, simulate
    from repro.core.genome import CGPSpec, Genome
    from repro.kernels import cgp_sim, ops

    spec = CGPSpec(n_i=prob.n_i, n_o=prob.n_o, n_n=prob.n_n)
    planes = simulate.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(prob.width, prob.kind))
    call = jax.jit(lambda n, o: ops.cgp_eval_batched(
        Genome(n, o), spec, planes, gvals, prob.gauss_sigma, moments=True))
    jax.block_until_ready(call(nodes, outs))
    t = time.perf_counter()
    partials, pops = jax.block_until_ready(call(nodes, outs))
    seconds = time.perf_counter() - t
    value = lambda d: sum(int(v) << (cgp_sim.DIGIT_BITS * k)
                          for k, v in enumerate(np.asarray(d)))
    abs_d, sgn_d, sq_d = ops.exact_sums(partials.moments)
    got = [{"abs": value(abs_d[i]), "sgn": value(sgn_d[i]),
            "sq": value(sq_d[i]), "err": int(partials.err_count[i]),
            "wce": int(partials.wce_max[i]),
            "acc0": int(partials.acc0_bad[i]),
            "hist": np.asarray(partials.hist[i]).astype(np.int64),
            "ones": np.asarray(pops[i]).astype(np.int64)}
           for i in range(len(nodes))]
    return got, seconds


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--width", type=int, default=PROB.width)
    ap.add_argument("--nodes", type=int, default=PROB.n_n)
    args = ap.parse_args()
    prob = R.Problem("mul", args.width, args.nodes)
    nodes, outs = genomes(prob, args.seed)
    kern, seconds = kernel_partials(prob, nodes, outs)
    ref = reference_partials(prob, nodes, outs)
    differ = {k: sum(not np.array_equal(a[k], b[k]) for a, b in
                     zip(kern, ref)) for k in ref[0]}
    import jax
    print(json.dumps({
        "width": prob.width, "n_n": prob.n_n, "genomes": len(nodes),
        "device": jax.devices()[0].device_kind,
        "differ": differ, "all_equal": not any(differ.values()),
        "kernel_s": seconds,
        "largest": {k: max(r[k] for r in ref) for k in ("abs", "sq", "wce")},
        "in_error": sum(r["err"] > 0 for r in ref),
        "tied_bit_errors": ref[-1]["err"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
