#!/usr/bin/env python3
"""Time the plain reference on a width-12 multiplier, and keep what it read.

  python3 bench/tests/measure_reference.py --batch 32 --seed 7 --out DIR

evaluates the golden 12x12 multiplier (900 nodes) and ``batch - 1``
offspring mutated from it at rate 0.01 over the whole 2^24-row cube, in
the blocks of the default budget, on the device this process finds.  One
warm-up call of one genome compiles the block program first.  It writes
``DIR/b<batch>.npz`` (the genomes and every number the reference read of
them) and prints one JSON line: the block plan, the compile seconds, the
seconds of the call (device and host together) and of its simulation
alone, per genome, the device's peak bytes in use, the bytes the
compiler gives the block program, and the process's peak resident host
memory.  Run it in a fresh process for each batch, so that the peaks are
this call's.

  python3 bench/tests/measure_reference.py --compare DIR/b32.npz \\
      --budget 268435456

evaluates the same genomes again here, with another budget (smaller
blocks), and prints the largest gaps: integer readings must be equal,
float64 ones within 1e-12 relative.
"""
import argparse
import json
import os
import resource
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import reference as R  # noqa: E402

PROB = R.Problem("mul", 12, 900)
RATE = 0.01
FIELDS = ("metrics", "power", "error_mean", "error_std", "ones", "hist")


def offspring(prob: R.Problem, count: int, rate: float, seed: int):
    """The golden genome and ``count - 1`` offspring mutated from it, as
    the replay's first generation draws them."""
    nodes, outs = R.golden_genome(prob.kind, prob.width, prob.n_n)
    if count == 1:
        return nodes[None], outs[None]
    d = R.mutation_draws(prob, seed, 1, count - 1, rate)
    o_nodes = np.where(d["mask_nodes"][0], d["new_nodes"][0], nodes[None])
    o_outs = np.where(d["mask_outs"][0], d["new_outs"][0], outs[None])
    return (np.concatenate([nodes[None], o_nodes]),
            np.concatenate([outs[None], o_outs]))


def measure(batch: int, seed: int, out: str) -> dict:
    import jax
    nodes, outs = offspring(PROB, batch, RATE, seed)
    plan = R.block_plan(PROB, batch)
    t = time.perf_counter()
    R.evaluate(PROB, nodes[:1], outs[:1])
    compile_s = time.perf_counter() - t
    program = R._simulator(PROB.n_i, PROB.n_n, PROB.n_o, plan.words).lower(
        nodes[:plan.genomes], outs[:plan.genomes], np.int32(0)).compile()
    mem = program.memory_analysis()
    t = time.perf_counter()
    ev = R.evaluate(PROB, nodes, outs)
    call_s = time.perf_counter() - t
    t = time.perf_counter()
    for _ in R.simulate_blocks(PROB, nodes, outs, plan):
        pass
    simulate_s = time.perf_counter() - t
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    os.makedirs(out, exist_ok=True)
    np.savez(os.path.join(out, f"b{batch}.npz"), nodes=nodes, outs=outs,
             power_key=np.array(ev.power_key),
             **{f: getattr(ev, f) for f in FIELDS})
    return {"batch": batch, "seed": seed, "plan": plan.__dict__,
            "platform": dev.platform, "kind": dev.device_kind,
            "compile_s": compile_s, "call_s": call_s,
            "s_per_genome": call_s / batch, "simulate_s": simulate_s,
            "peak_bytes_in_use": peak,
            "program_bytes": {"temp": mem.temp_size_in_bytes,
                              "output": mem.output_size_in_bytes,
                              "argument": mem.argument_size_in_bytes},
            "host_maxrss_bytes": resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024,
            "errors": int((ev.metrics[:, R.ER] > 0).sum())}


def compare(path: str, budget: int) -> dict:
    z = np.load(path)
    ev = R.evaluate(PROB, z["nodes"], z["outs"], budget=budget)
    gaps = {}
    for f in FIELDS:
        a, b = np.asarray(getattr(ev, f), np.float64), z[f]
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.where(a == b, 0.0, np.abs(a - b) / np.abs(b))
        gaps[f] = float(rel.max())
    gaps["power_key_equal"] = list(ev.power_key) == list(z["power_key"])
    gaps["plan"] = R.block_plan(PROB, len(z["nodes"]), budget).__dict__
    return gaps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "experiments", "tmp", "reference"))
    ap.add_argument("--compare")
    ap.add_argument("--budget", type=int, default=R.BLOCK_BYTES)
    args = ap.parse_args()
    if args.compare:
        result = compare(args.compare, args.budget)
    else:
        result = measure(args.batch, args.seed, args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
