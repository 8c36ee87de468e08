#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not part of a run).

  python3 bench/readings.py --workload mul8_paper.fig_grid \\
      --seeds 1,2,3,4,5,6,7,8,9,10,11,12

In one process on the chip: one warm-up sweep, then per seed the sweeps of
a whole window of the cell's traffic (the cell's own chunks), checked as a
run checks them (``check.compare``: the lower readings, from sound runs of
the program), and again with the control in the program's place
(``check.compare(..., control=True)``: the reference in bfloat16, the upper
readings), each judged by ``check.verdict`` against the configuration's
limits.  One JSON line per seed, then the largest program reading and the
smallest control reading of each number.
"""
import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
    sys.path[0] = ROOT

from bench import cells, check, run, traffic  # noqa: E402
from bench import reference as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    sys.path.insert(0, run.SRC)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    c = cell.config
    prog = run.Program(c, cell.mix.get("sweep", {}))
    prob = R.Problem(c["kind"], c["width"], c["n_n"])
    n_chunks = cell.window_chunks(cell.run_seconds)
    work = os.path.join(run.WORK_DIR, f"readings{os.getpid()}")
    high, low = {}, {}
    try:
        prog.sweep(traffic.warmup(cell.mix, 0, prog.chunk_size),
                   os.path.join(work, "warm"))
        for seed in (int(s) for s in args.seeds.split(",")):
            plan = traffic.sweeps(cell.mix, seed, n_chunks, prog.chunk_size)
            dirs = [os.path.join(work, f"{seed}_{i}")
                    for i in range(len(plan))]
            for sw, d in zip(plan, dirs):
                prog.sweep(sw, d)
            line = {"seed": seed, "chunks": n_chunks}
            for side, ctl in (("program", False), ("control", True)):
                nums, info = check.compare(
                    prob, c["generations"], c["lam"], c["mutation_rate"],
                    plan, dirs, seed, control=ctl)
                line[side] = {"correct": check.verdict(nums, c["limits"]),
                              "numbers": nums, "info": info}
            print(json.dumps(line), flush=True)
            for k in check.NUMBERS:
                v, u = line["program"]["numbers"][k], \
                    line["control"]["numbers"][k]
                high[k] = max(high.get(k, v), v)
                low[k] = min(low.get(k, u), u)
            for d in dirs:
                shutil.rmtree(d, ignore_errors=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "lower": high,
                      "upper": low}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
