#!/usr/bin/env python3
"""Record a short traced run on a TPU chip, gzipped, as a test fixture.

  python3 bench/tests/record_trace.py --out add8_3gen_scoped.xplane.pb.gz

The adder cell cut to ``GENERATIONS`` generations, its window ``CHUNKS``
chunks of one sweep, seed ``SEED``: ``bench/run.measure`` runs it as a
``--trace 1`` run (a warm-up sweep, the window traced inside a
``bench.window`` span, the check, the readers) and the trace it wrote is
kept.  ``counters()`` gives the counts the readers read it with.
"""
import argparse
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench import cells, run, trace, work  # noqa: E402

WORKLOAD = "add8_evoapprox.fig_grid"
GENERATIONS = 3
CHUNKS = 2
SEED = 1


def cell() -> cells.Cell:
    c = cells.resolve(WORKLOAD)
    c.config = dict(c.config, generations=GENERATIONS)
    return c


def counters() -> dict:
    """What ``bench/run.measure`` hands the readers of the fixture."""
    c = cell().config
    runs = CHUNKS * run.Program(c, cell().mix.get("sweep", {})).chunk_size
    return {"chunks": CHUNKS, "generations": GENERATIONS, "runs": runs,
            "lam": c["lam"], "n_n": c["n_n"], "n_inputs": 1 << c["n_i"],
            "evaluations": work.candidate_evaluations(runs, c["lam"],
                                                      GENERATIONS)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    c = cell()
    seconds = c.run_seconds * CHUNKS / c.config["window_chunks"]
    assert c.window_chunks(seconds) == CHUNKS <= run.TRACE_CHUNKS
    # where bench/run.py puts a run's files, so the readers find the trace
    work_dir = os.path.join(run.WORK_DIR, str(os.getpid()))
    shutil.rmtree(work_dir, ignore_errors=True)
    try:
        result, _ = run.measure(c, SEED, seconds, True, device, work_dir)
        xplane = trace.find_xplane(os.path.join(work_dir, "trace"))
        with open(xplane, "rb") as src, gzip.open(args.out, "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    print(json.dumps(result["metrics"]))
    print(f"record_trace: {os.path.getsize(args.out)} bytes -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
