"""Per-sweep set-up (``core/sweep.run_sweep_batched`` up to its first
chunk: ``core/search.problem_arrays``, thresholds and PRNG keys, the chunk
plan, buffers, the shard writer and its restore): the host spans
``sweep.prepare`` of the window, averaged over the sweeps."""


def read(run):
    spans = [e for e in run.in_window(run.trace.host)
             if e.name == "sweep.prepare"]
    if not spans:
        return None
    return sum(e.seconds for e in spans) / len(spans) * 1e3
