"""Shard commit (``core/results.SweepResultWriter.write_chunk``): the host
spans ``sweep.commit`` of the window, on whichever thread ran them, per
chunk."""


def read(run):
    spans = [e for e in run.in_window(run.trace.host)
             if e.name == "sweep.commit"]
    if not spans:
        return None
    return sum(e.seconds for e in spans) / run.counters["chunks"] * 1e3
