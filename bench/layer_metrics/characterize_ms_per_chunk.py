"""Sweep driver's final measurement: device time of the
``characterize_chunk`` programs per chunk."""
from bench import trace


def read(run):
    seconds, n = trace.seconds_named(run.in_window(run.trace.modules),
                                     r"^jit_characterize_chunk\b")
    if not n:
        return None
    return seconds / run.counters["chunks"] * 1e3
