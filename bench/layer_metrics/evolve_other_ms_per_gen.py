"""Generation step outside the kernel (mutation, selection, the power
model's active-gate scan): device time of the ``evolve_chunk`` programs
less the kernel's, per generation of a chunk."""
from bench import trace, work


def read(run):
    program, n = trace.seconds_named(run.in_window(run.trace.modules),
                                     r"^jit_evolve_chunk\b")
    if not n:
        return None
    kernel, _ = trace.seconds_named(run.in_window(run.trace.ops),
                                    work.KERNEL_OP)
    c = run.counters
    return (program - kernel) / (c["chunks"] * c["generations"]) * 1e3
