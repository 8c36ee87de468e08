"""CGP kernel rate: gate-words of the window's candidate evaluations
(``work.gate_words``) per second of the kernel's summed device time."""
from bench import trace, work


def read(run):
    seconds, n = trace.seconds_named(run.in_window(run.trace.ops),
                                     work.KERNEL_OP)
    if not n:
        return None
    c = run.counters
    return work.gate_words(c["evaluations"], c["n_n"], c["n_inputs"]) \
        / seconds / 1e9
