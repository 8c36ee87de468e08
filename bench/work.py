"""The work the CGP kernel has to do, from shapes alone.

Exhaustive evaluation walks every gate of a candidate over every 32-input
word of the input cube, so one candidate costs ``n_n * 2^n_i / 32``
gate-words, however the kernel is laid out or dispatched.  Only the
candidates the search must evaluate count (runs x lambda x generations);
the golden circuit the program evaluates once more per chunk does not.
"""
from __future__ import annotations

#: the Pallas kernel's operation in a device trace (``kernels/cgp_sim``).
#: An operation's event is named by its HLO text, which names its operands
#: too, so only a name that starts with the kernel's is the kernel.
KERNEL_OP = r"^%cgp_sim"


def candidate_evaluations(runs: int, lam: int, generations: int) -> int:
    return runs * lam * generations


def gate_words(evaluations: int, n_n: int, n_inputs: int) -> int:
    """Gate-words of ``evaluations`` exhaustive candidate evaluations."""
    if n_inputs % 32:
        raise ValueError("the cube packs 32 inputs per word")
    return evaluations * n_n * (n_inputs // 32)
