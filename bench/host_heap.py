"""Recycle freed host memory inside the process (glibc's allocator).

The plain reference reduces each block of a width-12 cube (2^22 rows) on
``reference.HOST_THREADS`` threads through about half a GB of NumPy
temporaries, and a check reduces some 330 blocks.  In the sandbox of a v5e
chip host (gVisor, 40 GiB), memory that a process hands back (``munmap``,
``madvise(MADV_DONTNEED)``) is released to the machine far more slowly than
that: such a check lost about 265 MB a block and ran the host out of
memory.  ``keep()`` has glibc serve every allocation from the main arena's
heap, never by a mapping of its own, and never trim that heap, so the
temporaries of one block reuse the pages of the last.  No number computed
changes.
"""
from __future__ import annotations

import ctypes

M_TRIM_THRESHOLD, M_MMAP_MAX, M_ARENA_MAX = -1, -4, -8
SETTINGS = ((M_ARENA_MAX, 1), (M_MMAP_MAX, 0), (M_TRIM_THRESHOLD, 2**31 - 1))


def keep() -> bool:
    """Set this process's allocator so; False where it is not glibc's.

    Call it before the process starts threads: a thread that already has
    an arena of its own keeps it."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), \
        ctypes.c_int
    return all([mallopt(key, value) == 1 for key, value in SETTINGS])
