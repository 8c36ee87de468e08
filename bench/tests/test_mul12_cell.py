"""The width-12 cell resolves from its files alone: its configuration, the
fig_grid mix, both end-to-end metrics and its two per-layer metrics, which
read what the width-8 readers read; the width-8 cells keep their lists; and
the host heap that its check's reference runs in."""
import os

import pytest

from bench import cells, run, trace, traffic
from bench.tests.conftest import CPU, tiny

CELL = "mul12_evoapprox.fig_grid"


def test_the_width12_cell_resolves():
    cell = cells.resolve(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic_name == "fig_grid"
    assert (c["kind"], c["width"], c["n_i"], c["n_o"], c["n_n"]) \
        == ("mul", 12, 24, 24, 900)
    assert (c["lam"], c["mutation_rate"], c["generations"]) == (8, 0.004, 6)
    assert c["eval_mode"] == "exhaustive" and c["reduced"] == ["generations"]
    assert cell.window_chunks(cell.run_seconds) == 1
    assert c["limits"] == cells.resolve("mul8_paper.fig_grid").config[
        "limits"]
    assert [m["name"] for m in cell.end_to_end] == ["evals_per_s", "setup_s"]
    assert [m["name"] for m, _ in cell.per_layer] == [
        "mul12_kernel_gwords_per_s", "mul12_characterize_ms_per_chunk"]
    # one chunk of 32 runs of one constraint, as in the width-8 cells
    plan = traffic.sweeps(cell.mix, 2 ** 31 + 17, 1, 32)
    assert len(plan) == 1 and plan[0].runs == 32


def test_the_width8_cells_keep_their_lists():
    bench = cells.load_benchmark()
    for name in ("mul8_paper.fig_grid", "add8_evoapprox.fig_grid"):
        cell = cells.resolve(name)
        assert [m["name"] for m, _ in cell.per_layer] == [
            m["name"] for m in bench["per_layer"]
            if not m["name"].startswith("mul12_")]


def _event(name, start, end, where="0"):
    return trace.Event(name, start, end, where)


def test_the_width12_readers_read_as_the_width8_ones():
    ops = [_event("%cgp_sim_metrics_batched.3 = f32[256,40] custom-call()",
                  100, 300)]
    modules = [_event("jit_characterize_chunk(3)", 500, 550)]
    host = [_event("bench.window", 0, 1000, "python")]
    red = trace.Reduced(ops, modules, host, 1)
    counters = {"chunks": 1, "n_n": 900, "n_inputs": 1 << 24,
                "evaluations": 1536}
    r = run.Run(red, red.host_span("bench.window"), counters, 250e-9)
    readers = {m["name"]: read for m, read in cells.resolve(
        "mul8_paper.fig_grid").per_layer}
    readers12 = {m["name"]: read for m, read in cells.resolve(CELL).per_layer}
    assert readers12["mul12_kernel_gwords_per_s"](r) \
        == readers["cgp_kernel_gwords_per_s"](r) \
        == pytest.approx(1536 * 900 * (1 << 19) / 200e-9 / 1e9)
    assert readers12["mul12_characterize_ms_per_chunk"](r) \
        == readers["characterize_ms_per_chunk"](r) \
        == pytest.approx(50e-9 * 1e3)
    # where the trace holds neither, both leave their metric out
    empty = run.Run(trace.Reduced([], [], host, 1),
                    red.host_span("bench.window"), counters, 0.0)
    assert all(read(empty) is None for read in readers12.values())


def test_tiny_width12_cell_run_is_correct(tmp_path):
    result, info = run.measure(tiny(CELL), 2 ** 31 + 17, 0.01, False, CPU,
                               str(tmp_path))
    assert result["correct"], result["compared"]
    assert result["attempted"] == 32 and result["failed"] == 0
    assert set(result["metrics"]) == {"evals_per_s", "setup_s"}


def test_host_heap_serves_every_thread_from_one_arena():
    """``host_heap.keep()``, called before threads start, leaves glibc one
    arena for every thread of the reference's reduction (a fresh process:
    the setting is the whole process's)."""
    import subprocess
    import sys
    code = (
        "import ctypes, sys\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "import numpy as np\n"
        "from bench import host_heap\n"
        "keep = sys.argv[1] == '1'\n"
        "assert not keep or host_heap.keep()\n"
        "with ThreadPoolExecutor(8) as pool:\n"
        "    list(pool.map(lambda k: np.ones(1 << 23).sum(), range(32)))\n"
        "stats = ctypes.CDLL(None).malloc_stats\n"
        "stats.argtypes, stats.restype = (), None\n"
        "stats()\n")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    arenas = []
    for keep in ("0", "1"):
        err = subprocess.run([sys.executable, "-c", code, keep], cwd=root,
                             capture_output=True, text=True,
                             check=True).stderr
        arenas.append(sum(line.startswith("Arena") for line in
                          err.splitlines()))
    assert arenas[0] > 1 and arenas[1] == 1
