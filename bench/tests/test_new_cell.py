"""A later PR adds a cell with new files and entries alone: a copy of the
benchmark gains a configuration, a traffic mix (two Gauss sigmas, the
phenotype cache on, its own mutation rate), a per-layer metric and a
workload, and the harness finds them all by name, builds the cell's sweeps,
and runs it at a tiny size on the CPU to a correct check."""
import json
import os
import shutil

from bench import cells, check, run, traffic
from bench.tests.conftest import CPU, ROOT, tiny


def _third_cell(root: str) -> None:
    config = json.load(open(os.path.join(
        root, "bench", "configs", "mul8_paper.json")))
    config.update(width=6, n_i=12, n_o=12, n_n=200, window_chunks=4)
    _write(root, "bench/configs/mul6_small.json", json.dumps(config))
    _write(root, "bench/traffic/gauss_dedup.json", json.dumps({
        "about": "Gauss constraints at two sigmas, phenotype cache on",
        "gauss_sigma": 256.0, "config": {"mutation_rate": 0.01},
        "sweep": {"dedup": True},
        "constraints": [{"gauss": True, "gauss_sigma": 8.0},
                        {"gauss": True, "mae": 2.0, "gauss_sigma": 32.0},
                        {"er": 50.0}]}))
    _write(root, "bench/layer_metrics/kernel_calls.py",
           "from bench import trace, work\n\n\n"
           "def read(run):\n"
           "    return trace.seconds_named(run.in_window(run.trace.ops),\n"
           "                               work.KERNEL_OP)[1] or None\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "mul6_small", "source": "https://arxiv.org/abs/2206.13077",
        "file": "bench/configs/mul6_small.json", "reduced": ["generations"],
        "why": "a 6-bit multiplier"})
    bench["workloads"].append({
        "name": "mul6_small.gauss_dedup", "config": "mul6_small",
        "traffic": "gauss_dedup", "chips": 1, "why": "two-sigma dedup sweep"})
    bench["per_layer"].append({
        "name": "kernel_calls", "unit": "calls", "better": "lower",
        "source": "device_trace", "layer": "CGP kernel",
        "moves": "evals_per_s", "workloads": ["mul6_small.gauss_dedup"]})
    _write(root, "BENCHMARK.json", json.dumps(bench))


def test_third_cell_from_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: open(p, "rb").read() for p in _files(root)}
    _third_cell(root)

    # no file of the copy changed but the one the entries live in
    for path, data in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert open(path, "rb").read() == data, path

    cell = cells.resolve("mul6_small.gauss_dedup", root)
    assert cell.config["width"] == 6 and cell.chips == 1
    assert cell.config["mutation_rate"] == 0.01
    assert cell.window_chunks(cell.run_seconds) == 4
    assert [m["name"] for m, _ in cell.per_layer] == ["kernel_calls"]
    assert [m["name"] for m in cell.end_to_end] == ["evals_per_s",
                                                   "setup_s"]
    plan = traffic.sweeps(cell.mix, 99, 5, 32)
    assert [len(s.constraints) for s in plan] == [3, 2]
    assert all(s.runs == len(s.constraints) * 32 for s in plan)
    assert sorted(c["gauss_sigma"] for c in plan[0].constraints) \
        == [8.0, 32.0, 256.0]
    # one warm-up chunk per sigma: each is a program of its own
    assert len(traffic.warmup(cell.mix, 99, 32).constraints) == 3
    prog = run.Program(cell.config, cell.mix["sweep"])
    assert prog.settings == {"dedup": True}
    # the old cell still resolves, with the metrics the benchmark names
    # for it, and not the new cell's
    old = cells.resolve("mul8_paper.fig_grid", root)
    listed = json.loads(before[os.path.join(root, "BENCHMARK.json")])
    assert [m["name"] for m, _ in old.per_layer] == [
        m["name"] for m in listed["per_layer"]
        if cells.reports(m, "mul8_paper.fig_grid")]
    assert old.per_layer and all(callable(read) for _, read in old.per_layer)
    assert [m["name"] for m in old.end_to_end] == ["evals_per_s", "setup_s"]


def test_third_cell_runs_correct(tmp_path):
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    _third_cell(root)
    cell = tiny("mul6_small.gauss_dedup", root)
    result, info = run.measure(cell, 2 ** 33 + 5, cell.run_seconds * 3 / 4,
                               False, CPU, str(tmp_path / "work"))
    assert result["correct"], result["compared"]
    assert result["attempted"] == 3 * 32 and result["failed"] == 0
    assert info["replayed"] == check.REPLAY_RUNS


def _files(root):
    for d, _, names in os.walk(root):
        for n in names:
            yield os.path.join(d, n)


def _write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)
