"""A run of the harness on the CPU at a tiny size: the traffic generator,
the metric arithmetic, the comparison with the reference, and the refusal
to measure without a TPU."""
import json
import os

import numpy as np
import pytest

from bench import check, run, trace, traffic
from bench.tests.conftest import CPU, tiny

BIG_SEED = 2 ** 31 + 12345


def test_traffic_is_whole_chunks_from_the_seed():
    mix = traffic.load("fig_grid")
    a = traffic.sweeps(mix, BIG_SEED, 60, 32)
    assert a == traffic.sweeps(mix, BIG_SEED, 60, 32)
    assert a != traffic.sweeps(mix, BIG_SEED + 1, 60, 32)
    assert [len(s.constraints) for s in a] == [56, 4]
    for s in a:
        assert len(s.seeds) == 32 and s.runs % 32 == 0
        assert all(0 <= x < traffic.SEED_SPACE for x in s.seeds)
        assert len({json.dumps(c, sort_keys=True) for c in s.constraints}) \
            == len(s.constraints)
    assert all(c["gauss_sigma"] == 256.0 for c in a[0].constraints)
    # the warm-up draws its own runs: one chunk, the mix's one sigma
    w = traffic.warmup(mix, BIG_SEED, 32)
    assert len(w.constraints) == 1 and w.seeds != a[0].seeds
    con, seed = a[0].row(33)
    assert con == a[0].constraints[1] and seed == a[0].seeds[1]


def test_thresholds_follow_eq9():
    t = check.thresholds({"mae": 0.5, "er": 60, "acc0": True})
    assert t[0] == 0.5 and t[2] == 60 and np.isinf(t[1])
    assert t[5] == 1.0 and t[6] == -np.inf


def _event(name, start, end, where="0"):
    return trace.Event(name, start, end, where)


def test_layer_metric_arithmetic():
    from bench import cells
    kernel = "%cgp_sim_metrics_batched.12 = f32[256,440] custom-call()"
    ops = [_event(kernel, 100, 300),
           _event("%fusion.1 = f32[256] fusion(%cgp_sim_metrics_batched.12)",
                  250, 400), _event(kernel, 600, 700)]
    modules = [_event("jit_evolve_chunk(12)", 90, 450),
               _event("jit_characterize_chunk(3)", 500, 550),
               _event("jit_evolve_chunk(12)", 590, 800)]
    host = [_event("bench.window", 0, 1000, "python"),
            _event("bench.sweep", 0, 1000, "python")]
    red = trace.Reduced(ops, modules, host, 1)
    window = red.host_span("bench.window")
    assert trace.union(ops, window) == [(100, 400), (600, 700)]
    busy = trace.busy_seconds(red, window)
    assert busy == pytest.approx(400e-9)
    gaps = trace.idle_gaps(red, window)
    assert gaps == [(0, 100), (400, 600), (700, 1000)]
    counters = {"chunks": 2, "generations": 5, "evaluations": 10, "n_n": 4,
                "n_inputs": 64}
    r = run.Run(red, window, counters, busy)
    read = {m: cells.load_reader(cells.ROOT, m) for m in (
        "cgp_kernel_gwords_per_s", "evolve_other_ms_per_gen",
        "characterize_ms_per_chunk", "device_idle_pct")}
    # 10 candidates x 4 gates x 2 words over 300 ns of kernel
    assert read["cgp_kernel_gwords_per_s"](r) == pytest.approx(80 / 300e-9
                                                                / 1e9)
    # (360 + 210 - 300) ns of program outside the kernel over 10 generations
    assert read["evolve_other_ms_per_gen"](r) == pytest.approx(27e-6)
    assert read["characterize_ms_per_chunk"](r) == pytest.approx(25e-6)
    assert read["device_idle_pct"](r) == pytest.approx(60.0)
    empty = run.Run(trace.Reduced([], [], host, 0), window, counters, 0.0)
    assert read["cgp_kernel_gwords_per_s"](empty) is None
    assert read["evolve_other_ms_per_gen"](empty) is None


@pytest.mark.parametrize("name", ["mul8_paper.fig_grid",
                                  "add8_evoapprox.fig_grid"])
def test_tiny_run_is_correct(name, tmp_path, capsys):
    result, info = run.measure(tiny(name), BIG_SEED, 0.01, False, CPU,
                               str(tmp_path))
    assert result["correct"], result["compared"]
    assert info["checked"] > 0 and info["replayed"] == check.REPLAY_RUNS
    assert result["attempted"] == 32 and result["failed"] == 0
    assert set(result["metrics"]) == {"evals_per_s", "setup_s"}
    assert list(result)[-1] == "compared"
    assert "compilations in the window" in capsys.readouterr().out


def test_refuses_without_a_tpu(capsys):
    code = run.main(["--workload", "mul8_paper.fig_grid", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0
    assert not any(line.startswith("{") for line in out.out.splitlines())
    assert "TPU" in out.err


def test_rows_missing_twice_or_misplaced_are_wrong(tmp_path):
    sw = traffic.Sweep(({"mae": 1.0}, {"er": 50.0}), (5, 6))
    thr = np.stack([check.thresholds(sw.row(r)[0]) for r in range(4)])

    def shard(name, rows, thresholds):
        np.savez(os.path.join(tmp_path, f"shard_{name}.npz"),
                 grid_rows=np.asarray(rows, np.int32),
                 thresholds=thresholds.astype(np.float32))

    shard("00000000_00000002", [0, 1], thr[[0, 1]])
    shard("00000002_00000004", [1, 3], thr[[1, 0]])  # twice, then misplaced
    counts, rows = check._committed([sw], [str(tmp_path)])
    # row 1 twice, row 2 missing, row 3 with row 0's thresholds
    assert counts == {"rows_wrong": 3, "missing": 1}
    assert [(c, s) for c, s, _ in rows] == [sw.row(0), sw.row(1), sw.row(1)]
