"""tools/check_bench.py — the CI bench gate (ISSUE 5 acceptance: a
synthetic 2× slowdown against the committed baseline must exit non-zero)."""
import json
import os
import subprocess
import sys

from conftest import REPO

CHECK = os.path.join(REPO, "tools", "check_bench.py")

BASELINE = {
    "sweep": {"n_runs": 6, "serial_runs_per_s": 2.0,
              "batched_jnp_runs_per_s": 20.0,
              "batched_pallas_runs_per_s": 10.0},
    "gen": {"generations_per_s": 100.0},
    "results": {"spill_rows_per_s": 1e4, "row_kb": 7.0},
    "eval": {"fused_us_per_eval": 50.0},
    "_meta": {"smoke": True},
}


def _run(tmp_path, current, baseline=BASELINE, extra=()):
    cur, base = tmp_path / "cur.json", tmp_path / "base.json"
    cur.write_text(json.dumps(current))
    base.write_text(json.dumps(baseline))
    return subprocess.run(
        [sys.executable, CHECK, str(cur), "--baseline", str(base), *extra],
        capture_output=True, text=True)


def test_identical_passes(tmp_path):
    proc = _run(tmp_path, BASELINE)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "check_bench OK" in proc.stdout


def test_synthetic_2x_slowdown_fails(tmp_path):
    slow = json.loads(json.dumps(BASELINE))
    for bench in ("sweep", "gen", "results"):
        for k, v in slow[bench].items():
            if k.endswith("_per_s"):
                slow[bench][k] = v / 2          # throughput halves...
    slow["eval"]["fused_us_per_eval"] *= 2      # ...latency doubles
    proc = _run(tmp_path, slow)
    assert proc.returncode != 0, proc.stdout
    assert "FAIL sweep.serial_runs_per_s" in proc.stdout
    assert "FAIL eval.fused_us_per_eval" in proc.stdout
    # shape keys are not performance: n_runs/row_kb never gate
    assert "n_runs" not in proc.stdout and "row_kb" not in proc.stdout


def test_regression_inside_gate_passes(tmp_path):
    ok = json.loads(json.dumps(BASELINE))
    ok["gen"]["generations_per_s"] = 80.0   # -20% < 30% gate
    proc = _run(tmp_path, ok)
    assert proc.returncode == 0, proc.stdout


def test_tighter_gate_catches_it(tmp_path):
    ok = json.loads(json.dumps(BASELINE))
    ok["gen"]["generations_per_s"] = 80.0
    proc = _run(tmp_path, ok, extra=("--max-regression", "0.1"))
    assert proc.returncode != 0


def test_new_and_missing_keys_never_fail(tmp_path):
    cur = json.loads(json.dumps(BASELINE))
    del cur["gen"]                                   # GONE key
    cur["sweep"]["batched_new_leg_runs_per_s"] = 5.0  # NEW key
    proc = _run(tmp_path, cur)
    assert proc.returncode == 0, proc.stdout
    assert "GONE gen.generations_per_s" in proc.stdout
    assert "NEW  sweep.batched_new_leg_runs_per_s" in proc.stdout


def test_missing_baseline_is_not_a_failure(tmp_path):
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(BASELINE))
    proc = subprocess.run(
        [sys.executable, CHECK, str(cur), "--baseline",
         str(tmp_path / "nope.json")], capture_output=True, text=True)
    assert proc.returncode == 0
    assert "no baseline" in proc.stdout
