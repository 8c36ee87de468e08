"""What decides ``correct``: the shards a window committed, held against the
plain reference (``reference.py``).

Every grid row must be committed once, with the thresholds it was asked
for.  A sample of rows drawn from the seed is measured again by the
reference: the committed parent (its metrics, relative power, feasibility
and signed-error moments, and the last history entry, which the kernel
path produced) and the best genome (its fitness).  A smaller sample is
replayed from the golden parent through every generation, and its final
parent and best genomes must be the program's, gene for gene (the best as
far as float rounding leaves it open: ``reference.replay``), with every
history entry close to the replay's.  Each row is measured at its own
constraint's Gauss sigma.

Each number compared is printed beside its limit (the cell's configuration
file holds the limits):

* ``rows_wrong``: rows missing, committed twice, or with other thresholds;
* ``genome_mismatch``: replayed runs whose final parent or best genome
  differs from the reference's;
* ``feasible_mismatch``: sampled rows whose feasibility differs, leaving
  out rows with a metric within ``TIE_RTOL`` of its threshold;
* ``metric_gap``: largest relative gap of a metric (|AVG| relative to MAE,
  which bounds it; ACC0 and GAUSS 1 when they differ);
* ``power_gap``: largest relative gap of relative power or fitness;
* ``moment_gap``: largest gap of the signed-error mean (relative to the
  error's standard deviation, at least 1) and standard deviation.

The control (``control=True``) puts the reference computed in bfloat16 in
the program's place: every number the program committed of the compared
rows is measured again in bfloat16, and the same comparison judges it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import numpy as np

from bench import reference as R
from bench import traffic

NUMBERS = ("rows_wrong", "genome_mismatch", "feasible_mismatch",
           "metric_gap", "power_gap", "moment_gap")
#: rows measured again, and rows replayed, per run
SAMPLE_RUNS = 16
REPLAY_RUNS = 1
#: how near a threshold, or another selection, float32 and float64 may
#: decide apart: 50 times the largest float32 gap read on the chip
TIE_RTOL = 1e-5


def thresholds(constraint: dict) -> np.ndarray:
    """Eq. 9 threshold vector of a constraint (percent; ACC0/GAUSS 1 when
    required, -inf when free)."""
    t = np.full(R.N_METRICS, np.inf)
    for name, i in (("mae", R.MAE), ("wce", R.WCE), ("er", R.ER),
                    ("mre", R.MRE), ("avg", R.AVG)):
        if name in constraint:
            t[i] = float(constraint[name])
    t[R.ACC0] = 1.0 if constraint.get("acc0") else -np.inf
    t[R.GAUSS] = 1.0 if constraint.get("gauss") else -np.inf
    return t


def load_shards(results_dir: str) -> dict[str, np.ndarray]:
    """Every committed shard's rows, concatenated."""
    shards = [np.load(p) for p in
              sorted(glob.glob(os.path.join(results_dir, "shard_*.npz")))]
    if not shards:
        return {}
    return {k: np.concatenate([s[k] for s in shards]) for k in shards[0].files}


def _rel(p, r, scale=None) -> float:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    scale = np.abs(r) if scale is None else np.asarray(scale, np.float64)
    both_inf = np.isinf(p) & np.isinf(r) & (np.sign(p) == np.sign(r))
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where((p == r) | both_inf, 0.0,
                       np.abs(p - r) / np.maximum(scale, 1e-300))
    gap = np.where(np.isnan(gap), np.inf, gap)
    return float(gap.max(initial=0.0))


def metric_gap(p: np.ndarray, r: np.ndarray) -> float:
    """Largest gap over metric vectors (..., N_METRICS)."""
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    mag = [R.MAE, R.WCE, R.ER, R.MRE]
    gap = _rel(p[..., mag], r[..., mag])
    gap = max(gap, _rel(p[..., R.AVG], r[..., R.AVG],
                        np.maximum(np.abs(r[..., R.AVG]), r[..., R.MAE])))
    flags = p[..., [R.ACC0, R.GAUSS]] != r[..., [R.ACC0, R.GAUSS]]
    return max(gap, float(flags.any()))


def _committed(sweeps: list[traffic.Sweep], dirs: list[str]
               ) -> tuple[dict, list[tuple[dict, int, dict]]]:
    """Count wrong rows; return every committed row as (constraint, CGP
    seed, data)."""
    wrong = missing = 0
    rows = []
    for sweep, d in zip(sweeps, dirs):
        z = load_shards(d)
        got = z.get("grid_rows", np.zeros(0, np.int64))
        counts = np.bincount(got, minlength=sweep.runs)[:sweep.runs]
        missing += int((counts == 0).sum())
        wrong += int((counts != 1).sum()) + int((got >= sweep.runs).sum())
        for k, r in enumerate(got):
            if r >= sweep.runs:
                continue
            data = {f: z[f][k] for f in z}
            constraint, seed = sweep.row(int(r))
            want = thresholds(constraint).astype(np.float32)
            if not np.array_equal(data["thresholds"], want):
                wrong += 1
                continue
            rows.append((constraint, seed, data))
    return {"rows_wrong": wrong, "missing": missing}, rows


def _evaluate(prob: R.Problem, nodes: np.ndarray, outs: np.ndarray,
              sigmas: np.ndarray, dtype=np.float64) -> R.Evaluation:
    """``R.evaluate`` of each genome at its own Gauss sigma."""
    got: dict[str, np.ndarray] = {}
    for s in np.unique(sigmas):
        at = np.flatnonzero(sigmas == s)
        ev = R.evaluate(dataclasses.replace(prob, gauss_sigma=float(s)),
                        nodes[at], outs[at], dtype)
        for f in ("metrics", "power", "error_mean", "error_std", "active"):
            v = getattr(ev, f)
            if f not in got:
                got[f] = np.empty((len(sigmas),) + v.shape[1:], v.dtype)
            got[f][at] = v
    return R.Evaluation(**got)


def _golden_power(prob: R.Problem, dtype=np.float64) -> float:
    gold_n, gold_o = R.golden_genome(prob.kind, prob.width, prob.n_n)
    return R.evaluate(prob, gold_n[None], gold_o[None], dtype).power[0]


def _in_programs_place(prob: R.Problem, rows: list) -> list:
    """The rows as the reference in bfloat16 would commit them: the
    program's genomes, and every number measured of them (the parent's
    metrics, relative power, feasibility and moments, the last history
    entry, the best genome's fitness) measured again in bfloat16."""
    if not rows:
        return rows
    k = len(rows)
    col = lambda f: np.stack([d[f] for _, _, d in rows])
    sigmas = np.array([c["gauss_sigma"] for c, _, _ in rows] * 2)
    low = _evaluate(prob, np.concatenate([col("parent_nodes"),
                                          col("best_nodes")]),
                    np.concatenate([col("parent_outs"), col("best_outs")]),
                    sigmas, R.BF16)
    g_low = _golden_power(prob, R.BF16)
    out = []
    for i, (constraint, seed, d) in enumerate(rows):
        met, power = low.metrics[i], low.power[i]
        feas = bool(R.feasible(met, thresholds(constraint)))
        last = {"hist_metrics": met, "hist_power_rel": power / g_low,
                "hist_fit": power if feas else np.inf}
        d = dict(d, metrics=met, power_rel=power / g_low, feasible=feas,
                 error_mean=low.error_mean[i], error_std=low.error_std[i],
                 best_fit=low.power[k + i])
        for f, v in last.items():
            h = np.array(d[f], np.float64)
            h[-1] = v
            d[f] = h
        out.append((constraint, seed, d))
    return out


def compare(prob: R.Problem, gens: int, lam: int, rate: float,
            sweeps: list[traffic.Sweep], dirs: list[str], seed: int,
            control: bool = False) -> tuple[dict, dict]:
    """(numbers compared, details) for the shards in ``dirs``; with
    ``control``, for the reference in bfloat16 in the program's place."""
    counts, rows = _committed(sweeps, dirs)
    nums = {"rows_wrong": counts["rows_wrong"], "genome_mismatch": 0,
            "feasible_mismatch": 0, "metric_gap": 0.0, "power_gap": 0.0,
            "moment_gap": 0.0}
    info = {"missing": counts["missing"], "checked": 0, "replayed": 0,
            "ambiguous": 0, "branches": 0, "steered": 0, "open_bests": 0,
            "golden_above": 0}
    if not rows:
        return nums, info
    sel = [rows[i] for i in traffic.sample(seed, "check", len(rows),
                                           SAMPLE_RUNS)]
    again = [rows[i] for i in traffic.sample(seed, "replay", len(rows),
                                             REPLAY_RUNS)]
    if control:
        sel, again = _in_programs_place(prob, sel), \
            _in_programs_place(prob, again)
    g_power = _golden_power(prob)

    thr = np.stack([thresholds(c) for c, _, _ in sel])
    sigmas = np.array([c["gauss_sigma"] for c, _, _ in sel])
    col = lambda f: np.stack([d[f] for _, _, d in sel])
    ev = _evaluate(prob, np.concatenate([col("parent_nodes"),
                                         col("best_nodes")]),
                   np.concatenate([col("parent_outs"), col("best_outs")]),
                   np.concatenate([sigmas, sigmas]))
    k = len(sel)
    par, best = slice(0, k), slice(k, 2 * k)
    ref_met, ref_pow = ev.metrics[par], ev.power[par]
    ref_fit = R.fitness(R.Evaluation(ref_met, ref_pow, ev.error_mean[par],
                                     ev.error_std[par]), thr)
    edge = R.at_threshold(ref_met, thr, TIE_RTOL)
    nums["metric_gap"] = max(metric_gap(col("metrics"), ref_met),
                             metric_gap(col("hist_metrics")[:, -1], ref_met))
    nums["power_gap"] = max(
        _rel(col("power_rel"), ref_pow / g_power),
        _rel(col("hist_power_rel")[:, -1], ref_pow / g_power),
        _rel(np.where(edge, ref_pow, col("hist_fit")[:, -1]),
             np.where(edge, ref_pow, ref_fit)),
        _rel(col("best_fit"), ev.power[best]))
    std = ev.error_std[par]
    nums["moment_gap"] = max(
        _rel(col("error_mean"), ev.error_mean[par], np.maximum(std, 1.0)),
        _rel(col("error_std"), std))
    ref_feas = R.feasible(ref_met, thr)
    best_feas = R.feasible(ev.metrics[best], thr) | R.at_threshold(
        ev.metrics[best], thr, TIE_RTOL)
    nums["feasible_mismatch"] = int(
        ((col("feasible").astype(bool) != ref_feas) & ~edge).sum()
        + (~best_feas).sum())
    info["checked"] = k

    for constraint, cgp_seed, d in again:
        rp = R.replay(dataclasses.replace(
            prob, gauss_sigma=float(constraint["gauss_sigma"])),
            thresholds(constraint), cgp_seed, gens, lam, rate, d, TIE_RTOL)
        same = (np.array_equal(rp.parent_nodes, d["parent_nodes"])
                and np.array_equal(rp.parent_outs, d["parent_outs"])
                and np.array_equal(rp.best_nodes, d["best_nodes"])
                and np.array_equal(rp.best_outs, d["best_outs"]))
        nums["genome_mismatch"] += int(not same)
        nums["metric_gap"] = max(nums["metric_gap"],
                                 metric_gap(d["hist_metrics"],
                                            rp.hist_metrics))
        nums["power_gap"] = max(nums["power_gap"],
                                _rel(d["hist_power_rel"], rp.hist_power_rel),
                                _rel(d["hist_fit"], rp.hist_fit),
                                _rel(d["best_fit"], rp.best_fit))
        info["replayed"] += 1
        info["ambiguous"] += rp.ambiguous
        info["branches"] = max(info["branches"], rp.branches)
        info["steered"] += len(rp.steered)
        info["open_bests"] = max(info["open_bests"], rp.open_bests)
        info["golden_above"] += int(not rp.golden_ties)
    return nums, info


def verdict(nums: dict, limits: dict) -> bool:
    return all(nums[k] <= limits[k] for k in NUMBERS)
