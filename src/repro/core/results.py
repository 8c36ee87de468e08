"""Streaming sweep-results layer: on-disk shard spill + lazy read-back.

``SweepResult`` used to be the only results surface of the batched sweep
engine (``core.sweep``): every per-run summary AND every per-generation
history lived in host RAM for the whole grid.  At the paper's scale (~27k
runs × thousands of generations × ``N_METRICS`` floats) the histories alone
are tens of GB — with the fused (runs × λ) kernel the evaluation side is no
longer the bottleneck, host-side result handling is.  This module moves the
results path to disk:

  * ``SweepResultWriter`` — called by ``sweep.run_sweep_batched`` after every
    finished chunk; commits the chunk's rows as ONE append-only ``.npz``
    shard (atomic tmp + rename, presence == committed).  Shards are run-major
    and named by their execution-order span, so a re-run of the same grid
    overwrites a shard with bit-identical bytes instead of duplicating rows.
  * ``SweepResultReader`` — lazily iterates shards.  Per-run summary columns
    (``(n_runs,)`` / ``(n_runs, N_METRICS)``) are tiny and are scattered back
    to grid order on demand; per-generation histories are only ever yielded
    one shard at a time (``iter_history``), so peak host memory stays
    independent of grid size.  ``correlations()`` / ``fronts()`` feed
    ``core.pareto`` with exactly the arrays the in-RAM path would build —
    results are bit-identical.

Schema: a ``manifest.json`` (written once, atomically) pins the grid
fingerprint (same identity ``checkpoint/store`` checkpoints are guarded by),
a schema fingerprint (field names/dtypes/shapes + version), the history mode
and the chunk size.  The chunk size is pinned because shard spans are the
deterministic ``sweep.plan_chunks`` partition of the σ-grouped execution
order — resuming with a different chunk size would produce overlapping
spans, so the writer refuses it.

The shard set doubles as the sweep's resume state: ``restore`` scatters the
committed spans back into the driver's summary buffers, so a ``results_dir``
sweep resumes mid-grid even without a ``checkpoint_dir`` (and, because shards
commit every chunk while checkpoints commit every ``checkpoint_every``
chunks, shards are never staler than the checkpoint).

Multi-pod execution (DESIGN.md §6): the manifest additionally pins ``n_pods``
and the full deterministic ``chunk_spans`` plan, and the chunk plan is
round-robin partitioned across pods (``pod_partition``).  Each pod commits
only its own spans, so a partially-run multi-pod directory holds a UNION of
per-pod prefixes — committed coverage is computed per pod
(``pod_prefix_spans``) instead of as one global contiguous prefix, and
single-pod directories are the ``n_pods=1`` special case of the same rule.
Pods share nothing at runtime beyond this one-time manifest: span names,
shard bytes and the manifest content are all deterministic functions of the
fingerprinted grid, so concurrent creation by several pods is idempotent.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import re
import shutil
import sys
import zipfile
from typing import Iterator, Sequence

import jax
import numpy as np

from repro.checkpoint.store import atomic_save_npz, atomic_write_json
from repro.core import metrics as M

SCHEMA_VERSION = 3  # v3: + certified_mask summary column (DESIGN.md §10)
MANIFEST = "manifest.json"
HISTORY_MODES = ("none", "summary", "full")
_SHARD_RE = re.compile(r"^shard_(\d{8})_(\d{8})\.npz$")

#: summary fields present in every shard: name -> (trailing shape spec, dtype)
#: (leading axis is always the row axis; symbolic dims are resolved against
#: the manifest at write/read time)
SUMMARY_FIELDS = {
    "grid_rows": ((), "int32"),            # grid-order index of each row
    "thresholds": (("n_metrics",), "float32"),
    "parent_nodes": (("n_n", 3), "int32"),
    "parent_outs": (("n_o",), "int32"),
    "best_nodes": (("n_n", 3), "int32"),
    "best_outs": (("n_o",), "int32"),
    "best_fit": ((), "float32"),
    "metrics": (("n_metrics",), "float32"),
    # per-metric standard errors (DESIGN.md §9): zeros for exhaustive grids,
    # CLT estimates for sampled ones.  Part of SCHEMA_VERSION 2 — pre-§9
    # shard directories carry a different schema fingerprint and cannot be
    # extended by this code (re-run the sweep to migrate).
    "metrics_stderr": (("n_metrics",), "float32"),
    "power_rel": ((), "float32"),
    "feasible": ((), "uint8"),
    # exact-certification flag (DESIGN.md §10): 1 when the row's error
    # metrics are EXACT over the full cube (exhaustive census, or sampled +
    # escalated through ``core.certify``), 0 for uncertified sampled
    # estimates.  Part of SCHEMA_VERSION 3; v2 directories are READ with a
    # zero default (``READ_DEFAULTS``) but cannot be extended by this writer.
    "certified_mask": ((), "uint8"),
    "error_mean": ((), "float32"),
    "error_std": ((), "float32"),
}

#: summary fields absent from older schema versions the reader still
#: accepts, keyed by manifest version: reads leave the buffer's dtype-zero
#: default in place (certified_mask=0 — nothing in a pre-§10 directory was
#: escalated to the exact tier).
READ_DEFAULTS = {2: frozenset({"certified_mask"})}
MIN_READ_VERSION = min(READ_DEFAULTS, default=SCHEMA_VERSION)

#: per-generation history fields, present when ``keep_history != "none"``
HISTORY_FIELDS = {
    "hist_power_rel": (("gens",), "float32"),
    "hist_fit": (("gens",), "float32"),
    "hist_metrics": (("gens", "n_metrics"), "float32"),
}


def normalize_history_mode(keep_history) -> str:
    """Map the legacy bool knob onto the mode string (True -> "full",
    False -> "none"); validate strings against ``HISTORY_MODES``."""
    if keep_history is True:
        return "full"
    if keep_history is False:
        return "none"
    if keep_history not in HISTORY_MODES:
        raise ValueError(
            f"keep_history must be one of {HISTORY_MODES} (or a legacy "
            f"bool), got {keep_history!r}")
    return keep_history


def shard_fields(keep_history: str) -> dict:
    """The shard schema of a history mode: summary always, histories on disk
    for both "summary" and "full" (the modes only differ in what the driver
    keeps in RAM)."""
    fields = dict(SUMMARY_FIELDS)
    if keep_history != "none":
        fields.update(HISTORY_FIELDS)
    return fields


def schema_fingerprint(keep_history: str, dims: dict[str, int]) -> str:
    """Identity of the shard layout: version + field names/shapes/dtypes +
    the resolved symbolic dims.  Stored in the manifest next to the grid
    fingerprint; a mismatch means the directory holds shards this code (or
    this grid geometry) cannot extend."""
    ident = {
        "version": SCHEMA_VERSION,
        "fields": {k: [list(s), d] for k, (s, d)
                   in sorted(shard_fields(keep_history).items())},
        "dims": {k: int(v) for k, v in sorted(dims.items())},
    }
    return hashlib.sha256(
        json.dumps(ident, sort_keys=True).encode()).hexdigest()


def _shard_name(start: int, end: int) -> str:
    return f"shard_{start:08d}_{end:08d}.npz"


def _scan_spans(results_dir: str) -> list[tuple[int, int]]:
    """Committed shard spans, sorted by start (atomic rename => presence is
    the commit marker)."""
    spans = []
    for name in os.listdir(results_dir):
        if m := _SHARD_RE.match(name):
            spans.append((int(m.group(1)), int(m.group(2))))
    return sorted(spans)


def _prefix_spans(spans: Sequence[tuple[int, int]]) -> list[tuple[int, int]]:
    """The contiguous-from-zero prefix of a sorted span list.  Orphans past a
    gap are unreachable by a resumed single-pod sweep's skip logic and are
    ignored (and deterministically overwritten when the sweep gets there).
    Fallback coverage rule for directories whose manifest predates the
    ``chunk_spans`` plan; plan-pinned directories use ``pod_prefix_spans``."""
    out, want = [], 0
    for start, end in spans:
        if start != want:
            break
        out.append((start, end))
        want = end
    return out


def pod_partition(chunk_spans: Sequence[tuple[int, int]],
                  n_pods: int) -> list[list[tuple[int, int]]]:
    """Round-robin assignment of execution-order chunk spans to pods.

    Pod ``p`` owns ``chunk_spans[p::n_pods]`` — deterministic from the plan
    alone (no coordination), and interleaved so σ-grouped plans spread each
    σ's chunks across pods instead of handing one pod a whole σ block.
    """
    if n_pods < 1:
        raise ValueError(f"n_pods must be >= 1, got {n_pods}")
    return [list(chunk_spans[p::n_pods]) for p in range(n_pods)]


def pod_prefix_spans(committed: Sequence[tuple[int, int]],
                     chunk_spans: Sequence[tuple[int, int]],
                     n_pods: int) -> list[tuple[int, int]]:
    """Committed coverage of a pod-partitioned plan: the union over pods of
    each pod's contiguous committed prefix OF ITS OWN span sequence, sorted.

    This is the multi-pod generalization of ``_prefix_spans`` (to which it
    reduces for ``n_pods=1``): pods commit independently, so the directory
    may cover e.g. pod 1's first three chunks while pod 0 has one — a state
    with global gaps that is still an exact per-pod resume point.  Spans past
    a gap in a pod's OWN sequence are orphans (ignored; deterministically
    overwritten with identical bytes when that pod gets there), exactly like
    the single-pod rule.
    """
    have = set(map(tuple, committed))
    out: list[tuple[int, int]] = []
    for pod_seq in pod_partition([tuple(s) for s in chunk_spans], n_pods):
        for span in pod_seq:
            if span not in have:
                break
            out.append(span)
    return sorted(out)


class SweepResultWriter:
    """Append-only shard writer for one fingerprinted grid.

    Created by ``sweep.run_sweep_batched`` when ``SweepConfig.results_dir``
    is set.  ``write_chunk`` commits one chunk of run-major rows; ``restore``
    is the resume path (scatter the committed coverage back into the summary
    buffers).  Opening a directory that holds a DIFFERENT grid (or the same
    grid with a different chunk size / history mode / pod count) raises —
    pass ``on_mismatch="reset"`` to wipe and restart it instead (the figure
    pipeline namespaces directories by fingerprint, so it never needs to).

    Multi-pod sweeps hand every pod's writer the same ``chunk_spans`` plan
    and ``n_pods``; ``pod_spans`` is the per-pod span filter (which chunks
    this pod owns) and committed coverage is the union of per-pod prefixes
    (``pod_prefix_spans``) rather than one global prefix.
    """

    def __init__(self, results_dir: str, *, grid_fingerprint: str,
                 grid_meta: list[dict], n_runs: int, gens: int,
                 n_n: int, n_o: int, keep_history: str, chunk_size: int,
                 chunk_spans: Sequence[tuple[int, int]] | None = None,
                 n_pods: int = 1, problem_meta: dict | None = None,
                 on_mismatch: str = "error"):
        self.results_dir = results_dir
        keep_history = normalize_history_mode(keep_history)
        dims = {"gens": gens, "n_metrics": M.N_METRICS,
                "n_n": n_n, "n_o": n_o}
        manifest = {
            "schema_version": SCHEMA_VERSION,
            "grid_fingerprint": grid_fingerprint,
            "schema_fingerprint": schema_fingerprint(keep_history, dims),
            "keep_history": keep_history,
            "chunk_size": int(chunk_size),
            "n_pods": int(n_pods),
            "chunk_spans": ([[int(s), int(e)] for s, e in chunk_spans]
                            if chunk_spans is not None else None),
            "n_runs": int(n_runs),
            "dims": dims,
            "metric_names": list(M.METRIC_NAMES),
            # problem geometry (width/kind) so downstream consumers — the
            # artifact registry (core.artifacts, DESIGN.md §12) — can rebuild
            # LUTs from genomes without out-of-band knowledge.  Informational:
            # not part of the mismatch check (the grid fingerprint already
            # covers the problem), absent (None) for writers that replay raw
            # buffers without a SearchConfig.
            "problem": problem_meta,
            "grid": grid_meta,
        }
        os.makedirs(results_dir, exist_ok=True)
        path = os.path.join(results_dir, MANIFEST)
        if os.path.exists(path):
            with open(path) as f:
                have = json.load(f)
            keys = ("grid_fingerprint", "schema_fingerprint", "chunk_size",
                    "keep_history", "n_runs", "schema_version", "n_pods")
            # pre-pod manifests carry no pod fields; they are single-pod
            defaults = {"n_pods": 1}
            diff = [k for k in keys
                    if have.get(k, defaults.get(k)) != manifest[k]]
            if diff:
                if on_mismatch != "reset":
                    raise ValueError(
                        f"results_dir {results_dir!r} holds a different "
                        f"sweep (mismatched: {diff}); use a fresh directory "
                        f"or on_mismatch='reset'")
                for name in os.listdir(results_dir):
                    p = os.path.join(results_dir, name)
                    shutil.rmtree(p) if os.path.isdir(p) else os.remove(p)
                atomic_write_json(path, manifest)
            else:
                if manifest["chunk_spans"] is None and have.get(
                        "chunk_spans"):
                    # reopened without a plan: keep the pinned one (the plan
                    # is a deterministic function of the matched fingerprint
                    # + chunk_size, so it cannot disagree with this sweep)
                    manifest["chunk_spans"] = have["chunk_spans"]
                if manifest["problem"] is None and have.get("problem"):
                    # reopened by a problem-blind writer: keep the pinned
                    # geometry (same fingerprint => same problem)
                    manifest["problem"] = have["problem"]
                if any(k not in have
                       for k in ("n_pods", "chunk_spans", "problem")):
                    # matching pre-pod / pre-§12 directory: one-time
                    # idempotent upgrade
                    atomic_write_json(path, manifest)
        else:
            atomic_write_json(path, manifest)
        self.manifest = manifest
        self._fields = shard_fields(keep_history)
        self._dims = dims

    def spans(self) -> list[tuple[int, int]]:
        """All committed shard spans (execution order), sorted."""
        return _scan_spans(self.results_dir)

    def pod_spans(self, pod_index: int) -> list[tuple[int, int]]:
        """The span filter of one pod: the ordered slice of the chunk plan
        that pod ``pod_index`` owns (requires a ``chunk_spans`` plan)."""
        plan = self.manifest.get("chunk_spans")
        if plan is None:
            raise ValueError("writer opened without a chunk_spans plan")
        parts = pod_partition([tuple(s) for s in plan],
                              self.manifest["n_pods"])
        return parts[pod_index]

    def live_spans(self) -> list[tuple[int, int]]:
        """Committed coverage: the union of per-pod committed prefixes of the
        manifest's chunk plan (global contiguous prefix when no plan is
        pinned — pre-pod directories)."""
        committed = self.spans()
        plan = self.manifest.get("chunk_spans")
        if plan is None:
            return _prefix_spans(committed)
        return pod_prefix_spans(committed, [tuple(s) for s in plan],
                                self.manifest["n_pods"])

    def coverage(self) -> int:
        """Number of runs covered by the committed per-pod prefixes."""
        return sum(end - start for start, end in self.live_spans())

    #: load/scatter failures ``restore`` treats as a damaged shard rather
    #: than a bug: zero-byte or truncated files (BadZipFile/EOFError/
    #: OSError/ValueError from ``np.load``), a missing or malformed member
    #: (KeyError), out-of-range grid rows (IndexError)
    _CORRUPT_ERRORS = (OSError, EOFError, ValueError, KeyError, IndexError,
                       zipfile.BadZipFile)

    def _quarantine(self, path: str, err: Exception) -> None:
        """Move a damaged shard aside (``<name>.corrupt`` — no longer a
        committed span) so the sweep re-runs and re-commits it instead of
        crashing at restore time (DESIGN.md §11: a crash between the data
        write and the directory fsync on a pre-fsync layer can legitimately
        leave a truncated file under the committed name)."""
        quarantined = path + ".corrupt"
        os.replace(path, quarantined)
        print(f"[results] quarantined damaged shard {path} -> "
              f"{quarantined}: {type(err).__name__}: {err}",
              file=sys.stderr, flush=True)

    def restore(self, bufs: dict[str, np.ndarray]) -> list[tuple[int, int]]:
        """Scatter the committed coverage into grid-order buffers in place
        (only keys present in ``bufs`` are touched) and return the covered
        spans — the sweep's resume point (each pod skips its own committed
        prefix; other pods' spans pre-fill the result buffers).

        A zero-byte/truncated/unreadable shard is quarantined (renamed +
        logged, see ``_quarantine``), its span drops out of the committed
        coverage, and the scatter restarts over the recomputed coverage —
        re-scattering a healthy shard is idempotent.
        """
        while True:
            live = self.live_spans()
            path = None
            try:
                for start, end in live:
                    path = self._path(start, end)
                    with np.load(path) as z:
                        rows = z["grid_rows"]
                        for key in bufs:
                            if key in z:
                                bufs[key][rows] = z[key]
            except self._CORRUPT_ERRORS as e:
                self._quarantine(path, e)
                continue
            return live

    @functools.partial(jax.profiler.annotate_function, name="sweep.commit")
    def write_chunk(self, span: tuple[int, int],
                    rows: dict[str, np.ndarray]) -> str:
        """Atomically commit one chunk's rows as a shard.

        ``span`` is the [start, end) execution-order span from
        ``sweep.plan_chunks``; ``rows`` must hold exactly the schema's fields
        with ``end - start`` rows each, including ``grid_rows`` (the
        grid-order index of each row — σ-grouped execution permutes the
        grid, shards record the mapping).  The commit is the profiler span
        ``sweep.commit``, on whichever thread runs it.
        """
        start, end = span
        n = end - start
        if set(rows) != set(self._fields):
            raise ValueError(f"shard fields {sorted(rows)} != schema "
                             f"{sorted(self._fields)}")
        out = {}
        for key, (shape, dtype) in self._fields.items():
            want = (n,) + tuple(self._dims[d] if isinstance(d, str) else d
                                for d in shape)
            arr = np.ascontiguousarray(rows[key], dtype=dtype)
            if arr.shape != want:
                raise ValueError(f"{key}: shape {arr.shape} != {want}")
            out[key] = arr
        path = self._path(start, end)
        atomic_save_npz(path, out)
        return path

    def _path(self, start: int, end: int) -> str:
        return os.path.join(self.results_dir, _shard_name(start, end))


class SweepResultReader:
    """Lazy view over a committed shard set.

    Summary columns are materialized on demand in grid order (constraints
    outer, seeds inner — a few floats per run, cheap at any grid size);
    per-generation histories are only ever surfaced one shard at a time.
    ``correlations()`` / ``fronts()`` are bit-identical to calling
    ``pareto.metric_correlations`` / ``pareto.sweep_fronts`` on the in-RAM
    ``SweepResult`` of the same grid.

    Attributes:
      manifest:     the writer's manifest dict (fingerprints, dims, grid).
      n_runs:       grid size (completed or not).
      gens:         generations per run (history row length).
      keep_history: "none" | "summary" | "full" — "none" shards carry no
                    history fields.
      fingerprint:  the grid fingerprint (``sweep.grid_fingerprint``).
    """

    def __init__(self, results_dir: str):
        self.results_dir = results_dir
        path = os.path.join(results_dir, MANIFEST)
        if not os.path.exists(path):
            raise FileNotFoundError(f"no results manifest at {path!r}")
        with open(path) as f:
            self.manifest = json.load(f)
        ver = self.manifest["schema_version"]
        if not MIN_READ_VERSION <= ver <= SCHEMA_VERSION:
            raise ValueError(
                f"shard schema v{ver} not readable by "
                f"v{SCHEMA_VERSION} reader "
                f"(accepts v{MIN_READ_VERSION}..v{SCHEMA_VERSION})")
        self.schema_version: int = ver
        # fields this directory's shards predate; reads keep the dtype-zero
        # default in their place (e.g. certified_mask=0 for v2 shards)
        self._absent: frozenset = READ_DEFAULTS.get(ver, frozenset())
        self.n_runs: int = self.manifest["n_runs"]
        self.gens: int = self.manifest["dims"]["gens"]
        self.keep_history: str = self.manifest["keep_history"]
        self.fingerprint: str = self.manifest["grid_fingerprint"]
        self.metric_names: list[str] = self.manifest["metric_names"]
        # pre-pod manifests pin neither a pod count nor the chunk plan
        self.n_pods: int = self.manifest.get("n_pods", 1)
        # problem geometry for LUT reconstruction (core.artifacts); None
        # for directories written before DESIGN.md §12
        self.problem: dict | None = self.manifest.get("problem")

    # -- shard-level access -------------------------------------------------

    def spans(self) -> list[tuple[int, int]]:
        """Committed shard spans, execution order: the union of per-pod
        committed prefixes of the manifest's chunk plan — a mid-sweep
        multi-pod directory legitimately has global gaps (DESIGN.md §6).
        Falls back to the global contiguous prefix for pre-pod manifests
        without a pinned plan."""
        committed = _scan_spans(self.results_dir)
        plan = self.manifest.get("chunk_spans")
        if plan is None:
            return _prefix_spans(committed)
        return pod_prefix_spans(committed, [tuple(s) for s in plan],
                                self.n_pods)

    @property
    def completed(self) -> int:
        """Runs covered by the committed per-pod prefixes."""
        return sum(end - start for start, end in self.spans())

    def done_mask(self) -> np.ndarray:
        """(n_runs,) bool, grid order — rows with committed results."""
        mask = np.zeros(self.n_runs, dtype=bool)
        for _, rows in self.iter_shards(fields=("grid_rows",)):
            mask[rows["grid_rows"]] = True
        return mask

    def iter_shards(self, fields: Sequence[str] | None = None
                    ) -> Iterator[tuple[tuple[int, int], dict]]:
        """Yield ``(span, {field: (rows, ...) array})`` per committed shard,
        loading only ``fields`` (default: every field in the shard) — the
        constant-memory access path."""
        for start, end in self.spans():
            path = os.path.join(self.results_dir, _shard_name(start, end))
            with np.load(path) as z:
                # drop fields the directory's schema version predates — the
                # caller's pre-zeroed buffers keep the documented default
                keys = (z.files if fields is None
                        else [k for k in fields if k not in self._absent])
                yield (start, end), {k: z[k] for k in keys}

    def iter_history(self) -> Iterator[tuple[np.ndarray, dict]]:
        """Yield ``(grid_rows, {hist_*: (rows, gens, ...)})`` per shard.

        Raises if the shard set was written with ``keep_history="none"``.
        Peak memory is one chunk of history, independent of grid size.
        """
        if self.keep_history == "none":
            raise ValueError('shards written with keep_history="none" hold '
                             'no per-generation histories')
        fields = ("grid_rows",) + tuple(HISTORY_FIELDS)
        for _, rows in self.iter_shards(fields=fields):
            yield rows["grid_rows"], {k: rows[k] for k in HISTORY_FIELDS}

    # -- grid-order summary -------------------------------------------------

    def summary(self, fields: Sequence[str] | None = None
                ) -> dict[str, np.ndarray]:
        """Materialize summary columns in grid order.

        Args:
          fields: summary field names (default: all of ``SUMMARY_FIELDS``
            except ``grid_rows``).  History fields are refused — use
            ``iter_history``.
        Returns:
          {field: (n_runs, ...) array} plus ``"done_mask"``: (n_runs,) bool.
          Rows not yet committed are zero.
        """
        if fields is None:
            fields = [k for k in SUMMARY_FIELDS if k != "grid_rows"]
        bad = set(fields) - set(SUMMARY_FIELDS)
        if bad:
            raise ValueError(f"not summary fields: {sorted(bad)} "
                             f"(histories go through iter_history)")
        dims = self.manifest["dims"]
        out, mask = {}, np.zeros(self.n_runs, dtype=bool)
        for key in fields:
            shape, dtype = SUMMARY_FIELDS[key]
            trail = tuple(dims[d] if isinstance(d, str) else d for d in shape)
            out[key] = np.zeros((self.n_runs,) + trail, dtype=dtype)
        for _, rows in self.iter_shards(fields=("grid_rows",) + tuple(fields)):
            idx = rows["grid_rows"]
            mask[idx] = True
            for key in fields:
                if key in rows:  # else: version-absent, zero default stands
                    out[key][idx] = rows[key]
        out["done_mask"] = mask
        return out

    def records(self) -> list:
        """Rebuild grid-order ``search.CircuitRecord`` rows for every
        committed run — the same list ``search.run_sweep`` returns."""
        from repro.core.search import CircuitRecord
        s = self.summary(["parent_nodes", "parent_outs", "metrics",
                          "metrics_stderr", "power_rel", "feasible",
                          "certified_mask", "error_mean", "error_std"])
        grid = self.manifest["grid"]
        recs = []
        for i in np.flatnonzero(s["done_mask"]):
            recs.append(CircuitRecord(
                genome_nodes=s["parent_nodes"][i],
                genome_outs=s["parent_outs"][i],
                metrics=s["metrics"][i],
                power_rel=float(s["power_rel"][i]),
                constraint=grid[i]["constraint"],
                seed=int(grid[i]["seed"]),
                feasible=bool(s["feasible"][i]),
                error_mean=float(s["error_mean"][i]),
                error_std=float(s["error_std"][i]),
                metrics_stderr=s["metrics_stderr"][i],
                certified=bool(s["certified_mask"][i]),
            ))
        return recs

    # -- pareto feeds (mirror SweepResult's methods) ------------------------

    def _masked(self, feasible_only: bool):
        s = self.summary(["metrics", "power_rel", "feasible"])
        mask = s["done_mask"] & (s["feasible"].astype(bool)
                                 if feasible_only else True)
        return s["metrics"][mask], s["power_rel"][mask]

    def correlations(self, feasible_only: bool = True) -> np.ndarray:
        """|Pearson| cross-metric correlations over committed runs (paper
        Fig. 6) — bit-identical to ``SweepResult.correlations``."""
        from repro.core.pareto import metric_correlations
        metrics, _ = self._masked(feasible_only)
        return metric_correlations(metrics)

    def fronts(self, metric_indices: Sequence[int] = (M.MAE, M.ER),
               feasible_only: bool = True) -> dict[int, np.ndarray]:
        """Power-vs-metric Pareto fronts (paper Figs. 7-14 axes) —
        bit-identical to ``SweepResult.fronts``."""
        from repro.core.pareto import sweep_fronts
        metrics, power = self._masked(feasible_only)
        return sweep_fronts(power, metrics, metric_indices)
