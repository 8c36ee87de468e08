"""Batched constraint-grid sweep engine (paper Sec. IV at scale).

The paper's experiment is a grid of ~27k (1+λ) runs over combined
error-constraint configurations × seeds.  ``search.run_sweep`` used to walk
that grid with a serial Python loop around ``evolve`` — one XLA program
dispatch per run, golden arrays rebuilt and the evolve program re-traced per
seed.  This module evaluates the whole grid as ONE jit'd program per chunk:

  * the threshold grid is stacked into a ``(chunk, N_METRICS)`` matrix and the
    per-run PRNG keys into ``(chunk, 2)``; ``make_batched_generation_step`` /
    ``init_state_batched`` from ``core.evolve`` carry that run axis — mutation
    and selection are vmapped per run, but each generation's whole
    (chunk × λ) offspring population is evaluated in one shot (for
    ``backend="pallas"`` a single fused kernel dispatch with the genome axis
    on the Pallas grid),
  * the golden circuit, input cube and golden power come from ONE
    ``problem_arrays`` call, are closed over, and are never re-traced — under
    vmap they stay unbatched so XLA shares them across every run,
  * generations are scanned on the OUTSIDE with the run axis inside the scan
    body (``scan_generations`` over a vmapped step), so candidate evaluation
    fuses across runs — on CPU this amortizes the per-op scheduling overhead
    that dominates small-width runs; on TPU it feeds the VPU full lanes.

Chunked execution bounds device memory: the grid is split into
``chunk_size``-run batches (peak live simulation state is roughly
``chunk_size × λ × n_wires × n_words × 4`` bytes) and chunks are padded to a
fixed width so every chunk with the same Gauss σ reuses one compiled program.
Runs with different ``gauss_sigma`` cannot share a trace (σ fixes the static
histogram bin edges), so chunk boundaries additionally break on σ changes.

Progress is resumable: after every ``checkpoint_every`` chunks the full sweep
state (evolved parent/best genomes, fitness, final metrics and optional
per-generation histories) is committed through ``repro.checkpoint.store``;
a restarted sweep with the same grid fingerprint continues mid-grid from the
last committed chunk.

Results stream to disk instead of accumulating in host RAM when
``SweepConfig.results_dir`` is set: every finished chunk is committed as one
append-only shard through ``core.results.SweepResultWriter`` and the shard
set is itself the resume state (see ``core.results`` for the schema).  The
``keep_history`` mode picks what stays in RAM — at paper scale (27k runs)
only ``"summary"``/``"none"`` keep the host footprint flat.

Multi-host execution shards the grid over the ``pod`` mesh axis
(DESIGN.md §6): with ``SweepConfig.n_pods > 1`` the deterministic chunk plan
is round-robin partitioned across pods (``results.pod_partition``) and THIS
process executes only pod ``pod_index``'s slice — each pod dispatches its own
(chunk × λ) fused program and commits its own shards into the shared
``results_dir``, whose one-time manifest is the only cross-pod coordination.
Resume is per pod (each pod skips the committed prefix of its OWN span
sequence), and because every chunk's bytes are a deterministic function of
the fingerprinted grid, a pod-sharded sweep produces bit-identical shards to
the single-host run of the same grid.  Every dispatch evaluates the whole
input cube on one chip (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core import certify
from repro.core import metrics as M
from repro.core import migrate as migrate_mod
from repro.core.commit import ChunkCommitter
from repro.core.evalcache import PhenotypeLRU
from repro.core.results import (SweepResultReader, SweepResultWriter,
                                normalize_history_mode, pod_partition)
from repro.core.evolve import (EvolveConfig, eval_segment, init_state_batched,
                               make_batched_generation_step, mutate_segment,
                               scan_generations, select_segment)
from repro.core.fitness import ConstraintSpec, feasible
from repro.core.genome import CGPSpec, Genome, phenotype_digests
from repro.core.power import circuit_cost_from_probs
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    """Execution knobs of the batched sweep (grid semantics live in
    ``SearchConfig``/``ConstraintSpec``).

    ``keep_history`` picks where per-generation parent histories live
    (legacy bools are accepted: ``True`` -> ``"full"``, ``False`` ->
    ``"none"``):

      * ``"full"``    — histories kept in host RAM on the returned
        ``SweepResult`` (``hist_*`` arrays, ``(n_runs, gens, ...)``) and,
        when ``results_dir`` is set, spilled to shards too.  RAM grows with
        grid size — fine for small grids, not for the paper's 27k runs.
      * ``"summary"`` — histories are spilled to ``results_dir`` shards but
        NEVER held in RAM (``SweepResult.hist_*`` are None); read them back
        one chunk at a time via ``SweepResultReader.iter_history``.  Peak
        host memory is one chunk of history — independent of grid size.
        Without a ``results_dir`` the histories are dropped.
      * ``"none"``    — no histories anywhere (smallest shards/checkpoints).

    ``results_dir`` enables the streaming results layer (``core.results``):
    every finished chunk commits one append-only shard, and the shard set is
    the resume state — a restarted sweep with the same grid fingerprint
    continues after the last committed shard (``checkpoint_dir`` is then
    redundant for resume; shards commit every chunk, checkpoints every
    ``checkpoint_every`` chunks).

    ``checkpoint_dir`` is best given one directory per grid: resume matches
    checkpoints by grid fingerprint so foreign checkpoints are never loaded,
    but step numbers are run counts, and two grids sharing a directory can
    overwrite each other's equal-numbered steps (older ones are also pruned,
    keep=3, after each commit).

    ``n_pods``/``pod_index`` pod-shard the grid (DESIGN.md §6): the chunk
    plan is round-robin partitioned over ``n_pods`` and this process runs
    only pod ``pod_index``'s slice (every pod of a multi-host launch runs
    the same command with its own index; ``pod_index=None`` resolves it from
    the active mesh / process index via ``parallel.ctx.default_pod_index``).
    Multi-pod sweeps REQUIRE a shared ``results_dir`` (the shard set is the
    only resume state whose coverage tolerates per-pod prefixes) and refuse
    ``checkpoint_dir`` (checkpoints assume one global prefix).

    ``dedup`` overrides the phenotype-dedup evaluation cache for every chunk
    of THIS sweep (``None`` defers to ``cfg.evolve.dedup``; DESIGN.md §8):
    offspring sharing an active subgraph are evaluated once per generation,
    and a cross-generation host LRU (``dedup_cache_size`` entries, keyed by
    phenotype digest × grid fingerprint × σ) skips the kernel for phenotypes
    it has already measured.  Execution-only — results are bit-identical
    with the cache on or off, the grid fingerprint ignores it, and
    checkpoints/shards resume across the setting.  Measured hit/miss
    counters come back on ``SweepResult.dedup_stats``.

    ``async_commit`` moves shard/checkpoint commits onto a bounded
    single-worker background thread (``core.commit.ChunkCommitter``,
    DESIGN.md §11): chunk N+1 dispatches while chunk N's npz write + fsync
    runs off-thread.  Span order, the atomic-rename commit contract and
    error surfacing are all preserved (worker exceptions re-raise at the
    next submit/drain; the queue drains on every exit, ``KeyboardInterrupt``
    included), and the committed BYTES are identical to the synchronous
    path's — execution-only like ``dedup``, never fingerprinted.
    ``commit_depth`` bounds how many chunk commits may be pending before
    the sweep loop blocks (host-memory backpressure).

    ``migrate_every`` turns on chunk-level island migration between pods
    (``core.migrate``, DESIGN.md §11): every ``migrate_every`` chunks of its
    OWN slice a pod publishes its per-σ-group elites as an atomic
    fingerprint-stamped ``migrants_pod{i}_gen{g}.npz`` under ``results_dir``,
    and every chunk of epoch ``e >= 1`` folds the epoch ``e-1`` elites of
    ALL publishing pods into its initial population under a deterministic
    ``(power_rel, digest)`` merge rule.  RESULT-CHANGING — unlike
    ``async_commit`` it joins the grid fingerprint (only when on, together
    with ``n_pods``/``chunk_size``/``MIGRATE_TOP_K``, since the epoch
    structure depends on the plan), so ``migrate_every=0`` fingerprints and
    shards stay byte-identical to the migration-less engine.  Requires
    ``results_dir`` (the migrant files ride the shared directory).
    Importing waits up to ``migrate_timeout`` seconds for a lagging pod's
    migrant file before raising.
    """
    chunk_size: int = 32          # runs per jit'd batch (device-memory bound)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1     # chunks between checkpoint commits
    keep_history: str | bool = "full"  # "none" | "summary" | "full"
    results_dir: str | None = None     # streaming shard spill (core.results)
    max_chunks: int | None = None  # stop after N chunks (tests/ops drains)
    n_pods: int = 1               # pod-shard the chunk plan (DESIGN.md §6)
    pod_index: int | None = None  # this process's pod (None: resolve via ctx)
    dedup: bool | None = None     # phenotype-dedup cache override (§8)
    dedup_cache_size: int = 1 << 16  # cross-generation LRU entry bound
    async_commit: bool = False    # background shard/checkpoint commits (§11)
    commit_depth: int = 2         # pending commits before submit blocks
    migrate_every: int = 0        # chunks per migration epoch; 0 = off (§11)
    migrate_timeout: float = 120.0  # seconds to wait for a peer's migrants

    def __post_init__(self):
        if self.dedup_cache_size < 1:
            raise ValueError(f"dedup_cache_size must be >= 1, got "
                             f"{self.dedup_cache_size}")
        if self.commit_depth < 1:
            raise ValueError(
                f"commit_depth must be >= 1, got {self.commit_depth}")
        if self.migrate_every < 0:
            raise ValueError(
                f"migrate_every must be >= 0, got {self.migrate_every}")
        if self.migrate_every > 0 and self.results_dir is None:
            raise ValueError(
                "migrate_every needs a results_dir: migrant files ride "
                "the shared results directory (DESIGN.md §11)")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {self.chunk_size}")
        if self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {self.checkpoint_every}")
        if self.n_pods < 1:
            raise ValueError(f"n_pods must be >= 1, got {self.n_pods}")
        if self.pod_index is not None and not (
                0 <= self.pod_index < self.n_pods):
            raise ValueError(f"pod_index {self.pod_index} outside "
                             f"[0, {self.n_pods})")
        if self.n_pods > 1:
            if self.results_dir is None:
                raise ValueError(
                    "multi-pod sweeps need a shared results_dir: the shard "
                    "set is the only resume state that tolerates per-pod "
                    "prefixes (DESIGN.md §6)")
            if self.checkpoint_dir is not None:
                raise ValueError(
                    "checkpoint_dir assumes a single global progress prefix; "
                    "multi-pod sweeps resume through the results_dir shards")
        object.__setattr__(self, "keep_history",
                           normalize_history_mode(self.keep_history))


@dataclasses.dataclass
class SweepResult:
    """Stacked output of a (possibly partial) grid sweep.

    Run-major arrays are ordered like the grid: ``constraints`` outer,
    ``seeds`` inner.  Execution internally groups runs by ``gauss_sigma``, so
    on an interrupted sweep (``max_chunks``) the completed rows need not be a
    prefix — ``done_mask`` marks them; ``records`` holds exactly the
    completed runs, in grid order.

    ``hist_*`` arrays are populated only with ``keep_history="full"``; in
    ``"summary"`` mode the histories live in the ``results_dir`` shards
    (``reader().iter_history()``), in ``"none"`` mode nowhere.
    """
    records: list                      # list[CircuitRecord], len == completed
    thresholds: np.ndarray             # (n_runs, N_METRICS)
    metrics: np.ndarray                # (n_runs, N_METRICS) final measurement
    metrics_stderr: np.ndarray         # (n_runs, N_METRICS) per-metric SEs
                                       # (zeros for exhaustive grids, §9)
    power_rel: np.ndarray              # (n_runs,)
    feasible: np.ndarray               # (n_runs,) bool
    best_fit: np.ndarray               # (n_runs,)
    hist_power_rel: np.ndarray | None  # (n_runs, gens)
    hist_fit: np.ndarray | None        # (n_runs, gens)
    hist_metrics: np.ndarray | None    # (n_runs, gens, N_METRICS)
    done_mask: np.ndarray              # (n_runs,) bool — rows populated
    completed: int
    n_runs: int
    runs_per_sec: float                # throughput of this call (0 if resumed
                                       # with nothing left to do)
    results_dir: str | None = None     # shard spill location, if streaming
    dedup_stats: dict | None = None    # phenotype-cache counters (§8), when
                                       # the dedup path ran this call
    certified_mask: np.ndarray | None = None  # (n_runs,) bool — rows whose
                                       # error metrics are EXACT (§10): the
                                       # whole grid for exhaustive sweeps,
                                       # escalated elites for sampled ones
    certify_stats: dict | None = None  # escalation counters, when the
                                       # §10 escalation tier ran this call
    migrate_stats: dict | None = None  # island-migration counters (§11):
                                       # published/imported/adopted/waited_s,
                                       # when chunk-level migration ran

    def reader(self) -> SweepResultReader:
        """Open the shard set this sweep streamed to (requires a
        ``SweepConfig.results_dir``)."""
        if self.results_dir is None:
            raise ValueError("sweep ran without results_dir — no shards")
        return SweepResultReader(self.results_dir)

    def correlations(self, feasible_only: bool = True) -> np.ndarray:
        """|Pearson| cross-metric correlation over completed runs."""
        from repro.core.pareto import metric_correlations
        mask = self.done_mask & (self.feasible if feasible_only else True)
        return metric_correlations(self.metrics[mask])

    def fronts(self, metric_indices: Sequence[int] = (M.MAE, M.ER),
               feasible_only: bool = True) -> dict[int, np.ndarray]:
        """Power-vs-metric Pareto fronts (paper Figs. 7-14 axes)."""
        from repro.core.pareto import sweep_fronts
        mask = self.done_mask & (self.feasible if feasible_only else True)
        return sweep_fronts(self.power_rel[mask],
                            self.metrics[mask], metric_indices)


# --------------------------------------------------------------------------
# Batched core (one chunk = one XLA program)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def evolve_chunk(spec: CGPSpec, cfg: EvolveConfig, golden: Genome,
                 thr_mat: jax.Array, in_planes: jax.Array,
                 golden_vals: jax.Array, golden_power: jax.Array,
                 keys: jax.Array):
    """Evolve ``thr_mat.shape[0]`` runs in one program.

    The serial ``evolve`` semantics are preserved per run (same per-run PRNG
    stream, same selection): generation scan outside, run axis inside the
    scan body via ``evolve.make_batched_generation_step``, which evaluates
    the whole (chunk × λ) offspring population in one shot per generation —
    for ``backend="pallas"`` that is a single fused kernel dispatch with the
    genome axis on the Pallas grid.  Histories are returned run-major.
    """
    batched_step = make_batched_generation_step(spec, cfg)
    state0 = init_state_batched(spec, cfg, golden, thr_mat, in_planes,
                                golden_vals, keys)
    state, (hp, hm, hf) = scan_generations(batched_step, state0, thr_mat,
                                           in_planes, golden_vals,
                                           golden_power, cfg.generations)
    return state, hp.T, jnp.swapaxes(hm, 0, 1), hf.T


_init_state_batched_jit = jax.jit(
    init_state_batched, static_argnames=("spec", "cfg"))


@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def evolve_chunk_seeded(spec: CGPSpec, cfg: EvolveConfig,
                        state0: "jax.Array", thr_mat: jax.Array,
                        in_planes: jax.Array, golden_vals: jax.Array,
                        golden_power: jax.Array):
    """``evolve_chunk`` from an EXPLICIT initial state (same generation scan,
    same histories) — the migration path's entry point (DESIGN.md §11): the
    driver builds ``state0`` via ``_init_state_batched_jit`` and optionally
    folds imported migrant elites into it (``migrate.fold_segment``) before
    the scan.  A migrating sweep routes EVERY chunk through this function —
    epoch-0 chunks (nothing to import yet) included — so all chunks of a σ
    group share one trace."""
    batched_step = make_batched_generation_step(spec, cfg)
    state, (hp, hm, hf) = scan_generations(batched_step, state0, thr_mat,
                                           in_planes, golden_vals,
                                           golden_power, cfg.generations)
    return state, hp.T, jnp.swapaxes(hm, 0, 1), hf.T


def _evolve_chunk_dedup(spec: CGPSpec, cfg: EvolveConfig, golden: Genome,
                        thr_mat: jax.Array, in_planes: jax.Array,
                        golden_vals: jax.Array, golden_power: jax.Array,
                        keys: jax.Array, cache: PhenotypeLRU,
                        scope: tuple, state0=None):
    """``evolve_chunk`` with the phenotype-dedup cache (DESIGN.md §8).

    The generation loop runs on the host so the dedup decision can happen in
    Python between jit segments: per generation, (1) ``mutate_segment``
    draws the (C × λ) offspring with exactly the scanned path's PRNG
    stream, (2) the offspring are canonicalized+hashed on the host and
    reduced to unique *uncached* phenotypes, (3) ``eval_segment`` dispatches
    only those (padded to a power-of-two bucket so jit retraces stay
    logarithmic in the population size), (4) the cached/shared results are
    scattered back to every copy and ``select_segment`` finishes the step.
    Every evaluation result a copy receives is the phenotype-invariant
    (metric_vec, power) projection (see ``core.evalcache``), so the returned
    state and histories are bit-identical to ``evolve_chunk``'s.

    ``scope`` pins the cache entries' validity (grid fingerprint, σ); the
    LRU itself lives across chunks of one sweep call.  An explicit
    ``state0`` (the migration path's folded initial state, DESIGN.md §11)
    replaces the golden-parent init.
    """
    C, lam = thr_mat.shape[0], cfg.lam
    if state0 is None:
        state0 = _init_state_batched_jit(spec, cfg, golden, thr_mat,
                                         in_planes, golden_vals, keys)
    state = state0
    stats = cache.stats
    hp, hm, hf = [], [], []
    for _ in range(cfg.generations):
        key, offspring = mutate_segment(spec, cfg, state)
        nodes = np.asarray(offspring.nodes).reshape(C * lam, spec.n_n, 3)
        outs = np.asarray(offspring.outs).reshape(C * lam, spec.n_o)
        digests = phenotype_digests(nodes, outs, spec)
        stats.candidates += len(digests)

        first: dict[bytes, int] = {}
        for i, d in enumerate(digests):
            if d in first:
                stats.dup_hits += 1
            else:
                first[d] = i
        values: dict[bytes, tuple] = {}
        miss_digests: list[bytes] = []
        for d in first:
            val = cache.get((d,) + scope)
            if val is None:
                miss_digests.append(d)
            else:
                stats.lru_hits += 1
                values[d] = val
        if miss_digests:
            rows = [first[d] for d in miss_digests]
            n_miss = len(rows)
            stats.evaluated += n_miss
            pad = 1 << (n_miss - 1).bit_length()  # bucketed jit shapes
            sel = np.asarray(rows + rows[:1] * (pad - n_miss))
            mv, pw = eval_segment(spec, cfg, jnp.asarray(nodes[sel]),
                                  jnp.asarray(outs[sel]), in_planes,
                                  golden_vals)
            mv = np.asarray(mv)[:n_miss]
            pw = np.asarray(pw)[:n_miss]
            for j, d in enumerate(miss_digests):
                values[d] = (mv[j], pw[j])
                cache.put((d,) + scope, values[d])

        mets = np.stack([values[d][0] for d in digests])
        pows = np.asarray([values[d][1] for d in digests], np.float32)
        state, (p_rel, p_met, p_fit) = select_segment(
            spec, cfg, state, key, offspring,
            jnp.asarray(mets.reshape(C, lam, M.N_METRICS)),
            jnp.asarray(pows.reshape(C, lam)), thr_mat, golden_power)
        hp.append(np.asarray(p_rel))
        hm.append(np.asarray(p_met))
        hf.append(np.asarray(p_fit))

    gens_axis = 1  # run-major histories, like evolve_chunk's returns
    return (state, np.stack(hp, axis=gens_axis),
            np.stack(hm, axis=gens_axis), np.stack(hf, axis=gens_axis))


@functools.partial(jax.jit,
                   static_argnames=("spec", "gauss_sigma", "sampled"))
def characterize_chunk(spec: CGPSpec, gauss_sigma: float, nodes: jax.Array,
                       outs: jax.Array, thr_mat: jax.Array,
                       in_planes: jax.Array, golden_vals: jax.Array,
                       golden_power: jax.Array, sampled: bool = False):
    """Final measurement of stacked genomes (metrics, power with the delay
    scan, feasibility, error moments) in one CGP kernel dispatch.

    Returns (metrics, stderr, power_rel, feasible, count, moments):
    ``sampled`` turns the second-moment partials into per-metric standard
    errors (DESIGN.md §9), zeros otherwise (a census has no sampling error);
    ``moments`` holds the digits of the exact Σd and Σd², which
    ``finish_moments`` turns into the signed error's mean and standard
    deviation on the host.
    """
    genomes = Genome(nodes, outs)
    with jax.named_scope("characterize.kernel"):
        partials, pops = kops.cgp_eval_batched(
            genomes, spec, in_planes, golden_vals, gauss_sigma,
            moments=True)
    with jax.named_scope("characterize.moments"):
        moments = kops.exact_sums(partials.moments)[1:]
    finalize = lambda p: M.finalize_metrics(p, spec.n_o, gauss_sigma)
    met = jax.vmap(finalize)(partials)
    if sampled:
        sterr = jax.vmap(lambda p: M.metric_stderr(p, spec.n_o))(partials)
    else:
        sterr = jnp.zeros(met.shape, jnp.float32)
    probs = pops / partials.count.astype(jnp.float32)[:, None]
    cost = jax.vmap(lambda g, p: circuit_cost_from_probs(g, spec, p))(
        genomes, probs)
    return (met, sterr, cost.power / golden_power,
            jax.vmap(feasible)(met, thr_mat), partials.count, moments)


def finish_moments(count, moments) -> tuple[np.ndarray, np.ndarray]:
    """float32 (mean, std) of the signed error from ``characterize_chunk``'s
    ``count`` and ``moments``, in exact integer arithmetic on the host."""
    mean, std = M.exact_error_moments(np.asarray(count),
                                      *(np.asarray(d) for d in moments))
    return mean.astype(np.float32), std.astype(np.float32)


# --------------------------------------------------------------------------
# Grid planning / checkpoint layout
# --------------------------------------------------------------------------

def sweep_grid(constraints: Sequence[ConstraintSpec],
               seeds: Sequence[int]) -> list[tuple[ConstraintSpec, int]]:
    """Run order of the grid: constraints outer, seeds inner (the historical
    ``run_sweep`` order — records stay comparable across engines)."""
    return [(con, int(seed)) for con in constraints for seed in seeds]


def plan_chunks(sigmas: np.ndarray, chunk_size: int) -> list[tuple[int, int]]:
    """[start, end) chunk spans: ≤ chunk_size runs, uniform gauss_sigma."""
    spans, start = [], 0
    n = len(sigmas)
    while start < n:
        end = min(start + chunk_size, n)
        brk = np.flatnonzero(sigmas[start:end] != sigmas[start])
        if brk.size:
            end = start + int(brk[0])
        spans.append((start, end))
        start = end
    return spans


def grid_fingerprint(cfg, grid, keep_history: str | bool,
                     migrate: dict | None = None) -> str:
    """Identity of (problem, grid, history mode) — guards checkpoint resume
    AND the results-shard manifest (``core.results``).  The history mode is
    part of the identity because it changes the buffer/shard schema.

    ``migrate`` carries the chunk-level island-migration knobs when (and
    only when) migration is on (DESIGN.md §11) — migration is
    result-changing and its epoch structure depends on the chunk plan and
    pod partition, so they join the identity; ``None`` (migration off)
    leaves every pre-§11 fingerprint unchanged."""
    ecfg = cfg.evolve
    # the legacy bool spellings hash as bools so checkpoints written before
    # the mode strings existed still resume ("summary" is new, no legacy)
    keep_history = {"full": True, "none": False}.get(
        normalize_history_mode(keep_history), "summary")
    ident = {
        "width": cfg.width, "kind": cfg.kind, "n_n": cfg.n_n,
        "generations": ecfg.generations, "lam": ecfg.lam,
        "mutation_rate": ecfg.mutation_rate, "backend": ecfg.backend,
        "migrate_every": ecfg.migrate_every,
        "keep_history": keep_history,
        "grid": [(con.describe(), con.gauss_sigma, seed)
                 for con, seed in grid],
        "thresholds": hashlib.sha256(
            np.stack([con.thresholds() for con, _ in grid]).tobytes()
        ).hexdigest(),
    }
    # eval_mode is RESULT-changing (unlike dedup): sampled grids key
    # on the full sample-stream identity so a checkpoint/shard set can never
    # resume under different evaluation inputs.  Exhaustive grids omit the
    # keys entirely — their fingerprints (and hence pre-§9 checkpoints)
    # are unchanged.
    if ecfg.eval_mode != "exhaustive":
        from repro.core import sampling
        ident["eval_mode"] = ecfg.eval_mode
        ident["sample_stream"] = sampling.stream_fingerprint(
            cfg.width, ecfg.sample_size, ecfg.input_dist, ecfg.sample_seed)
        # the exact-verification escalation tier (DESIGN.md §10) rewrites
        # escalated rows' shard metrics with certified values, so it is
        # result-changing for sampled grids; keys appear only when on, so
        # pre-§10 sampled (and all exhaustive) fingerprints are unchanged
        if getattr(ecfg, "certify", False):
            ident["certify"] = {"budget": int(ecfg.certify_budget)}
    if migrate:
        ident["migrate"] = migrate
    return hashlib.sha256(json.dumps(ident, sort_keys=True,
                                     default=float).encode()).hexdigest()


def _alloc_buffers(spec: CGPSpec, n_runs: int, gens: int,
                   keep_history: str) -> dict[str, np.ndarray]:
    """Grid-order host buffers; ``hist_*`` only in "full" mode (the other
    modes keep host RAM independent of the history volume)."""
    bufs = {
        "parent_nodes": np.zeros((n_runs, spec.n_n, 3), np.int32),
        "parent_outs": np.zeros((n_runs, spec.n_o), np.int32),
        "best_nodes": np.zeros((n_runs, spec.n_n, 3), np.int32),
        "best_outs": np.zeros((n_runs, spec.n_o), np.int32),
        "best_fit": np.zeros((n_runs,), np.float32),
        "metrics": np.zeros((n_runs, M.N_METRICS), np.float32),
        "metrics_stderr": np.zeros((n_runs, M.N_METRICS), np.float32),
        "power_rel": np.zeros((n_runs,), np.float32),
        "feasible": np.zeros((n_runs,), np.uint8),
        "certified_mask": np.zeros((n_runs,), np.uint8),
        "error_mean": np.zeros((n_runs,), np.float32),
        "error_std": np.zeros((n_runs,), np.float32),
    }
    if keep_history == "full":
        bufs["hist_power_rel"] = np.zeros((n_runs, gens), np.float32)
        bufs["hist_fit"] = np.zeros((n_runs, gens), np.float32)
        bufs["hist_metrics"] = np.zeros((n_runs, gens, M.N_METRICS),
                                        np.float32)
    return bufs


def _try_resume(ckpt_dir: str, bufs: dict, fingerprint: str) -> int:
    """Load the newest committed state OF THIS GRID in place; returns runs
    done.  Steps are scanned newest-first by fingerprint so a stale
    checkpoint of a different grid sharing the directory cannot shadow this
    grid's progress."""
    for step in reversed(store.committed_steps(ckpt_dir)):
        if store.load_metadata(ckpt_dir, step).get("fingerprint") \
                != fingerprint:
            continue
        tree, meta = store.load_checkpoint(ckpt_dir, step, bufs)
        for k, v in tree.items():
            bufs[k][...] = np.asarray(v)
        return int(meta["done"])
    return 0


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------

def run_sweep_batched(cfg, constraints: Sequence[ConstraintSpec],
                      seeds: Sequence[int] = (0,),
                      sweep: SweepConfig | None = None) -> SweepResult:
    """Execute the constraint×seed grid with the batched engine.

    ``cfg`` is a ``search.SearchConfig``; per-run results match the serial
    ``run_search`` path (same PRNG streams, same evaluation semantics).
    With ``sweep.results_dir`` every finished chunk streams to an on-disk
    shard (``core.results``) and the shard set is the resume state;
    otherwise resume goes through ``sweep.checkpoint_dir`` as before.

    With ``sweep.n_pods > 1`` this call executes ONE pod's slice of the
    chunk plan (DESIGN.md §6) — run it once per pod (one process per host
    on a multi-host mesh, each with its own ``pod_index``) against the
    shared ``results_dir``; the returned ``SweepResult`` covers everything
    committed so far (this pod's work plus other pods' restored shards),
    with ``done_mask`` marking the covered grid rows.
    """
    from repro.core.search import CircuitRecord, problem_arrays
    # the set-up, up to the first chunk, is the profiler span sweep.prepare
    prepare = jax.profiler.TraceAnnotation("sweep.prepare")
    prepare.__enter__()

    sweep = sweep or SweepConfig()
    mode = sweep.keep_history  # normalized by SweepConfig.__post_init__
    grid = sweep_grid(constraints, seeds)
    n_runs = len(grid)
    gens = cfg.evolve.generations
    gold, spec, in_planes, gvals, gpower = problem_arrays(cfg)

    thr = np.stack([con.thresholds() for con, _ in grid])
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for _, s in grid])
    sigmas = np.array([con.gauss_sigma for con, _ in grid])

    # Execution order groups runs by gauss_sigma (stable, so grid order is
    # kept within a group): sigma-interleaved grids would otherwise shatter
    # into tiny chunks that padding blows back up to chunk_size.  Results are
    # scattered back to grid order; coverage is tracked per execution-order
    # chunk span (deterministic from the fingerprinted grid, so resume stays
    # valid and — multi-pod — tolerates other pods' gaps).
    perm = np.argsort(sigmas, kind="stable")
    chunks = plan_chunks(sigmas[perm], sweep.chunk_size)

    pod = sweep.pod_index
    if pod is None:
        if sweep.n_pods > 1:
            from repro.parallel import ctx
            pod = ctx.default_pod_index(sweep.n_pods)
        else:
            pod = 0

    sampled = cfg.evolve.eval_mode == "sampled"
    # the dedup cache scope must pin WHICH inputs an entry was measured on:
    # the sample-stream fingerprint joins (grid fingerprint, σ) for sampled
    # grids (DESIGN.md §9); exhaustive scopes are unchanged.
    sample_scope: tuple = ()
    if sampled:
        from repro.core import sampling
        sample_scope = (sampling.stream_fingerprint(
            cfg.width, cfg.evolve.sample_size, cfg.evolve.input_dist,
            cfg.evolve.sample_seed),)

    # exact-verification escalation tier (DESIGN.md §10): sampled grids
    # only — an exhaustive census is already exact, so every exhaustive row
    # is marked certified without escalation and ``certify`` is a no-op.
    certify_on = sampled and bool(getattr(cfg.evolve, "certify", False))
    policy = (certify.CertifyPolicy(budget=cfg.evolve.certify_budget)
              if certify_on else None)
    # budget position of each span in the FULL deterministic plan (not this
    # pod's slice), so pods and resumed sweeps budget identically
    plan_pos = {span: i for i, span in enumerate(chunks)}
    n_escalated = 0

    dedup = sweep.dedup if sweep.dedup is not None else cfg.evolve.dedup
    cache = PhenotypeLRU(sweep.dedup_cache_size) if dedup else None

    bufs = _alloc_buffers(spec, n_runs, gens, mode)
    migrating = sweep.migrate_every > 0
    fingerprint = grid_fingerprint(
        cfg, grid, mode,
        migrate={"every": sweep.migrate_every, "n_pods": sweep.n_pods,
                 "chunk_size": sweep.chunk_size,
                 "top_k": migrate_mod.MIGRATE_TOP_K} if migrating else None)
    writer = None
    exec_done = np.zeros(n_runs, bool)  # execution-order positions covered
    if sweep.results_dir:
        writer = SweepResultWriter(
            sweep.results_dir, grid_fingerprint=fingerprint,
            grid_meta=[{"constraint": con.describe(), "seed": seed,
                        "gauss_sigma": con.gauss_sigma}
                       for con, seed in grid],
            n_runs=n_runs, gens=gens, n_n=spec.n_n, n_o=spec.n_o,
            keep_history=mode, chunk_size=sweep.chunk_size,
            chunk_spans=chunks, n_pods=sweep.n_pods,
            problem_meta={"width": cfg.width, "kind": cfg.kind,
                          "n_n": spec.n_n})
        # shards commit every chunk (checkpoints only every
        # checkpoint_every), so they are the freshest resume state
        for s, e in writer.restore(bufs):
            exec_done[s:e] = True
    elif sweep.checkpoint_dir:
        exec_done[:_try_resume(sweep.checkpoint_dir, bufs, fingerprint)] = \
            True

    # multi-pod always has a writer (SweepConfig enforces results_dir), so
    # the manifest-pinned plan is the single source of the pod partition
    my_chunks = chunks if sweep.n_pods == 1 else writer.pod_spans(pod)

    # chunk-level island migration (DESIGN.md §11): the manager's epoch
    # bookkeeping is a function of the deterministic plan alone, so every
    # pod derives the same publish/import schedule with no coordination
    migrator = None
    if migrating:
        pod_lens = [len(s) for s in pod_partition(chunks, sweep.n_pods)]
        migrator = migrate_mod.MigrationManager(
            sweep.results_dir, pod, pod_lens, sweep.migrate_every,
            fingerprint, timeout=sweep.migrate_timeout)
        mig_eval_cache: dict[tuple, tuple | None] = {}

        def _publish_epoch(epoch: int) -> None:
            # derived from the committed grid-order rows of the epoch's own
            # spans — identical whether they ran just now or were restored
            # from shards, so resume republishes identical bytes
            spans = my_chunks[epoch * sweep.migrate_every:
                              (epoch + 1) * sweep.migrate_every]
            rows = np.concatenate([perm[s:e] for s, e in spans])
            migrator.maybe_publish(epoch, migrate_mod.select_elites(
                bufs["parent_nodes"][rows], bufs["parent_outs"][rows],
                bufs["power_rel"][rows],
                bufs["feasible"][rows].astype(bool), sigmas[rows], spec))

        def _migrant_batch(epoch: int, sigma: float, ecfg):
            # one import + evaluation per (epoch, σ); migrants are padded to
            # a power-of-two bucket by repeating row 0 AFTER the real rows,
            # so fold_segment's first-index argmin is unaffected
            mkey = (epoch, float(sigma))
            if mkey not in mig_eval_cache:
                cand = migrator.candidates(epoch, sigma)
                if cand is None:
                    mig_eval_cache[mkey] = None
                else:
                    mn, mo = cand
                    m = len(mn)
                    pad_to = 1 << (m - 1).bit_length()
                    rows = np.r_[np.arange(m),
                                 np.zeros(pad_to - m, np.int64)]
                    mn = jnp.asarray(mn[rows])
                    mo = jnp.asarray(mo[rows])
                    mmv, mpw = eval_segment(spec, ecfg, mn, mo, in_planes,
                                            gvals)
                    mig_eval_cache[mkey] = (mn, mo, mmv, mpw)
            return mig_eval_cache[mkey]

    # background commit pipeline (DESIGN.md §11): shard/checkpoint commits
    # run on one bounded worker so the next chunk dispatches immediately;
    # identical bytes, identical order, errors surface at the next submit
    committer = (ChunkCommitter(sweep.commit_depth) if sweep.async_commit
                 and (writer is not None or sweep.checkpoint_dir) else None)

    def _commit_checkpoint(tree: dict, done: int) -> None:
        store.save_checkpoint(sweep.checkpoint_dir, done, tree,
                              {"done": done, "fingerprint": fingerprint})
        store.cleanup(sweep.checkpoint_dir, keep=3)
    prepare.__exit__(None, None, None)

    t0 = time.perf_counter()
    ran = chunks_run = 0
    try:
        for pos, (start, end) in enumerate(my_chunks):
            if exec_done[start:end].all():
                # committed by a previous (interrupted) sweep; an epoch whose
                # chunks were all restored may still owe its migrant file
                # (crash between the last shard commit and the publish)
                if migrator is not None and \
                        migrator.publishes_at(pos) is not None:
                    _publish_epoch(migrator.publishes_at(pos))
                continue
            if sweep.max_chunks is not None and \
                    chunks_run >= sweep.max_chunks:
                break
            n = end - start
            pad = sweep.chunk_size - n
            sel = perm[np.r_[start:end, np.full(pad, end - 1)]]  # pad: last
            orig = sel[:n]  # grid-order rows this chunk fills
            sigma = float(sigmas[orig[0]])
            ecfg = dataclasses.replace(cfg.evolve, gauss_sigma=sigma, seed=0)

            state0 = None
            if migrator is not None:
                # migration folds into an EXPLICIT initial state; every
                # chunk of a migrating sweep takes the seeded path (epoch-0
                # chunks included) so all chunks of a σ group share a trace
                state0 = _init_state_batched_jit(
                    spec, ecfg, gold, jnp.asarray(thr[sel]), in_planes,
                    gvals, jnp.asarray(keys[sel]))
                ep = migrator.epoch_of(pos)
                if ep >= 1:
                    mb = _migrant_batch(ep - 1, sigma, ecfg)
                    if mb is not None:
                        mn, mo, mmv, mpw = mb
                        state0, n_adopt = migrate_mod.fold_segment(
                            spec, ecfg, state0, mn, mo, mmv, mpw,
                            jnp.asarray(thr[sel]))
                        migrator.stats["adopted"] += int(n_adopt)

            with jax.profiler.TraceAnnotation("sweep.dispatch"):
                if dedup:
                    state, hp, hm, hf = _evolve_chunk_dedup(
                        spec, ecfg, gold, jnp.asarray(thr[sel]), in_planes,
                        gvals, gpower, jnp.asarray(keys[sel]), cache,
                        (fingerprint, sigma) + sample_scope, state0=state0)
                elif state0 is not None:
                    state, hp, hm, hf = evolve_chunk_seeded(
                        spec, ecfg, state0, jnp.asarray(thr[sel]), in_planes,
                        gvals, gpower)
                else:
                    state, hp, hm, hf = evolve_chunk(
                        spec, ecfg, gold, jnp.asarray(thr[sel]), in_planes,
                        gvals, gpower, jnp.asarray(keys[sel]))
                met, sterr, prel, feas, count, moments = characterize_chunk(
                    spec, sigma, state.parent.nodes, state.parent.outs,
                    jnp.asarray(thr[sel]), in_planes, gvals, gpower,
                    sampled=sampled)

            # histories stay in RAM ("full") or spill per chunk to a shard
            keep_hist = mode == "full" or (writer is not None
                                           and mode != "none")
            with jax.profiler.TraceAnnotation("sweep.fetch"):
                chunk_rows = {key: np.asarray(value)[:n] for key, value in (
                    ("parent_nodes", state.parent.nodes),
                    ("parent_outs", state.parent.outs),
                    ("best_nodes", state.best.nodes),
                    ("best_outs", state.best.outs),
                    ("best_fit", state.best_fit),
                    ("metrics", met),
                    ("metrics_stderr", sterr),
                    ("power_rel", prel),
                    ("feasible", feas))}
                chunk_rows["error_mean"], chunk_rows["error_std"] = \
                    finish_moments(np.asarray(count)[:n],
                                   [np.asarray(d)[:n] for d in moments])
                hist_rows = {key: np.asarray(value)[:n] for key, value in (
                    ("hist_power_rel", hp), ("hist_fit", hf),
                    ("hist_metrics", hm))} if keep_hist else {}
            nodes_np = chunk_rows["parent_nodes"]
            outs_np = chunk_rows["parent_outs"]
            met_np = chunk_rows["metrics"] = chunk_rows["metrics"].copy()
            sterr_np = chunk_rows["metrics_stderr"] = \
                chunk_rows["metrics_stderr"].copy()
            feas_np = chunk_rows["feasible"] = \
                chunk_rows["feasible"].astype(np.uint8)
            cert = chunk_rows["certified_mask"] = np.zeros(n, np.uint8)
            if not sampled:
                cert[:] = 1  # the census is its own certificate (§10)
            elif certify_on:
                # escalate the best sampled-feasible elites to the exact
                # tier: their shard rows become certified-exact measurements
                cap = policy.chunk_budget(plan_pos[(start, end)], len(chunks))
                for r in certify.select_escalations(
                        feas_np, chunk_rows["power_rel"], cert, cap):
                    cmet = certify.certified_metrics(
                        nodes_np[r], outs_np[r], spec, cfg.kind, cfg.width,
                        sigma, dispatch_rows=policy.dispatch_rows)
                    met_np[r] = cmet
                    sterr_np[r] = 0.0  # no sampling error left to report
                    feas_np[r] = np.uint8(
                        certify.feasible_np(cmet, thr[orig[r]]))
                    cert[r] = 1
                    n_escalated += 1

            for key, rows in chunk_rows.items():
                bufs[key][orig] = rows
            if mode == "full":
                for key, rows in hist_rows.items():
                    bufs[key][orig] = rows
            if writer is not None:
                chunk_rows["grid_rows"] = orig.astype(np.int32)
                chunk_rows["thresholds"] = thr[orig]
                # histories spill per chunk and (in "summary" mode) never
                # touch a grid-sized host buffer
                chunk_rows.update(hist_rows)
                if committer is not None:
                    # chunk_rows and its arrays are freshly built per chunk
                    # and never mutated after this point — safe to hand to
                    # the worker without copying
                    committer.submit(writer.write_chunk, (start, end),
                                     chunk_rows)
                else:
                    writer.write_chunk((start, end), chunk_rows)

            exec_done[start:end] = True
            ran += n
            chunks_run += 1
            if migrator is not None and \
                    migrator.publishes_at(pos) is not None:
                _publish_epoch(migrator.publishes_at(pos))
            if sweep.checkpoint_dir and (
                    chunks_run % sweep.checkpoint_every == 0
                    or exec_done.all()):
                # single-pod only (multi-pod refuses checkpoint_dir):
                # coverage is a plain prefix, whose length is the step
                done = int(np.argmin(exec_done)) if not exec_done.all() \
                    else n_runs
                if committer is not None:
                    # snapshot: the loop keeps mutating bufs while the
                    # worker serializes
                    committer.submit(_commit_checkpoint,
                                     {k: v.copy() for k, v in bufs.items()},
                                     done)
                else:
                    _commit_checkpoint(bufs, done)
    except BaseException:
        # drain handed-over commits even while unwinding (KeyboardInterrupt
        # included) so they are durably committed or dropped-after-poison,
        # but never mask the in-flight exception with a worker's
        if committer is not None:
            committer.close(raise_errors=False)
        raise
    with jax.profiler.TraceAnnotation("sweep.finish"):
        if committer is not None:
            committer.close()
        dt = time.perf_counter() - t0

        done_mask = np.zeros(n_runs, bool)
        done_mask[perm[exec_done]] = True
        records = []
        for i in np.flatnonzero(done_mask):
            con, seed = grid[i]
            records.append(CircuitRecord(
                genome_nodes=bufs["parent_nodes"][i],
                genome_outs=bufs["parent_outs"][i],
                metrics=bufs["metrics"][i],
                power_rel=float(bufs["power_rel"][i]),
                constraint=con.describe(),
                seed=seed,
                feasible=bool(bufs["feasible"][i]),
                error_mean=float(bufs["error_mean"][i]),
                error_std=float(bufs["error_std"][i]),
                metrics_stderr=bufs["metrics_stderr"][i],
                certified=bool(bufs["certified_mask"][i]),
            ))

    return SweepResult(
        records=records,
        thresholds=thr,
        metrics=bufs["metrics"],
        metrics_stderr=bufs["metrics_stderr"],
        power_rel=bufs["power_rel"],
        feasible=bufs["feasible"].astype(bool),
        best_fit=bufs["best_fit"],
        hist_power_rel=bufs.get("hist_power_rel"),
        hist_fit=bufs.get("hist_fit"),
        hist_metrics=bufs.get("hist_metrics"),
        done_mask=done_mask,
        completed=int(exec_done.sum()),
        n_runs=n_runs,
        runs_per_sec=(ran / dt) if ran else 0.0,
        results_dir=sweep.results_dir,
        dedup_stats=cache.stats.as_dict() if cache is not None else None,
        certified_mask=bufs["certified_mask"].astype(bool),
        certify_stats=({
            "escalated": n_escalated,
            "certified_rows": int(bufs["certified_mask"].sum()),
            "budget": int(cfg.evolve.certify_budget),
        } if certify_on else None),
        migrate_stats=dict(migrator.stats) if migrator is not None else None,
    )
