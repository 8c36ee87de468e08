"""High-level search API: one call = one paper-style approximation run.

``run_search`` is the programmatic entry point used by the examples and the
benchmark harness; ``run_sweep`` executes a grid of constraint configurations
(the paper's experimental methodology, Sec. IV) and returns all evolved
circuits with their final measurements, ready for Pareto analysis.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import golden as G
from repro.core import metrics as M
from repro.core import simulate
from repro.core.evolve import EvolveConfig, EvolveResult, evolve
from repro.core.fitness import ConstraintSpec
from repro.core.genome import CGPSpec, Genome
from repro.core.power import circuit_cost_from_probs


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    width: int = 8               # operand bit width (paper: 8x8 multiplier)
    kind: str = "mul"            # "mul" | "add"
    n_n: int = 400               # CGP nodes (paper: 400)
    evolve: EvolveConfig = EvolveConfig()


@dataclasses.dataclass
class CircuitRecord:
    """One evolved circuit with its full characterization."""
    genome_nodes: np.ndarray
    genome_outs: np.ndarray
    metrics: np.ndarray          # (N_METRICS,) final metric vector
    power_rel: float             # power(C)/power(G)
    constraint: str              # human-readable constraint description
    seed: int
    feasible: bool
    error_mean: float = 0.0      # signed error mean (Fig. 13 analyses)
    error_std: float = 0.0
    # (N_METRICS,) standard errors of the final metrics (DESIGN.md §9):
    # all-zero under eval_mode="exhaustive" (a census has no sampling
    # error), CLT estimates from the sample second moments when sampled.
    metrics_stderr: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(M.N_METRICS, np.float32))
    # True when ``metrics`` is EXACT over the full input cube (DESIGN.md
    # §10): always under eval_mode="exhaustive" (the census is its own
    # certificate); for sampled runs only after the exact-verification
    # escalation tier (``core.certify``) re-measured this circuit — sampled
    # WCE/ACC0/GAUSS values are otherwise only lower bounds / on-sample
    # verdicts and cannot certify a hard constraint.
    certified: bool = False


def problem_arrays(cfg: SearchConfig):
    """(golden genome, spec, in_planes, golden values, golden power).

    ``cfg.evolve.eval_mode`` picks the evaluation-input pair (DESIGN.md §9):
    the exhaustive 2^(2w) cube (historic default, bit-identical arrays), or
    a deterministic ``core.sampling`` operand sample packed into the same
    bit-plane/golden-value contract — the ONE branch point of the sampled
    mode; every consumer downstream is mode-agnostic.  The golden power
    normalizer is measured on the same inputs as the candidates (under
    sampling it becomes a sample estimate of the activity model, consistent
    across the numerator and denominator of ``power_rel``).
    """
    build = G.array_multiplier if cfg.kind == "mul" else G.ripple_carry_adder
    gold, spec = build(cfg.width, n_n=cfg.n_n)
    ecfg = cfg.evolve
    if ecfg.eval_mode == "sampled":
        from repro.core import sampling
        planes_np, gvals_np = sampling.sample_problem(
            cfg.width, cfg.kind, ecfg.sample_size, ecfg.input_dist,
            ecfg.sample_seed)
        in_planes = jnp.asarray(planes_np)
        gvals = jnp.asarray(gvals_np)
    else:
        in_planes = simulate.input_planes(spec.n_i)
        gvals = jnp.asarray(G.golden_values(cfg.width, cfg.kind))
    wires = simulate.simulate_planes(gold, spec, in_planes)
    probs = simulate.signal_probabilities(wires[spec.n_i:])
    gpower = circuit_cost_from_probs(gold, spec, probs).power
    return gold, spec, in_planes, gvals, gpower


def run_search(cfg: SearchConfig, constraint: ConstraintSpec,
               seed: int = 0) -> tuple[CircuitRecord, EvolveResult]:
    """One (1+λ) run under one combined constraint (paper Eq. 8/9)."""
    gold, spec, in_planes, gvals, gpower = problem_arrays(cfg)
    # NOTE: cfg.evolve.seed is deliberately NOT replaced — the PRNG key below
    # carries the seed, and EvolveConfig is a jit static arg, so baking the
    # seed in would re-trace `evolve` for every run of a sweep.
    ecfg = dataclasses.replace(cfg.evolve,
                               gauss_sigma=constraint.gauss_sigma)
    thr = jnp.asarray(constraint.thresholds())
    res = evolve(spec, ecfg, gold, thr, in_planes, gvals, gpower,
                 jax.random.PRNGKey(seed))
    rec = characterize(res.parent, spec, cfg, constraint, seed,
                       in_planes, gvals, gpower)
    return rec, res


def characterize(genome: Genome, spec: CGPSpec, cfg: SearchConfig,
                 constraint: ConstraintSpec, seed: int,
                 in_planes, gvals, gpower) -> CircuitRecord:
    """Full final measurement of an evolved circuit: the batched sweep's
    ``characterize_chunk`` on a batch of one."""
    from repro.core.sweep import characterize_chunk, finish_moments
    sampled = cfg.evolve.eval_mode == "sampled"
    met, stderr, prel, feas, count, moments = characterize_chunk(
        spec, constraint.gauss_sigma, genome.nodes[None], genome.outs[None],
        jnp.asarray(constraint.thresholds())[None], in_planes, gvals, gpower,
        sampled=sampled)
    emean, estd = finish_moments(count, moments)
    return CircuitRecord(
        genome_nodes=np.asarray(genome.nodes),
        genome_outs=np.asarray(genome.outs),
        metrics=np.asarray(met[0]),
        power_rel=float(prel[0]),
        constraint=constraint.describe(),
        seed=seed,
        feasible=bool(feas[0]),
        error_mean=float(emean[0]),
        error_std=float(estd[0]),
        metrics_stderr=np.asarray(stderr[0]),
        # serial path has no escalation driver: exact iff exhaustive
        certified=not sampled,
    )


def run_sweep(cfg: SearchConfig, constraints: Sequence[ConstraintSpec],
              seeds: Sequence[int] = (0,), *,
              sweep=None) -> list[CircuitRecord]:
    """Grid of constraint configs × seeds (paper Sec. IV methodology).

    Executed by the batched engine (``core.sweep``): the whole grid runs as
    vmapped chunks of one jit'd program instead of a serial Python loop.
    With ``cfg.evolve.backend="pallas"`` each chunk generation evaluates its
    whole (chunk × λ) population in ONE fused kernel dispatch (the genome
    axis on the Pallas grid); results stay bit-identical to the serial loop.

    Args:
      cfg: the problem (operand ``width``, ``kind``, CGP geometry, evolve
        budget).  ``cfg.evolve.seed`` is ignored — each run's PRNG stream is
        ``PRNGKey(seed)``, so a run's result depends only on its own
        ``(constraint, seed)`` pair, never on the rest of the grid (grids
        sharing a config row share its result bit-for-bit).
      constraints: grid rows, outer loop of the run order.
      seeds: inner loop of the run order.
      sweep: ``sweep.SweepConfig`` execution knobs — chunking, checkpoint
        resume, ``keep_history`` mode and the streaming ``results_dir``
        spill (``core.results``).  Default: ``keep_history="none"``, no
        spill (per-generation histories are unreachable through this
        records-only API; set a ``results_dir`` and read them back through
        ``results.SweepResultReader``, or call ``run_sweep_batched``).

    Returns:
      ``CircuitRecord`` list in grid order (constraints outer, seeds inner),
      one per completed run — identical to ``run_sweep_serial``.
    """
    from repro.core.sweep import SweepConfig, run_sweep_batched
    sweep = sweep or SweepConfig(keep_history="none")
    return run_sweep_batched(cfg, constraints, seeds, sweep).records


def run_sweep_serial(cfg: SearchConfig, constraints: Sequence[ConstraintSpec],
                     seeds: Sequence[int] = (0,)) -> list[CircuitRecord]:
    """Reference serial loop (one ``evolve`` dispatch per run).

    Kept as the equivalence oracle for the batched engine (tests) and the
    baseline of the ``sweep`` microbenchmark.
    """
    records = []
    for con in constraints:
        for seed in seeds:
            rec, _ = run_search(cfg, con, seed)
            records.append(rec)
    return records
