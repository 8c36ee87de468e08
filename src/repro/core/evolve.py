"""(1+λ) error-oriented CGP evolution — paper Sec. III-B / IV.

Single-island semantics (paper-faithful):
  parent ← golden circuit
  repeat: λ offspring by point mutation; evaluate Eq.(8)/(9) fitness
          (power if all error constraints hold else ∞); offspring with
          fitness ≤ parent replaces it (neutral drift enabled).

Distributed semantics (DESIGN.md §2 — the TPU-cluster formulation):
  mesh axes  pod  × data × model
             │       │       └─ input-space sharding (jnp backend): each
             │       │          shard simulates a 2^n_i/axis slice of the
             │       │          cube; metric partials and signal-prob
             │       │          popcounts combine with psum.
             │       └─ islands: independent (1+λ) runs; every
             │          ``migrate_every`` generations the globally best parent
             │          is broadcast and replaces strictly-worse parents.
             └─ constraint-configuration sweep: every pod slice evolves under
                its own threshold vector (the paper's 27k-run experiment grid).

Everything is jit-compiled; the generation loop is a ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as M
from repro.core import simulate
from repro.core.fitness import fitness as fitness_fn
from repro.core.genome import CGPSpec, Genome
from repro.core.mutate import mutate_population
from repro.core.power import CircuitCost, circuit_cost_from_probs
# Imported at module scope rather than inside the (jit-traced) eval path:
# every backend path shares one ops module and its process-wide
# interpret-mode pin (see ops.default_interpret).
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class EvolveConfig:
    generations: int = 2000
    lam: int = 4                 # λ offspring per generation
    # per-gene mutation probability.  0.004 ≈ 5 mutated genes for the paper's
    # 400-node genome — measured 10-20% better power at equal budget than the
    # 5%-of-genes setting, which cannot descend from the exact seed under
    # tight constraints (EXPERIMENTS.md §Perf hillclimb C4).
    mutation_rate: float = 0.004
    migrate_every: int = 64      # island migration period (distributed mode)
    gauss_sigma: float = 256.0
    seed: int = 0
    backend: str = "jnp"         # "jnp" | "pallas" candidate evaluation
    # Read by nothing: the kernel has one grid order (DESIGN.md §7).
    layout: str = "genome_major"
    # Phenotype-dedup evaluation cache (DESIGN.md §8): the batched sweep
    # engine canonicalizes+hashes each offspring's active subgraph, skips
    # the kernel for phenotypes already seen (within the generation or in
    # the cross-generation LRU) and scatters the cached result back.  A
    # pure execution knob — results are bit-identical with the cache on or
    # off, so it is NOT part of the grid fingerprint and checkpoints/shards
    # resume across the setting.  Honored by ``core.sweep.run_sweep_batched``
    # (the serial ``evolve`` path ignores it).
    dedup: bool = False
    # Evaluation-input mode (DESIGN.md §9).  "exhaustive" scores candidates
    # on the full 2^(2w) input cube (the historic default — bit-identical to
    # the pre-§9 engine); "sampled" scores them on a deterministic
    # ``sample_size``-row operand sample drawn from ``input_dist`` with the
    # counter-based stream seeded by ``sample_seed`` (``core.sampling``).
    # UNLIKE dedup this is result-changing: it IS part of the sweep grid
    # fingerprint and of the dedup cache scope (via the sample-stream
    # fingerprint).  The evolve/sweep engine itself only consumes whatever
    # (in_planes, golden_vals) it is handed — the mode picks which pair
    # ``search.problem_arrays`` builds.
    eval_mode: str = "exhaustive"    # "exhaustive" | "sampled"
    sample_size: int = 1 << 14       # rows (rounded up to pow2 words * 32)
    input_dist: str = "uniform"      # "uniform" | "gaussian" | "empirical"
    sample_seed: int = 0             # sample-stream seed (not the CGP seed)
    # Exact-verification escalation tier (DESIGN.md §10, ``core.certify``):
    # after each sampled sweep chunk is characterized, elites that satisfy
    # the combined constraint ON THE SAMPLE are re-measured EXACTLY over the
    # full 2^(2w) cube (full-cube dispatch or chunked bit-parallel pass),
    # capped per chunk by the adaptive ``CertifyPolicy`` built from
    # ``certify_budget``.  Result-changing for sampled grids (escalated
    # rows' shard metrics become exact), so like ``eval_mode`` it joins the
    # grid fingerprint — but ONLY when on, keeping pre-§10 sampled and all
    # exhaustive fingerprints byte-identical.  No-op under exhaustive
    # evaluation (the census is its own certificate) and on the serial
    # ``evolve`` path.
    certify: bool = False
    certify_budget: int = 8          # base escalations per sweep chunk

    def __post_init__(self):
        # bench/tests/test_compile.py still sets layout="genome_major"
        if self.layout != "genome_major":
            raise ValueError(f"layout must be 'genome_major', got "
                             f"{self.layout!r}")
        if self.eval_mode not in ("exhaustive", "sampled"):
            raise ValueError(f"eval_mode must be 'exhaustive' or 'sampled', "
                             f"got {self.eval_mode!r}")
        from repro.core.sampling import INPUT_DISTS
        if self.input_dist not in INPUT_DISTS:
            raise ValueError(f"input_dist must be one of {INPUT_DISTS}, "
                             f"got {self.input_dist!r}")
        if self.sample_size < 1:
            raise ValueError(
                f"sample_size must be >= 1, got {self.sample_size}")
        if self.certify_budget < 1:
            raise ValueError(
                f"certify_budget must be >= 1, got {self.certify_budget}")


class EvalResult(NamedTuple):
    metric_vec: jax.Array   # (N_METRICS,)
    cost: CircuitCost


class EvolveState(NamedTuple):
    parent: Genome
    parent_fit: jax.Array
    parent_metrics: jax.Array
    parent_power: jax.Array
    best: Genome            # best-ever feasible candidate
    best_fit: jax.Array
    key: jax.Array


class EvolveResult(NamedTuple):
    parent: Genome
    best: Genome
    best_fit: jax.Array
    # per-generation history of the parent: power_rel, metric vec, feasible
    hist_power_rel: jax.Array   # (gens,)
    hist_metrics: jax.Array     # (gens, N_METRICS)
    hist_fit: jax.Array         # (gens,)


# --------------------------------------------------------------------------
# Candidate evaluation
# --------------------------------------------------------------------------

def _eval_jnp(genome: Genome, spec: CGPSpec, in_planes: jax.Array,
              golden_vals: jax.Array, gauss_sigma: float,
              axis_name: str | None) -> EvalResult:
    """Pure-jnp evaluation over (a slice of) the input cube."""
    wires = simulate.simulate_planes(genome, spec, in_planes)
    cand_vals = simulate.unpack_values(wires[genome.outs])
    partials = M.error_partials(golden_vals, cand_vals, gauss_sigma,
                                n_bits=spec.n_o)
    pop = jax.lax.population_count(
        wires[spec.n_i:].view(jnp.uint32)).astype(jnp.float32).sum(axis=-1)
    if axis_name is not None:
        partials = M.combine_partials(partials, axis_name)
        pop = jax.lax.psum(pop, axis_name)
    n_total = partials.count.astype(jnp.float32)
    probs = pop / n_total
    metric_vec = M.finalize_metrics(partials, spec.n_o, gauss_sigma)
    cost = circuit_cost_from_probs(genome, spec, probs, with_delay=False)
    return EvalResult(metric_vec, cost)


def _eval_pallas(genome: Genome, spec: CGPSpec, in_planes: jax.Array,
                 golden_vals: jax.Array, gauss_sigma: float,
                 axis_name: str | None) -> EvalResult:
    """Fused Pallas sim+metrics kernel path (interpret=True on CPU), over
    the whole cube: a sharded cube (``axis_name``) is refused."""
    if axis_name is not None:
        raise ValueError("the Pallas kernel evaluates the whole cube on one "
                         f"chip; got a cube sharded over {axis_name!r}")
    partials, pop = kops.cgp_eval(genome, spec, in_planes, golden_vals,
                                  gauss_sigma)
    n_total = partials.count.astype(jnp.float32)
    probs = pop / n_total
    metric_vec = M.finalize_metrics(partials, spec.n_o, gauss_sigma)
    cost = circuit_cost_from_probs(genome, spec, probs, with_delay=False)
    return EvalResult(metric_vec, cost)


def get_eval_fn(backend: str) -> Callable[..., EvalResult]:
    return {"jnp": _eval_jnp, "pallas": _eval_pallas}[backend]


def _eval_pop_jnp(genomes: Genome, spec: CGPSpec, in_planes: jax.Array,
                  golden_vals: jax.Array, gauss_sigma: float,
                  axis_name: str | None) -> EvalResult:
    """Population (leading-R) evaluation: vmap of the per-genome jnp path;
    ``axis_name`` shards the cube (partials psum across it)."""
    return jax.vmap(lambda g: _eval_jnp(g, spec, in_planes, golden_vals,
                                        gauss_sigma, axis_name))(genomes)


def _eval_pop_pallas(genomes: Genome, spec: CGPSpec, in_planes: jax.Array,
                     golden_vals: jax.Array, gauss_sigma: float,
                     axis_name: str | None) -> EvalResult:
    """Population evaluation as ONE fused kernel dispatch.

    The stacked genome axis lands on the Pallas grid instead of a vmap
    batching dimension (``kernels.ops.cgp_eval_batched``), and the kernel
    evaluates the whole cube on one chip (DESIGN.md §7): a sharded cube
    (``axis_name``) is refused.
    """
    if axis_name is not None:
        raise ValueError("the Pallas kernel evaluates the whole cube on one "
                         f"chip; got a cube sharded over {axis_name!r}")
    partials, pops = kops.cgp_eval_batched(genomes, spec, in_planes,
                                           golden_vals, gauss_sigma)
    n_total = partials.count.astype(jnp.float32)            # (R,)
    probs = pops / n_total[:, None]
    metric_vec = jax.vmap(
        lambda p: M.finalize_metrics(p, spec.n_o, gauss_sigma))(partials)
    cost = jax.vmap(lambda g, pr: circuit_cost_from_probs(
        g, spec, pr, with_delay=False))(genomes, probs)
    return EvalResult(metric_vec, cost)


def get_population_eval(backend: str) -> Callable[..., EvalResult]:
    """Evaluation of (R,)-stacked genomes -> EvalResult with leading R."""
    return {"jnp": _eval_pop_jnp, "pallas": _eval_pop_pallas}[backend]


# --------------------------------------------------------------------------
# Generation step / scan loop
# --------------------------------------------------------------------------

def _select(state: EvolveState, offspring: Genome, fits: jax.Array,
            mets: jax.Array, powers: jax.Array) -> EvolveState:
    i = jnp.argmin(fits)
    off_best = jax.tree.map(lambda x: x[i], offspring)
    take = fits[i] <= state.parent_fit  # '≤' enables neutral drift
    pick = lambda a, b: jax.tree.map(
        lambda x, y: jnp.where(take, x, y), a, b)
    parent = pick(off_best, state.parent)
    parent_fit = jnp.where(take, fits[i], state.parent_fit)
    parent_metrics = jnp.where(take, mets[i], state.parent_metrics)
    parent_power = jnp.where(take, powers[i], state.parent_power)
    improves = fits[i] < state.best_fit
    best = jax.tree.map(lambda x, y: jnp.where(improves, x, y),
                        off_best, state.best)
    best_fit = jnp.minimum(fits[i], state.best_fit)
    return EvolveState(parent, parent_fit, parent_metrics, parent_power,
                       best, best_fit, state.key)


def make_generation_step(spec: CGPSpec, cfg: EvolveConfig,
                         axis_name: str | None = None,
                         island_axis: str | None = None):
    """Build the jit-able one-generation function.

    Returns step(state, thresholds, in_planes, golden_vals, gen_idx) -> state.
    """
    eval_pop = get_population_eval(cfg.backend)

    def step(state: EvolveState, thresholds, in_planes, golden_vals, gen_idx):
        key, k_mut = jax.random.split(state.key)
        offspring = mutate_population(k_mut, state.parent, spec, cfg.lam,
                                      cfg.mutation_rate)
        res = eval_pop(offspring, spec, in_planes, golden_vals,
                       cfg.gauss_sigma, axis_name)
        fits = jax.vmap(fitness_fn)(res.cost.power,
                                    res.metric_vec,
                                    jnp.broadcast_to(thresholds,
                                                     (cfg.lam,) + thresholds.shape))
        state = _select(state._replace(key=key), offspring, fits,
                        res.metric_vec, res.cost.power)

        if island_axis is not None:
            state = jax.lax.cond(
                (gen_idx + 1) % cfg.migrate_every == 0,
                lambda s: _migrate(s, island_axis),
                lambda s: s, state)
        return state

    return step


def _migrate(state: EvolveState, axis: str) -> EvolveState:
    """Broadcast the globally best parent to strictly-worse islands."""
    all_fit = jax.lax.all_gather(state.parent_fit, axis)       # (n_isl,)
    all_parent = jax.lax.all_gather(state.parent, axis)        # stacked tree
    j = jnp.argmin(all_fit)
    g_best_fit = all_fit[j]
    g_best = jax.tree.map(lambda x: x[j], all_parent)
    worse = state.parent_fit > g_best_fit
    parent = jax.tree.map(lambda a, b: jnp.where(worse, a, b),
                          g_best, state.parent)
    parent_fit = jnp.where(worse, g_best_fit, state.parent_fit)
    return state._replace(parent=parent, parent_fit=parent_fit)


def init_state(spec: CGPSpec, cfg: EvolveConfig, golden: Genome,
               thresholds: jax.Array, in_planes: jax.Array,
               golden_vals: jax.Array, key: jax.Array,
               axis_name: str | None = None) -> EvolveState:
    eval_fn = get_eval_fn(cfg.backend)
    res = eval_fn(golden, spec, in_planes, golden_vals, cfg.gauss_sigma,
                  axis_name)
    fit = fitness_fn(res.cost.power, res.metric_vec, thresholds)
    return EvolveState(golden, fit, res.metric_vec, res.cost.power,
                       golden, fit, key)


def make_batched_generation_step(spec: CGPSpec, cfg: EvolveConfig):
    """Run-batched one-generation function for the batched sweep engine.

    ``state`` leaves and ``thr_mat`` carry a leading run axis C.  Mutation
    and selection are vmapped per run (preserving each run's PRNG stream
    exactly as the serial path draws it), but the (C × λ) offspring
    population is FLATTENED and evaluated in one shot — for
    ``backend="pallas"`` that is a single fused kernel dispatch with
    R = C·λ genomes on the grid, instead of a vmap-of-vmap-of-pallas_call.
    Same positional signature as ``make_generation_step``'s result, so it
    drops into ``scan_generations`` directly.
    """
    eval_pop = get_population_eval(cfg.backend)

    def step(state: EvolveState, thr_mat, in_planes, golden_vals, gen_idx):
        C = thr_mat.shape[0]
        keys = jax.vmap(jax.random.split)(state.key)        # (C, 2, 2)
        key, k_mut = keys[:, 0], keys[:, 1]
        offspring = jax.vmap(
            lambda k, p: mutate_population(k, p, spec, cfg.lam,
                                           cfg.mutation_rate))(k_mut,
                                                               state.parent)
        flat = jax.tree.map(
            lambda x: x.reshape((C * cfg.lam,) + x.shape[2:]), offspring)
        res = eval_pop(flat, spec, in_planes, golden_vals, cfg.gauss_sigma,
                       None)
        res = jax.tree.map(
            lambda x: x.reshape((C, cfg.lam) + x.shape[1:]), res)
        fits = jax.vmap(lambda p, m, t: jax.vmap(fitness_fn)(
            p, m, jnp.broadcast_to(t, (cfg.lam,) + t.shape)))(
                res.cost.power, res.metric_vec, thr_mat)
        return jax.vmap(_select)(state._replace(key=key), offspring, fits,
                                 res.metric_vec, res.cost.power)

    return step


def init_state_batched(spec: CGPSpec, cfg: EvolveConfig, golden: Genome,
                       thr_mat: jax.Array, in_planes: jax.Array,
                       golden_vals: jax.Array, keys: jax.Array
                       ) -> EvolveState:
    """Per-run init for the batched sweep: the golden parent is evaluated
    ONCE (it is identical for every run) and broadcast over the run axis;
    only fitness differs per run (per-run thresholds)."""
    eval_fn = get_eval_fn(cfg.backend)
    res = eval_fn(golden, spec, in_planes, golden_vals, cfg.gauss_sigma,
                  None)
    C = thr_mat.shape[0]
    fit = jax.vmap(
        lambda t: fitness_fn(res.cost.power, res.metric_vec, t))(thr_mat)
    rep = lambda x: jnp.broadcast_to(x, (C,) + x.shape)
    parent = jax.tree.map(rep, golden)
    return EvolveState(parent, fit, rep(res.metric_vec), rep(res.cost.power),
                       parent, fit, keys)


# --------------------------------------------------------------------------
# Dedup-path jit segments (DESIGN.md §8)
# --------------------------------------------------------------------------
#
# The phenotype-dedup sweep path (``core.sweep``) cannot run the generation
# loop as one ``lax.scan``: the dedup decision (which offspring share an
# active subgraph, which phenotypes are already cached) is host-side Python
# between kernel dispatches.  The loop is therefore split into three jit'd
# segments per generation — mutate, evaluate-uniques, select — that together
# perform EXACTLY the computation of ``make_batched_generation_step``'s one
# fused step (same PRNG splits, same op order), so results stay bit-identical
# to the scanned path with the cache on or off.

@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def mutate_segment(spec: CGPSpec, cfg: EvolveConfig, state: EvolveState
                   ) -> tuple[jax.Array, Genome]:
    """Per-run PRNG split + λ offspring; the batched step's first half.

    Returns (next keys (C, 2), offspring with leading (C, λ)).
    """
    keys = jax.vmap(jax.random.split)(state.key)        # (C, 2, 2)
    offspring = jax.vmap(
        lambda k, p: mutate_population(k, p, spec, cfg.lam,
                                       cfg.mutation_rate))(keys[:, 1],
                                                           state.parent)
    return keys[:, 0], offspring


@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def eval_segment(spec: CGPSpec, cfg: EvolveConfig, nodes: jax.Array,
                 outs: jax.Array, in_planes: jax.Array,
                 golden_vals: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Population evaluation of a (U,)-stacked unique-phenotype batch.

    Returns the phenotype-invariant projection the dedup cache stores:
    (metric_vec (U, N_METRICS), power (U,)).  Traced once per padded batch
    size U (the dedup driver pads to power-of-two buckets to bound
    retraces).
    """
    res = get_population_eval(cfg.backend)(
        Genome(nodes, outs), spec, in_planes, golden_vals, cfg.gauss_sigma,
        None)
    return res.metric_vec, res.cost.power


@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def select_segment(spec: CGPSpec, cfg: EvolveConfig, state: EvolveState,
                   key: jax.Array, offspring: Genome, metric_vec: jax.Array,
                   power: jax.Array, thr_mat: jax.Array,
                   golden_power: jax.Array):
    """Fitness + (1+λ) selection; the batched step's second half.

    ``metric_vec``/``power`` carry leading (C, λ) — gathered from the dedup
    cache.  Emits the same per-generation history row ``scan_generations``
    traces, so the host loop can assemble bit-identical histories.
    """
    fits = jax.vmap(lambda p, m, t: jax.vmap(fitness_fn)(
        p, m, jnp.broadcast_to(t, (cfg.lam,) + t.shape)))(
            power, metric_vec, thr_mat)
    state = jax.vmap(_select)(state._replace(key=key), offspring, fits,
                              metric_vec, power)
    out = (state.parent_power / golden_power, state.parent_metrics,
           state.parent_fit)
    return state, out


def scan_generations(step, state0: EvolveState, thresholds: jax.Array,
                     in_planes: jax.Array, golden_vals: jax.Array,
                     golden_power: jax.Array, generations: int):
    """Scan ``step`` over ``generations``, tracing the parent history.

    Shared by the serial, sharded, and batched-sweep paths; ``step`` may carry
    leading batch axes on state/thresholds (e.g. the vmapped run axis of
    ``core.sweep``) as long as it accepts the same positional signature as
    ``make_generation_step``'s result.
    """
    def body(state, gen_idx):
        state = step(state, thresholds, in_planes, golden_vals, gen_idx)
        out = (state.parent_power / golden_power, state.parent_metrics,
               state.parent_fit)
        return state, out

    return jax.lax.scan(body, state0, jnp.arange(generations))


@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def evolve(spec: CGPSpec, cfg: EvolveConfig, golden: Genome,
           thresholds: jax.Array, in_planes: jax.Array,
           golden_vals: jax.Array, golden_power: jax.Array,
           key: jax.Array) -> EvolveResult:
    """Single-island paper-faithful run (jit; scan over generations)."""
    step = make_generation_step(spec, cfg)
    state0 = init_state(spec, cfg, golden, thresholds, in_planes, golden_vals,
                        key)
    state, (hp, hm, hf) = scan_generations(step, state0, thresholds,
                                           in_planes, golden_vals,
                                           golden_power, cfg.generations)
    return EvolveResult(state.parent, state.best, state.best_fit, hp, hm, hf)


# --------------------------------------------------------------------------
# Distributed evolution (shard_map over the production mesh)
# --------------------------------------------------------------------------

def evolve_sharded(mesh, spec: CGPSpec, cfg: EvolveConfig, golden: Genome,
                   thresholds_per_pod: jax.Array, golden_power: jax.Array,
                   *, data_axis: str = "data", cube_axis: str = "model",
                   pod_axis: str | None = None):
    """Build the shard_map'd multi-island evolve function (DESIGN.md §2.2).

    This is the ISLAND formulation of the distributed search: one (1+λ) run
    per ``data``-axis slice with periodic best-parent migration
    (``_migrate``), each run's candidate evaluation input-space-sharded over
    ``cube_axis`` (metric partials / popcounts psum across it, see
    ``metrics.combine_partials``; jnp backend only, as the Pallas kernel
    evaluates the whole cube on one chip), and — when ``pod_axis`` is given
    — one constraint configuration per pod slice.  For the paper's
    constraint×seed GRID at production scale, use the pod-sharded batched
    sweep instead (``core.sweep.run_sweep_batched`` with
    ``SweepConfig.n_pods``, DESIGN.md §6): there the pod axis partitions
    whole chunks of independent runs and needs no cross-pod collectives at
    all.

    Args:
      mesh: the active device mesh; must carry ``data_axis`` and
        ``cube_axis`` (and ``pod_axis`` when given).  The production shapes
        are built by ``launch.mesh``.
      spec/cfg/golden/golden_power: the problem, as in ``evolve`` —
        ``cfg.migrate_every`` sets the island migration period.
      thresholds_per_pod: ``(n_pod_cfgs, N_METRICS)`` threshold matrix,
        sharded over ``pod_axis`` so each pod slice evolves under its own
        combined-constraint vector — or ``(1, N_METRICS)`` replicated when
        ``pod_axis`` is None (every island shares one constraint).
      data_axis / cube_axis / pod_axis: physical mesh-axis names (the
        logical mapping lives in ``parallel.ctx.LOGICAL``).

    Returns:
      fn(thresholds, keys, in_planes, golden_vals) — shard_map'd over
      ``mesh``; ``keys`` is ``(n_islands,)`` PRNG keys sharded over
      ``data_axis`` (see ``make_island_keys``), ``in_planes``/``golden_vals``
      the input cube sharded over ``cube_axis`` on the word/value axis.
      Returns per-island stacked (parent, best, best_fit, hist_power_rel,
      hist_metrics, hist_fit), gathered over ``data_axis``.
    """
    from jax.sharding import PartitionSpec as P

    def island_run(thresholds, key, in_planes, golden_vals):
        # runs on ONE (pod, data, model) shard; model axis splits the cube
        thresholds = thresholds[0]  # local shard is (1, N_METRICS)
        step = make_generation_step(spec, cfg, axis_name=cube_axis,
                                    island_axis=data_axis)
        state0 = init_state(spec, cfg, golden, thresholds, in_planes,
                            golden_vals, key[0], axis_name=cube_axis)
        state, (hp, hm, hf) = scan_generations(step, state0, thresholds,
                                               in_planes, golden_vals,
                                               golden_power, cfg.generations)
        # re-add leading axes stripped by shard_map (1 island per shard)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return (expand(state.parent), expand(state.best),
                state.best_fit[None], hp[None], hm[None], hf[None])

    pod = pod_axis if pod_axis is not None else None
    in_specs = (P(pod, None),            # thresholds (pods, N_METRICS)
                P(data_axis),            # per-island keys
                P(None, cube_axis),      # input planes (n_i, W)
                P(cube_axis))            # golden values (2^n,)
    out_leaf = P(data_axis)
    out_specs = (jax.tree.map(lambda _: out_leaf, golden),
                 jax.tree.map(lambda _: out_leaf, golden),
                 out_leaf, out_leaf, out_leaf, out_leaf)

    fn = jax.shard_map(island_run, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn


def make_island_keys(seed: int, n_islands: int) -> jax.Array:
    return jax.vmap(lambda i: jax.random.fold_in(
        jax.random.PRNGKey(seed), i))(jnp.arange(n_islands))
