"""Kernel-layout tuning subsystem (DESIGN.md §7.3).

The fused CGP-evaluation kernel has execution knobs that change throughput
but never results: the evaluation-grid ``layout`` (genome-major vs the
transposed cube-major grid of ``cgp_sim``), the cube ``block_words`` and the
genome-axis pad ``r_tile``.  Which combination wins depends on the problem
shape and the backend — cube-block reuse only pays where HBM traffic is real
(TPU), interpret mode pays per pad row, small cubes fit in one block anyway.
This module owns that decision:

  * ``KernelVariant`` — one (layout, block_words, r_tile) point; the
    ``default_variants`` registry enumerates the candidates for a problem
    shape (both layouts × the block sizes that divide the cube).
  * ``autotune`` — measured pass: dispatches the real batched kernel on a
    synthetic population for every variant, times it through the same
    machinery ``benchmarks/kernel_micro.py`` uses (pass its timer as
    ``time_fn``; the built-in default is equivalent), and persists the
    winner into the JSON tuning table.
  * the tuning table — one JSON file (``REPRO_TUNE_TABLE`` env var, default
    ``experiments/tuning/kernel_layout.json``), ``entries`` keyed by
    ``w{width}_r{R}_{backend}`` so interpret-mode measurements can never
    shadow TPU ones.  Schema in DESIGN.md §7.3.
  * ``resolve_variant``/``resolve_layout`` — the ``layout="auto"`` path used
    by ``kernels.ops.cgp_eval_batched``: exact (width, R, backend) hit,
    else the nearest-R entry of the same (width, backend), else the
    conservative default (genome-major — the longest-validated layout).

Tuning entries are advisory, never load-bearing: both layouts are
bit-identical (differentially tested), so a stale or foreign table can cost
throughput but not correctness.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Callable, Sequence

LAYOUTS = ("genome_major", "cube_major")
DEFAULT_LAYOUT = "genome_major"
TABLE_ENV = "REPRO_TUNE_TABLE"
TABLE_VERSION = 1

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_TABLE = os.path.join(_ROOT, "experiments", "tuning",
                             "kernel_layout.json")

# candidate cube block sizes (words); clipped to the cube width per problem
BLOCK_CANDIDATES = (128, 256, 512, 1024, 2048)


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One point of the kernel execution space (results-invariant knobs)."""
    layout: str = DEFAULT_LAYOUT
    block_words: int | None = None  # None: the kernel's shape rule
    r_tile: int = 8

    def key(self) -> str:
        return f"{self.layout}/bw{self.block_words}/rt{self.r_tile}"


def table_path() -> str:
    return os.environ.get(TABLE_ENV) or DEFAULT_TABLE


def table_key(width: int, R: int, backend: str) -> str:
    return f"w{width}_r{R}_{backend}"


def backend_key(interpret: bool) -> str:
    """Tuning-table backend tag: measurements taken in interpret mode are
    meaningless for the compiled kernel and must never shadow it."""
    if interpret:
        return "interpret"
    import jax
    return jax.default_backend()


# path -> (stat token, parsed table or None-for-unparseable).  The token is
# (st_mtime_ns, st_size, st_ino) rather than a bare mtime: a same-second
# rewrite is invisible to 1s-granularity mtimes on some filesystems, but the
# atomic-rename writes used here always change the inode (and usually the
# size), so the token catches it.  Parse failures are cached under the same
# token (value None) so a corrupt table isn't re-read and re-parsed on every
# trace's ``resolve_variant`` call.
_TABLE_CACHE: dict[str, tuple[tuple[int, int, int], dict | None]] = {}


def _stat_token(path: str) -> tuple[int, int, int] | None:
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (st.st_mtime_ns, st.st_size, st.st_ino)


def load_table(path: str | None = None) -> dict:
    """Read the tuning table ({} if absent/invalid).  Cached by stat token so
    the per-trace ``resolve_variant`` calls don't re-read the file."""
    path = path or table_path()
    token = _stat_token(path)
    if token is None:
        return {}
    cached = _TABLE_CACHE.get(path)
    if cached is not None and cached[0] == token:
        return cached[1] if cached[1] is not None else {}
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        _TABLE_CACHE[path] = (token, None)  # negative-cache the parse failure
        return {}
    if not isinstance(table, dict) or "entries" not in table:
        _TABLE_CACHE[path] = (token, None)
        return {}
    _TABLE_CACHE[path] = (token, table)
    return table


def save_entry(width: int, R: int, backend: str, entry: dict,
               path: str | None = None) -> dict:
    """Merge one winner entry into the table (atomic rename write)."""
    from repro.checkpoint import store
    path = path or table_path()
    table = dict(load_table(path)) or {"version": TABLE_VERSION,
                                       "entries": {}}
    entries = dict(table.get("entries", {}))
    entries[table_key(width, R, backend)] = entry
    table["entries"] = entries
    table["version"] = TABLE_VERSION
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    store.atomic_write_json(path, table)
    _TABLE_CACHE.pop(path, None)
    return table


def default_variants(n_words: int, interpret: bool,
                     r_tiles: Sequence[int] | None = None
                     ) -> list[KernelVariant]:
    """Registry of candidate variants for a cube of ``n_words`` words.

    Both layouts × every candidate block size that divides the cube (the
    kernel requires ``W % bw == 0``) and keeps its digit sums exact (1,024
    words at most where the kernel carries); interpret mode pays every pad
    row as a recomputed evaluation, so its registry pins ``r_tile=1``
    while compiled candidates use the sublane-aligned 8.
    """
    from repro.kernels import cgp_sim
    if r_tiles is None:
        r_tiles = (1,) if interpret else (8,)
    # the tuned calls have no moments, whose bound alone depends on n_o
    largest = cgp_sim.default_block_words(n_words, 1, moments=False)
    blocks = sorted({min(b, n_words) for b in BLOCK_CANDIDATES
                     if n_words % min(b, n_words) == 0
                     and min(b, n_words) <= largest})
    return [KernelVariant(layout=layout, block_words=bw, r_tile=rt)
            for layout in LAYOUTS for bw in blocks for rt in r_tiles]


def resolve_variant(width: int, R: int, backend: str,
                    path: str | None = None,
                    default: KernelVariant | None = None) -> KernelVariant:
    """The ``layout="auto"`` resolution path (exact → nearest-R → default).

    Nearest-R matching (log-distance, same width+backend) makes a sparse
    table useful: a sweep's chunk×λ population size rarely equals a tuned R
    exactly, but the winning layout is stable across nearby R.  ``default``
    is returned on a full miss (callers pass their interpret-aware
    execution defaults; the bare ``KernelVariant()`` otherwise).
    """
    entries = load_table(path).get("entries", {})
    hit = entries.get(table_key(width, R, backend))
    if hit is None:
        suffix = f"_{backend}"
        prefix = f"w{width}_r"
        best = None
        for key, entry in entries.items():
            if not (key.startswith(prefix) and key.endswith(suffix)):
                continue
            try:
                r_ent = int(key[len(prefix):-len(suffix)])
            except ValueError:
                continue
            dist = abs(math.log(max(r_ent, 1)) - math.log(max(R, 1)))
            if best is None or dist < best[0]:
                best = (dist, entry)
        hit = best[1] if best is not None else None
    if hit is None:
        return default if default is not None else KernelVariant()
    return KernelVariant(layout=hit.get("layout", DEFAULT_LAYOUT),
                         block_words=hit.get("block_words"),
                         r_tile=int(hit.get("r_tile", 8)))


def resolve_layout(width: int, R: int, backend: str,
                   path: str | None = None) -> str:
    return resolve_variant(width, R, backend, path).layout


def _measure(fn: Callable[[], object], reps: int) -> float:
    """Default timer — same protocol as ``benchmarks.kernel_micro._time``
    (compile + warm call, then averaged timed reps, block_until_ready)."""
    import jax
    fn()
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def autotune(width: int, R: int, *, kind: str = "mul", n_n: int = 400,
             gauss_sigma: float = 256.0, reps: int = 3,
             variants: Sequence[KernelVariant] | None = None,
             interpret: bool | None = None, path: str | None = None,
             time_fn: Callable[[Callable[[], object], int], float] | None
             = None) -> dict:
    """Measure every registry variant on a synthetic R-genome population and
    persist the winner for this (width, R, backend) into the tuning table.

    ``time_fn(fn, reps) -> seconds`` lets callers supply their own timing
    machinery (``benchmarks/kernel_micro.py --tune`` passes its ``_time``);
    the default is equivalent.  Returns the written entry, which includes
    the full per-variant measurement for the bench trajectory.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import golden as G
    from repro.core import simulate as S
    from repro.core.genome import CGPSpec, random_genome
    from repro.kernels import cgp_sim

    if interpret is None:
        from repro.kernels import ops
        interpret = ops.default_interpret()
    backend = backend_key(interpret)
    spec = CGPSpec(n_i=2 * width, n_o=2 * width, n_n=n_n)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(width, kind))
    genomes = jax.vmap(lambda k: random_genome(k, spec))(
        jax.random.split(jax.random.PRNGKey(0), R))
    if variants is None:
        variants = default_variants(planes.shape[1], interpret)
    if time_fn is None:
        time_fn = _measure

    timings: dict[str, float] = {}
    for v in variants:
        def dispatch(v=v):
            return cgp_sim.cgp_sim_metrics_batched(
                genomes.nodes, genomes.outs, planes, gvals, n_i=spec.n_i,
                n_n=spec.n_n, n_o=spec.n_o, gauss_sigma=gauss_sigma,
                layout=v.layout, block_words=v.block_words, r_tile=v.r_tile,
                interpret=interpret)
        timings[v.key()] = time_fn(dispatch, reps)

    winner = min(variants, key=lambda v: timings[v.key()])
    entry = {
        "layout": winner.layout,
        "block_words": winner.block_words,
        "r_tile": winner.r_tile,
        "width": width, "R": R, "backend": backend,
        "n_n": n_n, "kind": kind, "reps": reps,
        "seconds": {k: round(t, 6) for k, t in timings.items()},
    }
    save_entry(width, R, backend, entry, path)
    return entry
