"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU only: the kernels target the TPU and
the CPU test suite validates them against ref.py in interpret mode.  On a
TPU backend the wrappers run compiled Mosaic, and the CGP kernel refuses
interpret mode there (``cgp_sim._resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import metrics as M
from repro.core.genome import CGPSpec, Genome
from repro.kernels import cgp_sim as _cgp
from repro.kernels import flash_attention as _fa
from repro.kernels import lut_matmul as _lut


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_INTERPRET_DEFAULT: bool | None = None


def default_interpret() -> bool:
    """Process-wide interpret-mode default for every kernel wrapper.

    Resolved from ``jax.default_backend()`` ONCE, at the first call, and
    cached for the life of the process.  ``jax.default_backend()`` itself is
    insensitive to trace context (it reads the process platform config, not
    e.g. a ``jax.default_device`` scope), so resolving it during tracing is
    safe — the trap this cache closes is the report CHANGING between traces
    (a ``jax.config.update("jax_platform_name", ...)`` after the first
    evolve trace was built): per-call resolution would bake different modes
    into different cached traces of the same program.  One pinned resolution
    makes every trace in the process agree.  (No eager import-time pin: that
    would force backend initialization as an import side effect.)
    """
    global _INTERPRET_DEFAULT
    if _INTERPRET_DEFAULT is None:
        _INTERPRET_DEFAULT = not _on_tpu()
    return _INTERPRET_DEFAULT


def _digits_value(d: jax.Array) -> jax.Array:
    """float32 of the integer that ``SUM_DIGITS`` carried digits hold (below
    2^48 in magnitude), rounded once: two 24-bit halves, each exact."""
    half = lambda k: d[..., k] + (d[..., k + 1] << 8) + (d[..., k + 2] << 16)
    return half(3).astype(jnp.float32) * float(1 << 24) + \
        half(0).astype(jnp.float32)


def _digit_columns(sums: jax.Array) -> tuple[jax.Array, ...]:
    """The kernel's digits of Σ|d| and Σd = 2 Σd⁺ - Σ|d| (and of Σd² in a
    ``moments`` call), as int32 and not yet carried."""
    C = _cgp
    digits = lambda at, n: sums[..., at:at + n].astype(jnp.int32)
    a, p = digits(C.ABS_DIGITS, C.SUM_DIGITS), digits(C.POS_DIGITS,
                                                       C.SUM_DIGITS)
    out = (a, 2 * p - a)
    if sums.shape[-1] == C.N_SUMS_MOMENTS:
        out += (digits(C.SQ_DIGITS_AT, C.SQ_DIGITS),)
    return out


def exact_sums(digits: tuple[jax.Array, ...]) -> tuple[jax.Array, ...]:
    """The digits of a ``MetricPartials.moments`` carried to base 2^8
    (int32, least significant first, the top one signed): Σ|d|, Σd and
    Σd² exactly, for ``core.metrics.exact_error_moments`` to finish on
    the host."""
    return tuple(_cgp.carry_digits(d) for d in digits)


def _partials_from_sums(sums: jax.Array, wce: jax.Array, hist: jax.Array
                        ) -> M.MetricPartials:
    """Decode the kernel's (..., n_sums) rows into MetricPartials: the
    exact Σ|d| and Σd converted to float32 once; a ``moments`` call's
    digits kept in ``moments``."""
    C = _cgp
    digits = _digit_columns(sums)
    abs_digits, sgn_digits = (_cgp.carry_digits(d) for d in digits[:2])
    return M.MetricPartials(
        abs_sum=_digits_value(abs_digits),
        wce_max=wce[..., 0],
        err_count=sums[..., C.ERR_CNT].astype(jnp.int32),
        rel_sum=sums[..., C.REL_SUM],
        sgn_sum=_digits_value(sgn_digits),
        acc0_bad=sums[..., C.ACC0_BAD].astype(jnp.int32),
        hist=hist.astype(jnp.int32),
        count=sums[..., C.COUNT].astype(jnp.int32),
        sq_sum=sums[..., C.SQ_SUM],
        rel_sq=sums[..., C.REL_SQ],
        moments=digits if len(digits) == 3 else None,
    )


def cgp_eval(genome: Genome, spec: CGPSpec, in_planes: jax.Array,
             golden_vals: jax.Array, gauss_sigma: float = 256.0,
             block_words: int | None = None,
             interpret: bool | None = None
             ) -> tuple[M.MetricPartials, jax.Array]:
    """Fused candidate evaluation -> (MetricPartials, per-gate popcounts).

    Drop-in for ref.cgp_eval_ref; used by core.evolve backend="pallas".
    """
    if interpret is None:
        interpret = default_interpret()
    sums, wce, hist, pops = _cgp.cgp_sim_metrics(
        genome.nodes, genome.outs, in_planes, golden_vals,
        n_i=spec.n_i, n_n=spec.n_n, n_o=spec.n_o,
        gauss_sigma=gauss_sigma, block_words=block_words,
        interpret=interpret)
    return _partials_from_sums(sums, wce, hist), pops


def cgp_eval_batched(genomes: Genome, spec: CGPSpec, in_planes: jax.Array,
                     golden_vals: jax.Array, gauss_sigma: float = 256.0,
                     block_words: int | None = None,
                     interpret: bool | None = None,
                     r_tile: int | None = None, moments: bool = False):
    """Fused (runs × λ) population evaluation in ONE kernel dispatch.

    ``genomes`` carries a leading stacked axis R: nodes (R, n_n, 3), outs
    (R, n_o).  The genome axis becomes Pallas grid dimension 0 and the
    whole input cube is evaluated on this chip (DESIGN.md §7) — this
    replaces ``jax.vmap(cgp_eval)`` over a population, which dispatched one
    kernel per genome (or one vmap-batched program) and left the run axis
    off the grid.  Returns (MetricPartials with leading R, pops (R, n_n));
    with ``moments`` the partials' ``moments`` hold the digits of the exact
    Σ|d|, Σd and Σd² (``exact_sums``).

    ``block_words=None`` takes the kernel's shape rule, the largest block
    whose digit sums stay exact (``cgp_sim.default_block_words``: 2,048
    words at width 8, 1,024 at width 12, 512 with ``moments``), and
    ``r_tile=None`` pads the genome axis to a multiple of 8 when compiled
    and 1 interpreted (interpret mode pays every pad row as a full
    evaluation).  Either may be passed explicitly: the integer outputs do
    not depend on them, the float32 sums only through their order.
    """
    if interpret is None:
        interpret = default_interpret()
    if r_tile is None:
        r_tile = 1 if interpret else 8
    sums, wce, hist, pops = _cgp.cgp_sim_metrics_batched(
        genomes.nodes, genomes.outs, in_planes, golden_vals,
        n_i=spec.n_i, n_n=spec.n_n, n_o=spec.n_o, gauss_sigma=gauss_sigma,
        block_words=block_words, r_tile=r_tile, moments=moments,
        interpret=interpret)
    return _partials_from_sums(sums, wce, hist), pops


def lut_matmul(a: jax.Array, b: jax.Array, lut: jax.Array,
               interpret: bool | None = None, **tiles) -> jax.Array:
    """Approximate-multiplier emulated matmul (pads to tile multiples).

    Arbitrary (M, N, K) are accepted: operands are zero-padded up to the
    tile grid and the output sliced back.  Zero-padding the contraction dim
    is NOT free under an approximate LUT — every padded k contributes
    ``LUT[0, 0]`` (an evolved circuit need not map 0×0 to 0) — so the
    ``pad_k * LUT[0, 0]`` bias is subtracted from every output element,
    keeping ragged shapes bit-identical to the unpadded LUT contraction.
    """
    if interpret is None:
        interpret = default_interpret()
    M_, K = a.shape
    _, N = b.shape
    bm = min(tiles.get("bm", 128), max(8, M_))
    bn = min(tiles.get("bn", 128), max(8, N))
    bk = min(tiles.get("bk", 128), max(8, K))
    pm, pn, pk = (-M_) % bm, (-N) % bn, (-K) % bk
    a_p = jnp.pad(a, ((0, pm), (0, pk)))
    b_p = jnp.pad(b, ((0, pk), (0, pn)))
    out = _lut.lut_matmul(a_p, b_p, lut, bm=bm, bn=bn, bk=bk,
                          interpret=interpret)
    out = out[:M_, :N]
    if pk:
        out = out - pk * lut[0, 0].astype(out.dtype)
    return out


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, interpret: bool | None = None,
                    bq: int = 128, bkv: int = 128) -> jax.Array:
    """Blocked attention; q (B, Hq, S, D), k/v (B, Hkv, S, D); GQA folded.

    Heads are grouped: q-heads h use kv-head h // (Hq // Hkv).
    """
    if interpret is None:
        interpret = default_interpret()
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    qf = q.reshape(B * Hq, Sq, D)
    kf = jnp.repeat(k, group, axis=1).reshape(B * Hq, Skv, D)
    vf = jnp.repeat(v, group, axis=1).reshape(B * Hq, Skv, D)
    out = _fa.flash_attention(qf, kf, vf, causal=causal,
                              bq=bq, bkv=bkv, interpret=interpret)
    return out.reshape(B, Hq, Sq, D)
