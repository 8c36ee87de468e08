"""Error metrics — paper Sec. II, Eq. (1)-(7).

All metrics are computed from integer output values over (a slice of) the
exhaustive input cube and are returned as *partial sums* so that input-space
sharding can combine shards with psum/pmax before normalization
(``finalize_metrics``).  Relativization follows the paper: magnitudes are
divided by the output range 2^m and reported in percent.

Metric vector layout (used by fitness thresholds; see ``fitness.py``):
    0 MAE_rel(%)  1 WCE_rel(%)  2 ER(%)  3 MRE(%)  4 |AVG|_rel(%)
    5 ACC0 (1 = holds)          6 GAUSS (1 = holds)
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

MAE, WCE, ER, MRE, AVG, ACC0, GAUSS = range(7)
METRIC_NAMES = ("mae", "wce", "er", "mre", "avg", "acc0", "gauss")
N_METRICS = 7


class MetricPartials(NamedTuple):
    """Shard-combinable raw sums.  Combine: add all but wce_max (max).

    x64 is disabled (the LM substrate must stay 32-bit), so the magnitude
    sums ``abs_sum``/``sgn_sum`` are float32 values of exact integers, by
    one of two paths.  The Pallas kernel (``kernels/cgp_sim``, the
    ``backend="pallas"`` path and every sweep's final measurement) keeps
    Σ|d| and Σd⁺ as base-2^8 digits with a carry, exact through n_o = 24
    over 2^24 rows, and converts each total to float32 once
    (``kernels.ops``); its ``moments`` call also keeps the digits of Σd² in
    ``moments``.  The jnp path (``error_partials``) uses ``_exact_sum``
    with two statically-chosen regimes.  Byte split: |e| = 256*hi + lo with
    hi/lo ≤ 2^8-1; partial sums of ≤2^16 byte-sized terms stay < 2^24 and
    are exact in float32; the recombination ``256*hi_sum + lo_sum`` is done
    in float32 whose error is ≤ 1 ulp of the total (relative ~6e-8).
    Outside that regime (operands wider than 16 bits or slices longer than
    2^16, where the byte split would silently overflow 2^24) the sum
    switches to per-bit popcounts — int32-exact counts recombined in
    float32 with error ≤ n_bits ulp (relative ~n_bits·2^-24) — documented
    precision of the metric pipeline (tests assert rtol 1e-5 vs the int64
    NumPy oracle up to width-12 value ranges).
    """
    abs_sum: jax.Array    # Σ |g - c|  (float32 via exact split sums)
    wce_max: jax.Array    # max |g - c|
    err_count: jax.Array  # #{x : g != c}
    rel_sum: jax.Array    # Σ |g-c| / max(g, 1)
    sgn_sum: jax.Array    # Σ (g - c)  (signed, Eq. 6)
    acc0_bad: jax.Array   # #{x : g = 0 ∧ c != 0}
    hist: jax.Array       # (n_bins,) signed-error histogram (zeros excluded)
    count: jax.Array      # #inputs in this slice
    sq_sum: jax.Array     # Σ (g - c)^2  (float32; variance estimator only)
    rel_sq: jax.Array     # Σ (|g-c| / max(g, 1))^2  (float32)
    # the CGP kernel's ``moments`` call only: int32 base-2^8 digits of the
    # exact Σ|g-c|, Σ(g-c) and Σ(g-c)^2 (``kernels.ops.exact_sums`` carries
    # them)
    moments: tuple | None = None


def gauss_bin_edges(sigma: float, n_side: int = 4) -> np.ndarray:
    """σ-wide bin edges covering ±n_side·σ, plus two open tail bins."""
    edges = np.arange(-n_side, n_side + 1, dtype=np.float64) * sigma
    return edges  # len 2*n_side+1 -> 2*n_side interior bins (+2 tails)


def gauss_bin_mass(sigma: float, n_side: int = 4) -> np.ndarray:
    """Expected probability mass per bin under N(0, σ) (tails included)."""
    from math import erf, sqrt
    edges = gauss_bin_edges(sigma, n_side)
    cdf = np.array([0.5 * (1 + erf(e / (sigma * sqrt(2)))) for e in edges])
    interior = np.diff(cdf)
    return np.concatenate([[cdf[0]], interior, [1.0 - cdf[-1]]])


def error_partials(golden: jax.Array, cand: jax.Array,
                   gauss_sigma: float, n_gauss_side: int = 4,
                   n_bits: int = 16) -> MetricPartials:
    """Raw per-slice sums from integer output values.

    Args:
      golden, cand: (S,) int32 exact / approximate outputs on this cube slice.
      gauss_sigma:  σ for the Gauss_σ histogram (static).
      n_bits:       static bound |g - c| < 2^n_bits (= the circuit's n_o);
                    picks the exact-sum regime (see ``_exact_sum``).
    """
    g = golden.astype(jnp.int32)
    c = cand.astype(jnp.int32)
    diff = g - c               # |diff| < 2^n_o ≤ 2^31, exact in int32
    ad = jnp.abs(diff)
    nz = diff != 0

    edges = jnp.asarray(gauss_bin_edges(gauss_sigma, n_gauss_side))
    n_bins = edges.shape[0] + 1
    bin_idx = jnp.searchsorted(edges, diff.astype(jnp.float32), side="right")
    hist = jnp.zeros((n_bins,), jnp.int32).at[bin_idx].add(
        nz.astype(jnp.int32))

    return MetricPartials(
        abs_sum=_exact_sum(ad, n_bits),
        # initial=0 is the identity (|diff| >= 0) AND makes the reduction
        # total on zero-size slices (empty sampled-shard partitions)
        wce_max=jnp.max(ad, initial=0),
        err_count=nz.sum(),
        rel_sum=(ad.astype(jnp.float32) /
                 jnp.maximum(g, 1).astype(jnp.float32)).sum(),
        sgn_sum=_exact_sum(jnp.maximum(diff, 0), n_bits,
                           minus=jnp.maximum(-diff, 0)),
        acc0_bad=((g == 0) & (c != 0)).sum(),
        hist=hist,
        count=jnp.asarray(diff.shape[0], jnp.int32),
        sq_sum=(ad.astype(jnp.float32) ** 2).sum(),
        rel_sq=((ad.astype(jnp.float32) /
                 jnp.maximum(g, 1).astype(jnp.float32)) ** 2).sum(),
    )


def _exact_sum(v: jax.Array, n_bits: int = 16,
               minus: jax.Array | None = None) -> jax.Array:
    """Integer-exact Σv (Σv - Σminus with ``minus``) for 0 ≤ v, minus <
    2^n_bits (see MetricPartials doc).

    The regime is chosen STATICALLY from (n_bits, slice length), never from
    values, so it is jit-stable:

      * byte split (historic path) whenever both block sums provably stay
        < 2^24 — exact, and rounded once like the Pallas kernel's digits
        (the two sums' byte columns subtract exactly first);
      * per-bit popcount otherwise: cnt_b = #{v with bit b set} is exact in
        int32 for any slice length (and float32-exact up to 2^24 terms);
        recombining Σ 2^b·cnt_b in float32, ascending, bounds the error at
        n_bits ulp of the total — vs the UNBOUNDED silent error the
        overflowed byte split used to produce for >8-bit operands
        (e.g. a 12×12 multiplier's n_o = 24).
    """
    n = int(np.prod(v.shape)) if v.shape else 1
    hi_max = max((1 << max(n_bits - 8, 0)) - 1, 0)
    if n * 255 < (1 << 24) and n * hi_max < (1 << 24):
        col = lambda x, f: f(x).astype(jnp.float32).sum()
        part = lambda f: col(v, f) - (col(minus, f) if minus is not None
                                      else 0.0)
        return 256.0 * part(lambda x: x >> 8) + part(lambda x: x & 0xFF)
    total = jnp.float32(0.0)
    for b in range(n_bits):  # ascending: small terms accumulate first
        cnt = ((v >> b) & 1).sum()
        if minus is not None:
            cnt = cnt - ((minus >> b) & 1).sum()
        total = total + float(1 << b) * cnt.astype(jnp.float32)
    return total


def combine_partials(p: MetricPartials, axis_name: str) -> MetricPartials:
    """psum/pmax partials across an input-space-sharding mesh axis."""
    ps = lambda x: jax.lax.psum(x, axis_name)
    return MetricPartials(
        abs_sum=ps(p.abs_sum), wce_max=jax.lax.pmax(p.wce_max, axis_name),
        err_count=ps(p.err_count), rel_sum=ps(p.rel_sum),
        sgn_sum=ps(p.sgn_sum), acc0_bad=ps(p.acc0_bad),
        hist=ps(p.hist), count=ps(p.count),
        sq_sum=ps(p.sq_sum), rel_sq=ps(p.rel_sq))


def finalize_metrics(p: MetricPartials, n_o: int, gauss_sigma: float,
                     n_gauss_side: int = 4,
                     gauss_slack: float = 1.0) -> jax.Array:
    """(N_METRICS,) float32 metric vector per the layout above.

    MAE/WCE/|AVG| are relativized to 2^n_o and expressed in PERCENT, as in the
    paper's figures; ER and MRE are percentages by definition.

    An empty shard (count == 0, possible with ragged sampled partitions)
    must finalize to all-zero sums / n=1, never 0/0 = NaN: NaN compares
    false against every threshold and silently poisons fitness selection.
    """
    out_range = float(1 << n_o)
    n = jnp.maximum(p.count.astype(jnp.float32), 1.0)
    mae = p.abs_sum.astype(jnp.float32) / n
    wce = p.wce_max.astype(jnp.float32)
    er = p.err_count.astype(jnp.float32) / n
    mre = p.rel_sum / n
    avg = p.sgn_sum.astype(jnp.float32) / n
    acc0 = (p.acc0_bad == 0).astype(jnp.float32)

    mass = jnp.asarray(gauss_bin_mass(gauss_sigma, n_gauss_side),
                       dtype=jnp.float32)
    allowed = mass * n * gauss_slack
    gauss_ok = jnp.all(p.hist.astype(jnp.float32) <= allowed)

    return jnp.stack([
        100.0 * mae / out_range,
        100.0 * wce / out_range,
        100.0 * er,
        100.0 * mre,
        100.0 * jnp.abs(avg) / out_range,
        acc0,
        gauss_ok.astype(jnp.float32),
    ])


def metric_stderr(p: MetricPartials, n_o: int) -> jax.Array:
    """(N_METRICS,) standard errors matching ``finalize_metrics`` units.

    CLT estimates from the sample second moments carried in the partials
    (shard-combinable: ``sq_sum``/``rel_sq`` psum like every other sum):

      * MAE / |AVG|:  sqrt(Var[|d|] / n), sqrt(Var[d] / n) — both from
        Σd² (|d|² = d²), scaled by 100/2^n_o like the point estimates;
      * ER:           Bernoulli sqrt(p̂(1-p̂)/n), in percent;
      * MRE:          sqrt(Var[rel] / n), in percent;
      * WCE / ACC0 / GAUSS: 0 — extreme-value / indicator metrics have no
        CLT interval; the sampled mode reports them as observed-on-sample
        (lower bounds), see DESIGN.md §9.

    Under exhaustive evaluation the "sample" is the full census, so the
    sampling error is zero by construction; callers report zeros there and
    only compute this for ``eval_mode="sampled"``.
    """
    out_range = float(1 << n_o)
    n = jnp.maximum(p.count.astype(jnp.float32), 1.0)
    mean_abs = p.abs_sum.astype(jnp.float32) / n
    mean_sgn = p.sgn_sum.astype(jnp.float32) / n
    mean_sq = p.sq_sum / n
    var_abs = jnp.maximum(mean_sq - mean_abs ** 2, 0.0)
    var_sgn = jnp.maximum(mean_sq - mean_sgn ** 2, 0.0)
    er_hat = p.err_count.astype(jnp.float32) / n
    var_er = jnp.maximum(er_hat * (1.0 - er_hat), 0.0)
    mre_hat = p.rel_sum / n
    var_rel = jnp.maximum(p.rel_sq / n - mre_hat ** 2, 0.0)
    rt_n = jnp.sqrt(n)
    zero = jnp.float32(0.0)
    return jnp.stack([
        100.0 * jnp.sqrt(var_abs) / rt_n / out_range,
        zero,
        100.0 * jnp.sqrt(var_er) / rt_n,
        100.0 * jnp.sqrt(var_rel) / rt_n,
        100.0 * jnp.sqrt(var_sgn) / rt_n / out_range,
        zero,
        zero,
    ])


def metrics_from_values(golden: jax.Array, cand: jax.Array, n_o: int,
                        gauss_sigma: float = 256.0) -> jax.Array:
    """Single-shard convenience: values -> finalized metric vector."""
    p = error_partials(golden, cand, gauss_sigma, n_bits=n_o)
    return finalize_metrics(p, n_o, gauss_sigma)


def exact_error_moments(count, sum_d, sum_sq
                        ) -> tuple[np.ndarray, np.ndarray]:
    """(mean, std) float64 of the signed error, on the host, from its exact
    integer sums Σd and Σd² given as base-2^8 digits (least significant
    first, the top one signed: ``kernels.ops.exact_sums``).

    n² Var = n Σd² - (Σd)² is formed in Python integers (Σd² reaches 2^72
    at width 12), so the only roundings are the two divisions and the
    square root: an error that is a constant offset has a standard
    deviation of exactly 0, however large the offset.
    """
    count = np.asarray(count)
    value = lambda d: sum(int(v) << (8 * k) for k, v in enumerate(d))
    sd = np.asarray(sum_d).reshape(count.size, -1)
    sq = np.asarray(sum_sq).reshape(count.size, -1)
    mean, std = np.zeros(count.size), np.zeros(count.size)
    for i, n in enumerate(count.reshape(-1)):
        n, s1, s2 = max(int(n), 1), value(sd[i]), value(sq[i])
        mean[i] = s1 / n
        std[i] = math.sqrt((n * s2 - s1 * s1) / (n * n))
    return mean.reshape(count.shape), std.reshape(count.shape)


# ------------------------- NumPy oracle (tests) -------------------------

def metrics_np(golden: np.ndarray, cand: np.ndarray, n_o: int,
               gauss_sigma: float = 256.0, n_gauss_side: int = 4,
               gauss_slack: float = 1.0) -> np.ndarray:
    g = golden.astype(np.int64)
    c = cand.astype(np.int64)
    diff = g - c
    ad = np.abs(diff)
    n = diff.size
    out_range = float(1 << n_o)
    mae = ad.mean()
    wce = ad.max()
    er = (diff != 0).mean()
    mre = (ad / np.maximum(g, 1)).mean()
    avg = diff.mean()
    acc0 = float(((g == 0) & (c != 0)).sum() == 0)
    edges = gauss_bin_edges(gauss_sigma, n_gauss_side)
    idx = np.searchsorted(edges, diff.astype(np.float64), side="right")
    hist = np.bincount(idx[diff != 0], minlength=len(edges) + 1)
    mass = gauss_bin_mass(gauss_sigma, n_gauss_side)
    gauss_ok = float(np.all(hist <= mass * n * gauss_slack))
    return np.array([100 * mae / out_range, 100 * wce / out_range, 100 * er,
                     100 * mre, 100 * abs(avg) / out_range, acc0, gauss_ok],
                    dtype=np.float32)


def metrics_stderr_np(golden: np.ndarray, cand: np.ndarray,
                      n_o: int) -> np.ndarray:
    """float64 oracle for ``metric_stderr`` (population-variance CLT SEs)."""
    g = golden.astype(np.int64)
    c = cand.astype(np.int64)
    diff = (g - c).astype(np.float64)
    ad = np.abs(diff)
    rel = ad / np.maximum(g, 1)
    n = max(diff.size, 1)
    out_range = float(1 << n_o)
    se = lambda v: np.sqrt(max(np.mean(v * v) - np.mean(v) ** 2, 0.0) / n)
    er_hat = (diff != 0).mean() if diff.size else 0.0
    return np.array([
        100 * se(ad) / out_range,
        0.0,
        100 * np.sqrt(max(er_hat * (1 - er_hat), 0.0) / n),
        100 * se(rel),
        100 * se(diff) / out_range,
        0.0,
        0.0,
    ], dtype=np.float32)
