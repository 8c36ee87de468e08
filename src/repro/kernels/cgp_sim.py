"""Fused CGP simulation + error-metric Pallas kernel (DESIGN.md §2).

THE paper hot loop: exhaustive bit-parallel candidate evaluation.  The TPU
formulation keeps the whole wire plane for a block of the input cube in VMEM
scratch and walks the netlist once with branch-free truth-table merges; the
same pass unpacks integer outputs and accumulates every error-metric partial
(Eq. 1-7 numerators) plus per-gate popcounts (for the activity power model) —
so a candidate costs exactly one HBM read of its input-plane block and one
accumulator row of write-back.

One dispatch evaluates R stacked genomes over the whole input cube on one
chip, on the grid ``(Rp, W // bw)`` (DESIGN.md §7; the Pallas grid runs
sequentially with the LAST dimension innermost): the GENOME axis is grid
dimension 0 (one sweep chunk of ``runs × λ`` candidates, which
``core.sweep``/``core.evolve`` flatten into it, padded to ``Rp``) and the
cube-block axis is dimension 1, so each genome streams the input cube from
HBM once — the paper's per-candidate traffic; what the fused grid removes is
the per-genome dispatch and trace overhead.

The ``(Rp, cols)`` float32 accumulator stays whole in VMEM for the entire
grid (its block index never changes, so it is written back to HBM once,
after the last step).  Grid step (genome r, block w) sets row r at w == 0
and combines into it afterwards (then carries the digits of the exact sums,
below), so every genome's cube blocks accumulate in ascending order.

Mosaic (the TPU compiler) constraints the kernel follows — interpret mode
checks none of them, ``tests/test_tpu_compile.py`` compiles for a described
v5e chip to keep them honest:

* a genome (nodes and outs flattened into one int32 row) is an SMEM block,
  read as scalars: node fan-ins are dynamic indices into the wire plane;
* a block of ``bw`` words is sublane-dense (``wire_shape``): ``S`` sublanes
  of ``L`` lanes, ``L = 128`` where ``bw`` is a multiple of it (``S = bw /
  128``) and else ``L = bw``, ``S = 1``.  Wire ``k`` of the block is rows
  ``[k S, (k + 1) S)`` of the ``(cols S, L)`` scratch, so at ``S >= 8`` every
  load, store and vector op of the netlist walk fills whole ``(8, 128)``
  vregs (one row of a ``(cols, bw)`` plane filled 1 sublane of 8);
* every VMEM block spans its last two dimensions: input planes
  ``(n_i, S, L)`` and golden values ``(32, S, L)`` (bit, then the block's
  words) cut from ``(rows, W / bw, S, L)`` arrays (squeezed to ``(rows, L)``
  at ``S = 1``), the accumulator whole;
* the accumulator row is assembled with lane selects, never a scatter, and
  the per-wire popcounts (taken on int32) become a row through one MXU
  contraction per sublane (a strided read of that sublane of every wire) —
  counts ≤ 32 are exact in bfloat16, and the float32 sums (≤ 32 L a
  contraction, then S of them) stay integer-exact.

Accumulator row (float32, ``n_wires = n_i + n_n``; ``cols`` rounds it up
to a multiple of 8, and the wire scratch has ``cols`` rows):
  ``[0, n_wires)``                    per-wire popcounts (input planes first;
                                      the wrapper returns the gate rows)
  ``n_wires + [0, n_sums)``           the sums below: ``N_SUMS`` columns,
                                      ``N_SUMS_MOMENTS`` with ``moments``
  ``n_wires + n_sums``                worst-case |error| (max-combined)
  ``n_wires + n_sums + 1 + [0, n_bins)``  signed-error histogram

The sums: ``ERR_CNT``, ``ACC0_BAD``, ``COUNT`` and the histogram are counts
of rows and the popcounts counts of set bits, so at most the ``MAX_ROWS``
= 2^24 rows a call may cover: float32 holds every such integer exactly.
WCE is below 2^24 (``MAX_N_O`` = 24 output bits).  ``REL_SUM``, ``SQ_SUM``
and ``REL_SQ`` are float32 sums (MRE, and the sampled mode's variance
estimators).  Σ|d| and Σd⁺ (Σd = 2 Σd⁺ - Σ|d|) reach 2^48 at n_o = 24
over 2^24 rows, so each is kept exact as ``SUM_DIGITS`` base-2^8 digits
(``ABS_DIGITS``, ``POS_DIGITS``): a block adds, to digit k, the block sum
of byte k of each row's value (below 2^23 for blocks of at most 2^15
rows), and every combine carries each digit's excess over 2^8 one lane up
(``pltpu.roll``), so every digit stays below 2^24 and the float32
accumulator stores it exactly; the top digit keeps its excess.  Where the
whole cube's digit sums stay below 2^24 (at most 2^16 rows: width 8),
the kernel leaves out the carry, a static choice, and a block may be the
whole cube.  ``default_block_words`` takes the largest block these bounds
allow: 2,048 words (the whole cube, ``(16, 128)``) at width 8, 1,024
(``(8, 128)``) where the kernel carries, 512 (``(4, 128)``) with
``moments``.  The
wrapper returns every digit fully carried (``carry_digits``), so a total
has one row however the cube was cut into blocks.  With
``moments`` (the sweep's final measurement, ``core.sweep.
characterize_chunk``) the ``SQ_DIGITS`` digits of Σd² (below 2^72) follow,
built the same way from the bytes of the products of the bytes of |d|
(blocks of at most 2^14 rows).  ``kernels.ops`` decodes the digits: Σ|d|
and Σd converted to float32 once, and all three exactly for the moments.
A call that could leave these bounds raises ValueError.

VMEM budget at the paper scale (8x8 multiplier, 400 nodes, the whole
2,048-word cube a block, S = 16, R=256): wire scratch (448 × 16, 128) int32
≈ 3.7 MB; input-plane block (16, 16, 128) 128 KB and golden block (32, 16,
128) 256 KB (double-buffered); accumulator 256 × 448 × 4 B ≈ 0.46 MB
(double-buffered) — ~4.5 MB in all.  At width 12 (900 nodes, 1,024 words,
S = 8, R=256): wire scratch (960 × 8, 128) int32 ≈ 3.9 MB, input-plane
block 96 KB, golden 128 KB, accumulator 256 × 960 × 4 B ≈ 1 MB (all
double-buffered) — ~6.3 MB.  The accumulator grows by ``4 cols`` B per
genome, so a dispatch stays inside the 16 MiB scoped-VMEM default up to a
few thousand genomes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import gates
from repro.core import metrics as M

# the sums (layout in the module doc): float32 totals first, then the
# base-2^8 digits of the exact integer sums
ERR_CNT, REL_SUM, ACC0_BAD, COUNT, SQ_SUM, REL_SQ = range(6)
DIGIT_BITS = 8
SUM_DIGITS = 6                     # Σ|d|, Σd⁺ < 2^24 rows × 2^24 = 2^48
SQ_DIGITS = 9                      # Σd² < 2^24 rows × 2^48 = 2^72
ABS_DIGITS = 6
POS_DIGITS = ABS_DIGITS + SUM_DIGITS
SQ_DIGITS_AT = POS_DIGITS + SUM_DIGITS
N_SUMS = SQ_DIGITS_AT              # columns of a call without ``moments``
N_SUMS_MOMENTS = N_SUMS + SQ_DIGITS  # and with
#: the widest call the counts and digits hold exactly
MAX_ROWS = 1 << 24
MAX_N_O = 24
_EXACT = 1 << 24                   # float32 holds every integer up to it
LANES = 128


def n_sums(moments: bool) -> int:
    return N_SUMS_MOMENTS if moments else N_SUMS


def _n_bytes(n_o: int) -> int:
    return -(-n_o // DIGIT_BITS)


def _byte(v: jax.Array, j: int, n_bytes: int) -> jax.Array:
    """Byte ``j`` of ``0 <= v < 2^(8 n_bytes)``; the top byte keeps the
    rest (below 2^8 too, as ``v`` has no more bits)."""
    v = v >> (DIGIT_BITS * j) if j else v
    return v if j == n_bytes - 1 else v & 0xFF


def _square_parts(n_bytes: int) -> list[tuple[int, int, int, tuple]]:
    """(i, j, weight, ts) of each product of bytes of |d|: d² = Σ_{i<=j}
    weight 2^(8(i+j)) x_i x_j over the bytes x of |d| (weight 2 where
    i < j), and byte t in ``ts`` of the product (below 2^17) goes to digit
    i + j + t; bytes that are always 0 are left out."""
    out = []
    for i in range(n_bytes):
        for j in range(i, n_bytes):
            w = 1 if i == j else 2
            out.append((i, j, w, tuple(
                t for t in range(3) if (w * 255 * 255) >> (DIGIT_BITS * t))))
    return out


def _block_bound(n_bytes: int, moments: bool) -> int:
    """The most one row adds to one digit column."""
    bound = {}
    for i, j, w, ts in _square_parts(n_bytes) if moments else ():
        for t in ts:
            bound[i + j + t] = bound.get(i + j + t, 0) + min(
                (w * 255 * 255) >> (DIGIT_BITS * t), 255)
    return max([255] + list(bound.values()))


def _carry_slack(W: int, n_o: int, moments: bool) -> int:
    """What a carried digit may hold (below 2^8 + 2^16) where a W-word
    cube's digit sums could pass 2^24 and the kernel carries; 0 where they
    cannot (the width-8 cubes): with the carry, the width-8 kernel ran
    1.4% slower on a TPU v5e (PERF.md section 6)."""
    carries = 32 * W * _block_bound(_n_bytes(n_o), moments) >= _EXACT
    return (1 << 8) + (1 << 16) if carries else 0


def default_block_words(W: int, n_o: int, moments: bool) -> int:
    """The largest block of a W-word cube (W halved until it fits) whose
    digit sums stay exact: 2,048 words (the whole cube) at width 8, 1,024
    at width 12, 512 with ``moments``."""
    bound = _block_bound(_n_bytes(n_o), moments)
    bw = W
    while bw > 1 and 32 * bw * bound + _carry_slack(W, n_o, moments) \
            > _EXACT:
        bw //= 2
    return bw


def wire_shape(bw: int) -> tuple[int, int]:
    """(S, L): a block of ``bw`` words as S sublanes of L lanes, dense
    wherever ``bw`` is a multiple of the 128 lanes; else one row (S = 1)."""
    L = LANES if bw % LANES == 0 else bw
    return bw // L, L


def _as_i32(v: int) -> int:
    """A 32-bit pattern as the Python int of the same int32 bits."""
    return v - (1 << 32) if v >= 1 << 31 else v


def _gate_eval(func: jax.Array, a: jax.Array, b: jax.Array,
               tt_packed: int = gates.TT_PACKED) -> jax.Array:
    """Branch-free packed gate eval via a packed truth-table scalar.

    ``tt_packed`` holds up to eight 4-bit truth tables (bit ``k`` of table
    ``f`` = output for inputs with ``a + 2b = k``); ``func`` selects one.
    The shift is arithmetic on int32 — the 0xF mask drops the sign bits.
    """
    tt = (jnp.int32(_as_i32(tt_packed)) >> (4 * func)) & 0xF
    na, nb = ~a, ~b
    m0, m1, m2, m3 = na & nb, a & nb, na & b, a & b
    s = lambda k: -((tt >> k) & 1)
    return (m0 & s(0)) | (m1 & s(1)) | (m2 & s(2)) | (m3 & s(3))


def _fold(x: jax.Array, reduce=jnp.sum) -> jax.Array:
    """``x`` reduced over its leading bit axis: (S, L) from a (32, S, L)
    slab, elementwise across vregs; (1, L) from a (32, L) one."""
    return reduce(x, axis=0, keepdims=x.ndim == 2)


def _slab_partials(g: jax.Array, planes: list[jax.Array], *, n_o: int,
                   edges: tuple[float, ...], moments: bool
                   ) -> tuple[list, jax.Array, list]:
    """Unpack the outputs of one slab of a block and fold each metric
    partial over the bit axis: (sums, wce, edge counts), each an array of
    the slab's word shape, ``None`` for digits no byte reaches."""
    # bit b of word w is input 32*w + b: row b of the golden slab; an
    # output plane (S, L) broadcasts over the leading bit axis, a (1, L)
    # one over sublanes
    bit = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    vals = jnp.zeros(g.shape, jnp.int32)
    for o, plane in enumerate(planes):  # static unroll: n_o <= 24
        vals = vals | (((plane >> bit) & 1) << o)
    diff = g - vals
    ad = jnp.abs(diff)
    nz = diff != 0
    f32 = lambda x: x.astype(jnp.float32)
    tot = lambda x: _fold(f32(x))
    pos = jnp.maximum(diff, 0)
    adf = f32(ad)
    relf = adf / f32(jnp.maximum(g, 1))
    sums = [None] * n_sums(moments)
    sums[ERR_CNT] = tot(nz)
    sums[REL_SUM] = tot(relf)
    sums[ACC0_BAD] = tot((g == 0) & (vals != 0))
    sums[SQ_SUM] = tot(adf * adf)
    sums[REL_SQ] = tot(relf * relf)
    # digit j of Σ|d| and Σd⁺ takes the block sum of byte j of each row
    nb = _n_bytes(n_o)
    for j in range(nb):
        sums[ABS_DIGITS + j] = tot(_byte(ad, j, nb))
        sums[POS_DIGITS + j] = tot(_byte(pos, j, nb))
    if moments:  # Σd², from the bytes of the products of bytes of |d|
        x = [_byte(ad, j, nb) for j in range(nb)]
        parts = {}
        for i, j, w, ts in _square_parts(nb):
            p = x[i] * x[j] * w
            for t in ts:
                b = (p >> (DIGIT_BITS * t)) if t else p
                b = b & 0xFF if t < 2 else b
                k = i + j + t
                parts[k] = b + parts[k] if k in parts else b
        for k, part in parts.items():
            sums[SQ_DIGITS_AT + k] = tot(part)
    # histogram = searchsorted(edges, diff, side="right") over nonzero
    # errors, as differences of "diff >= edge" counts (exact integers)
    dfl = f32(diff)
    ge = [tot(nz & (dfl >= e)) for e in edges]
    return sums, _fold(adf, jnp.max), ge


def _block_row(genome_ref, planes_ref, golden_ref, wires, *, n_i: int,
               n_n: int, n_o: int, edges: tuple[float, ...],
               moments: bool) -> jax.Array:
    """One genome × one cube block: netlist walk + fused metric partials,
    returned as a ``(1, cols)`` accumulator row (layout in the module doc).

    A wire's block of ``bw = S * L`` words is ``S`` rows of the ``(cols *
    S, L)`` scratch, sublane-dense: every vector op of the walk works on
    whole vregs once ``S >= 8``."""
    L = wires.shape[1]
    S = golden_ref.shape[1] if golden_ref.ndim == 3 else 1
    cols = wires.shape[0] // S
    n_wires = n_i + n_n
    bw = S * L
    at = lambda w: pl.multiple_of(w * S, S) if S > 1 else w
    wire = lambda w, s0=0, n=S: wires[pl.ds(at(w) + s0, n), :]

    # --- phase 1: netlist walk over the VMEM wire plane -------------------
    if S == 1:
        wires[0:n_i, :] = planes_ref[...]
    else:
        for i in range(n_i):
            wires[i * S:(i + 1) * S, :] = planes_ref[i]

    def node_step(k, _):
        a = wire(genome_ref[0, 3 * k])
        b = wire(genome_ref[0, 3 * k + 1])
        wires[pl.ds(at(n_i + k), S), :] = _gate_eval(
            genome_ref[0, 3 * k + 2], a, b)
        return 0

    jax.lax.fori_loop(0, n_n, node_step, 0)

    # --- phase 2: per-wire popcounts as one row: ones · popcount(wires)^T --
    # per sublane s (a strided read of row s of every wire): each count is
    # at most 32, exact in bfloat16, and each contraction's sums (at most
    # 32 L) are exact in float32, as are their S-term totals.  Rows past
    # n_wires hold stale scratch; their lanes are overwritten.
    ones = jnp.ones((8, L), jnp.bfloat16)
    pops = None
    for s in range(S):
        w = wires[pl.ds(s, cols, stride=S), :] if S > 1 else wires[...]
        counts = jax.lax.population_count(w).astype(jnp.float32)
        p = jax.lax.dot_general(
            ones, counts.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)[0:1]
        pops = p if pops is None else pops + p

    # --- phase 3: unpack outputs, fuse metric partials ---------------------
    # in slabs of at most 8 sublanes, so that the live values stay near
    # the register file; each partial folds to the word shape, slabs add
    outs = [genome_ref[0, 3 * n_n + o] for o in range(n_o)]
    n = min(S, 8)
    sums = None
    for s0 in range(0, S, n):
        g = golden_ref[:, s0:s0 + n, :] if S > 1 else golden_ref[...]
        ps, pw, pg = _slab_partials(g, [wire(o, s0, n) for o in outs],
                                    n_o=n_o, edges=edges, moments=moments)
        if sums is None:
            sums, wce, ge = ps, pw, pg
        else:
            sums = [a if a is None else a + b for a, b in zip(sums, ps)]
            wce = jnp.maximum(wce, pw)
            ge = [a + b for a, b in zip(ge, pg)]
    tot = lambda x: jnp.sum(x, keepdims=True)  # (1, 1)
    sums = [v if v is None else tot(v) for v in sums]
    sums[COUNT] = jnp.full((1, 1), 32.0 * bw, jnp.float32)
    wce = jnp.max(wce, keepdims=True)
    ge = [sums[ERR_CNT]] + [tot(v) for v in ge] + [
        jnp.zeros((1, 1), jnp.float32)]
    hist = [ge[b] - ge[b + 1] for b in range(len(edges) + 1)]

    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row = jnp.where(col < n_wires, pops, 0.0)
    for j, v in enumerate(sums + [wce] + hist):
        if v is not None:  # digits no byte reaches stay 0
            row = jnp.where(col == n_wires + j, v, row)
    return row


def carry_digits(d: jax.Array) -> jax.Array:
    """Digits (..., K) int32 carried to base 2^8, least significant first:
    every digit but the top in [0, 2^8), the top one signed (an arithmetic
    shift floors)."""
    d = [d[..., k] for k in range(d.shape[-1])]
    for k in range(len(d) - 1):
        d[k + 1] = d[k + 1] + (d[k] >> DIGIT_BITS)
        d[k] = d[k] & ((1 << DIGIT_BITS) - 1)
    return jnp.stack(d, axis=-1)


def _digit_groups(moments: bool) -> tuple[tuple[int, int], ...]:
    """(first column among the sums, digits) of each exact sum: the sums'
    last columns."""
    return ((ABS_DIGITS, SUM_DIGITS), (POS_DIGITS, SUM_DIGITS)) + (
        ((SQ_DIGITS_AT, SQ_DIGITS),) if moments else ())


def _canonical(sums: jax.Array) -> jax.Array:
    """``sums`` with each exact sum's digits fully carried: one value has
    one row, however the cube was cut into blocks."""
    groups = _digit_groups(sums.shape[-1] == N_SUMS_MOMENTS)
    return jnp.concatenate([sums[..., :ABS_DIGITS]] + [
        carry_digits(sums[..., at:at + n].astype(jnp.int32)).astype(
            jnp.float32) for at, n in groups], axis=-1)


def _carry(row: jax.Array, s: int, moments: bool) -> jax.Array:
    """One carry step over the digit columns of a row whose sums start at
    column ``s``: each digit but a sum's top keeps its low 8 bits and
    hands the rest one lane up, to the next digit of its sum.  Digits
    below 2^24 come out below 2^8 + 2^16."""
    col = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    digit = (col >= s + ABS_DIGITS) & (col < s + n_sums(moments))
    low = digit
    for at, n in _digit_groups(moments):
        low = low & (col != s + at + n - 1)
    d = row.astype(jnp.int32)
    up = pltpu.roll(jnp.where(low, d >> DIGIT_BITS, 0), 1, 1)
    d = jnp.where(low, d & ((1 << DIGIT_BITS) - 1), d) + up
    return jnp.where(digit, d.astype(jnp.float32), row)


def cgp_sim_kernel(genome_ref, planes_ref, golden_ref, acc_ref, wires, *,
                   n_i: int, n_n: int, n_o: int, edges: tuple[float, ...],
                   moments: bool, carry: bool):
    """Grid step (genome r, cube block w): row r of the VMEM-resident
    accumulator is set at w == 0 and combined into after: the WCE column by
    max, the others by addition, then (``carry``) the digits carry."""
    r = pl.program_id(0)
    blk = pl.program_id(1)
    row = _block_row(genome_ref, planes_ref, golden_ref, wires, n_i=n_i,
                     n_n=n_n, n_o=n_o, edges=edges, moments=moments)
    acc = acc_ref.at[pl.ds(r, 1), :]
    s = n_i + n_n

    @pl.when(blk == 0)
    def _init():
        acc[...] = row

    @pl.when(blk > 0)
    def _combine():
        prev = acc[...]
        col = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        new = jnp.where(col == s + n_sums(moments),  # the WCE column
                        jnp.maximum(prev, row), prev + row)
        acc[...] = _carry(new, s, moments) if carry else new


def _resolve_interpret(interpret: bool | None) -> bool:
    """``None`` takes the process default (interpret off-TPU only); asking
    for interpret mode on a TPU backend is refused, never silently run."""
    if interpret is None:
        from repro.kernels import ops
        interpret = ops.default_interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("interpret mode requested on a TPU backend: the "
                         "CGP kernel runs compiled there")
    return interpret


@functools.partial(
    jax.jit,
    static_argnames=("n_i", "n_n", "n_o", "gauss_sigma", "n_gauss_side",
                     "block_words", "r_tile", "moments", "interpret"))
def cgp_sim_metrics_batched(nodes: jax.Array, outs: jax.Array,
                            in_planes: jax.Array, golden_vals: jax.Array,
                            *, n_i: int, n_n: int, n_o: int,
                            gauss_sigma: float = 256.0, n_gauss_side: int = 4,
                            block_words: int | None = None, r_tile: int = 8,
                            moments: bool = False,
                            interpret: bool | None = None):
    """Fused (runs × λ) pallas_call: ONE dispatch for R stacked genomes.

    Args:
      nodes: (R, n_n, 3) int32 stacked genomes; outs: (R, n_o) int32.
      in_planes: (n_i, W) int32 — shared across the genome axis.
      golden_vals: (W*32,) int32 — shared across the genome axis.
      block_words: words of the cube a grid step takes; ``None`` takes
        ``default_block_words``.
      r_tile: pad of the genome axis; R is padded up to a multiple with
        copies of the last genome, sliced off on return, so ragged R (e.g. a
        non-multiple sweep-chunk tail) is transparent.
      moments: add the digits of Σd² to the sums.
      interpret: Pallas interpret mode; ``None`` takes
        ``ops.default_interpret()`` (on only off-TPU).
    Returns per-genome accumulators
      (sums (R, n_sums(moments)) f32, wce (R, 1) i32, hist (R, n_bins) f32,
       pops (R, n_n) f32).

    Raises ValueError where a count or digit could leave float32's exact
    range: more than ``MAX_ROWS`` rows, more than ``MAX_N_O`` outputs, or
    a block whose digit sums could pass 2^24.
    """
    interpret = _resolve_interpret(interpret)
    R = nodes.shape[0]
    genomes = jnp.concatenate([nodes.reshape(R, 3 * n_n), outs], axis=1)
    r_pad = (-R) % r_tile
    if r_pad:
        genomes = jnp.concatenate(
            [genomes, jnp.broadcast_to(genomes[-1:], (r_pad,) +
                                       genomes.shape[1:])])
    Rp = R + r_pad
    W = in_planes.shape[1]
    if 32 * W > MAX_ROWS or n_o > MAX_N_O:
        raise ValueError(
            f"{32 * W} rows and {n_o} outputs: the kernel's counts are exact "
            f"up to {MAX_ROWS} rows and |d| up to {MAX_N_O} bits")
    bw = default_block_words(W, n_o, moments) if block_words is None \
        else min(block_words, W)
    assert W % bw == 0, (W, bw)
    if 32 * bw * _block_bound(_n_bytes(n_o), moments) + _carry_slack(
            W, n_o, moments) > _EXACT:
        raise ValueError(f"block_words={bw} lets a digit pass 2^24")
    S, L = wire_shape(bw)
    edges = tuple(float(e) for e in np.float32(
        M.gauss_bin_edges(gauss_sigma, n_gauss_side)))
    n_bins = len(edges) + 1
    n_wires = n_i + n_n
    n_s = n_sums(moments)
    cols = -(-(n_wires + n_s + 1 + n_bins) // 8) * 8

    block_of = lambda r, w: (0, w, 0, 0)
    # (rows, W) -> (rows, blocks, S, L): a block's words sublane-dense; S
    # is a whole dimension, so any S is a legal block (squeezed at S = 1)
    dense = lambda x: x.reshape(x.shape[0], W // bw, S, L)
    words = (S, L) if S > 1 else (None, L)
    kernel = functools.partial(cgp_sim_kernel, n_i=n_i, n_n=n_n, n_o=n_o,
                               edges=edges, moments=moments,
                               carry=_carry_slack(W, n_o, moments) > 0)
    acc = pl.pallas_call(
        kernel,
        grid=(Rp, W // bw),
        in_specs=[
            pl.BlockSpec((None, 1, genomes.shape[1]),
                         lambda r, w: (r, 0, 0),
                         memory_space=pltpu.SMEM),           # one genome
            pl.BlockSpec((n_i, None) + words, block_of),
            pl.BlockSpec((32, None) + words, block_of),
        ],
        out_specs=pl.BlockSpec((Rp, cols), lambda r, w: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cols * S, L), jnp.int32)],  # wires
        interpret=interpret,
    )(genomes[:, None], dense(in_planes),
      dense(golden_vals.reshape(W, 32).T))[:R]
    s = n_wires
    return (_canonical(acc[:, s:s + n_s]),
            acc[:, s + n_s:s + n_s + 1].astype(jnp.int32),
            acc[:, s + n_s + 1:s + n_s + 1 + n_bins],
            acc[:, n_i:n_wires])


@functools.partial(
    jax.jit,
    static_argnames=("n_i", "n_n", "n_o", "gauss_sigma", "n_gauss_side",
                     "block_words", "interpret"))
def cgp_sim_metrics(nodes: jax.Array, outs: jax.Array, in_planes: jax.Array,
                    golden_vals: jax.Array, *, n_i: int, n_n: int, n_o: int,
                    gauss_sigma: float = 256.0, n_gauss_side: int = 4,
                    block_words: int | None = None,
                    interpret: bool | None = None):
    """Per-genome wrapper.  Returns (sums(N_SUMS,), wce(1,), hist, pops(n_n,)).

    in_planes: (n_i, W) int32; golden_vals: (W*32,) int32.  Delegates to the
    batched kernel with a singleton genome axis (``r_tile=1``: no pad rows),
    so there is exactly one kernel body to validate.
    """
    sums, wce, hist, pops = cgp_sim_metrics_batched(
        nodes[None], outs[None], in_planes, golden_vals,
        n_i=n_i, n_n=n_n, n_o=n_o, gauss_sigma=gauss_sigma,
        n_gauss_side=n_gauss_side, block_words=block_words,
        r_tile=1, interpret=interpret)
    return sums[0], wce[0], hist[0], pops[0]
