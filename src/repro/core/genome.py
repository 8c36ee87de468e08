"""CGP genome representation (paper Sec. III-A).

A candidate circuit with ``n_i`` primary inputs, ``n_o`` primary outputs and
``n_n`` two-input nodes is encoded exactly as in the paper: each node is
``(in0, in1, func)`` where the fan-in indices address either a primary input
(``< n_i``) or an *earlier* node (``n_i + k`` for node ``k``), i.e. full
levels-back (L = n_n), which forbids feedback by construction.  The genome is
kept as two int32 arrays so it vmaps/shards trivially:

    nodes : (n_n, 3) int32
    outs  : (n_o,)   int32

All functions here are jit/vmap-safe unless suffixed ``_np``.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gates


class Genome(NamedTuple):
    """A CGP genome; leaves may carry leading batch dims under vmap."""
    nodes: jax.Array  # (n_n, 3) int32 — (in0, in1, func)
    outs: jax.Array   # (n_o,)  int32


@dataclasses.dataclass(frozen=True)
class CGPSpec:
    """Static CGP problem shape (hashable: usable as a jit static arg)."""
    n_i: int          # primary inputs
    n_o: int          # primary outputs
    n_n: int = 400    # nodes (paper: 400)
    n_funcs: int = gates.N_FUNCS

    @property
    def n_wires(self) -> int:
        return self.n_i + self.n_n

    @property
    def n_genes(self) -> int:
        return self.n_n * 3 + self.n_o

    @property
    def n_inputs_total(self) -> int:
        """Number of exhaustive input combinations 2^n_i."""
        return 1 << self.n_i

    @property
    def n_words(self) -> int:
        """Packed 32-bit words needed to cover the input cube."""
        return max(1, self.n_inputs_total // 32)


def max_fanin_index(spec: CGPSpec) -> np.ndarray:
    """Exclusive upper bound of a legal fan-in index for each node position."""
    return spec.n_i + np.arange(spec.n_n, dtype=np.int32)


def random_genome(key: jax.Array, spec: CGPSpec) -> Genome:
    """Uniform random (legal, feed-forward) genome."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    hi = jnp.asarray(max_fanin_index(spec))  # (n_n,)
    in0 = jax.random.randint(k1, (spec.n_n,), 0, hi)
    in1 = jax.random.randint(k2, (spec.n_n,), 0, hi)
    func = jax.random.randint(k3, (spec.n_n,), 0, spec.n_funcs)
    outs = jax.random.randint(k4, (spec.n_o,), 0, spec.n_wires)
    return Genome(jnp.stack([in0, in1, func], axis=-1).astype(jnp.int32),
                  outs.astype(jnp.int32))


def validate_genome(genome: Genome, spec: CGPSpec) -> bool:
    """Host-side legality check (feed-forward indices in range)."""
    nodes = np.asarray(genome.nodes)
    outs = np.asarray(genome.outs)
    if nodes.shape != (spec.n_n, 3) or outs.shape != (spec.n_o,):
        return False
    hi = max_fanin_index(spec)
    ok = (nodes[:, 0] >= 0).all() and (nodes[:, 1] >= 0).all()
    ok &= (nodes[:, 0] < hi).all() and (nodes[:, 1] < hi).all()
    ok &= (0 <= nodes[:, 2]).all() and (nodes[:, 2] < spec.n_funcs).all()
    ok &= (outs >= 0).all() and (outs < spec.n_wires).all()
    return bool(ok)


# Steps per loop iteration of the reverse sweep.  Once the update is dense
# the loop's own overhead dominates a step; on a TPU v5e, 256 genomes of
# 400 nodes take 0.57 ms a sweep at 2 against 0.86 at 1 and 0.69 at 4.
_ACTIVE_MASK_UNROLL = 2


def active_mask(genome: Genome, spec: CGPSpec) -> jax.Array:
    """Boolean (n_wires,) mask of wires reachable from the primary outputs.

    Classic CGP "active node" computation (the paper's redundant encoding means
    most of the 400 nodes are usually inactive).  Because fan-ins always point
    backwards, a single reverse sweep over the node array suffices; implemented
    as ``lax.scan`` so it stays jit/vmap friendly.  Its device ops carry the
    scope ``active_mask`` in their HLO metadata, whatever program calls it.

    Each step ORs a one-hot of the node's fan-ins into the mask, compared
    against a wire iota, instead of writing the two fan-in positions: under
    ``vmap`` an indexed write becomes a scatter with one row index per
    candidate, which the TPU runs row after row, while the compare-and-OR
    over ``(batch, n_wires)`` is one fused elementwise op.
    """
    n_i, n_n = spec.n_i, spec.n_n
    wire = jnp.arange(spec.n_wires, dtype=jnp.int32)

    with jax.named_scope("active_mask"):
        in0, in1 = genome.nodes[:, 0], genome.nodes[:, 1]
        uses_b = jnp.asarray(gates.ONE_INPUT)[genome.nodes[:, 2]] == 0

        def step(active, k):
            # walk nodes from last to first
            idx = n_n - 1 - k
            is_act = active[n_i + idx]
            fanin = (wire == in0[idx]) | ((wire == in1[idx]) & uses_b[idx])
            return active | (fanin & is_act), None

        active0 = (wire[:, None] == genome.outs[None, :]).any(-1)
        active, _ = jax.lax.scan(step, active0, jnp.arange(n_n),
                                 unroll=_ACTIVE_MASK_UNROLL)
    return active


def active_node_count(genome: Genome, spec: CGPSpec) -> jax.Array:
    return active_mask(genome, spec)[spec.n_i:].sum()


def critical_path_ps(genome: Genome, spec: CGPSpec) -> jax.Array:
    """Longest-path delay (ps) over *active* wires using per-gate delays."""
    delay_tab = jnp.asarray(gates.DELAY_PS)
    one_input = jnp.asarray(gates.ONE_INPUT)
    act = active_mask(genome, spec)
    depth0 = jnp.zeros((spec.n_wires,), dtype=jnp.float32)

    def step(depth, k):
        node = genome.nodes[k]
        d_in0 = depth[node[0]]
        d_in1 = jnp.where(one_input[node[2]] == 1, 0.0, depth[node[1]])
        d = jnp.maximum(d_in0, d_in1) + delay_tab[node[2]]
        d = jnp.where(act[spec.n_i + k], d, 0.0)
        return depth.at[spec.n_i + k].set(d), None

    depth, _ = jax.lax.scan(step, depth0, jnp.arange(spec.n_n))
    return jnp.max(depth[genome.outs])


# --------------------------------------------------------------------------
# Canonical phenotype form (DESIGN.md §8)
# --------------------------------------------------------------------------
#
# The paper's CGP encoding is deliberately redundant: most of the 400 nodes
# are inactive, so many genotypes share one *phenotype* — the subgraph of
# active nodes actually reachable from the primary outputs.  Everything a
# candidate evaluation returns (error metrics AND the activity-masked
# power/area model) is a function of that subgraph alone, which makes the
# canonical form below a sound cache key for evaluation results:
#
#   * active nodes are COMPACTED to the front of the node array in their
#     original (= topological: fan-ins always point backwards) order and
#     every fan-in / output gene is renumbered accordingly;
#   * the unused second fan-in of a one-input gate is zeroed (it never
#     affects simulation, but would otherwise split identical phenotypes);
#   * inactive genes are dropped entirely (the tail of the canonical array
#     is zero and excluded from the digest).
#
# Two genotypes map to the same canonical form iff their active subgraphs
# are gate-for-gate identical (commutative input swaps are deliberately NOT
# folded — a swapped gate is a different, if equivalent, subgraph).  The
# digest is a 16-byte BLAKE2b over the canonical genes, so accidental
# collisions are vanishingly unlikely (~2^-64 at billions of entries).

PHENOTYPE_DIGEST_SIZE = 16  # bytes of BLAKE2b digest per phenotype


def active_mask_np(nodes: np.ndarray, outs: np.ndarray,
                   spec: CGPSpec) -> np.ndarray:
    """Batched host-side active mask: (R, n_wires) bool.

    NumPy twin of ``active_mask`` for the dedup cache's host-side
    canonicalization (one reverse sweep, vectorized over the population).
    """
    nodes = np.asarray(nodes)
    outs = np.asarray(outs)
    R = nodes.shape[0]
    n_i = spec.n_i
    one_input = gates.ONE_INPUT
    act = np.zeros((R, spec.n_wires), dtype=bool)
    act[np.arange(R)[:, None], outs] = True
    rows = np.arange(R)
    for k in range(spec.n_n - 1, -1, -1):
        is_act = act[:, n_i + k]
        uses_b = is_act & (one_input[nodes[:, k, 2]] == 0)
        act[rows, nodes[:, k, 0]] |= is_act
        act[rows, nodes[:, k, 1]] |= uses_b
    return act


def canonicalize_phenotypes_np(nodes: np.ndarray, outs: np.ndarray,
                               spec: CGPSpec
                               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Canonical active-subgraph form of a stacked population.

    Args:
      nodes: (R, n_n, 3) int32; outs: (R, n_o) int32 (host arrays).

    Returns:
      (canon_nodes (R, n_n, 3), canon_outs (R, n_o), n_active (R,)) — per
      genome, the first ``n_active[r]`` rows of ``canon_nodes[r]`` hold the
      active subgraph in topological order with renumbered fan-ins and
      zeroed unary second fan-ins; the tail rows are zero.
    """
    nodes = np.asarray(nodes)
    outs = np.asarray(outs)
    R = nodes.shape[0]
    n_i, n_n = spec.n_i, spec.n_n
    act = active_mask_np(nodes, outs, spec)
    node_act = act[:, n_i:]                       # (R, n_n)
    new_idx = np.cumsum(node_act, axis=1, dtype=np.int32) - 1
    n_active = node_act.sum(axis=1).astype(np.int32)

    def remap(w):  # wire index -> canonical wire index, rows aligned
        node_ref = w >= n_i
        k = np.clip(w - n_i, 0, n_n - 1)
        return np.where(node_ref,
                        n_i + np.take_along_axis(new_idx, k, axis=1), w)

    func = nodes[:, :, 2]
    unary = gates.ONE_INPUT[func] == 1
    m0 = remap(nodes[:, :, 0])
    m1 = remap(np.where(unary, 0, nodes[:, :, 1]))

    canon = np.zeros((R, n_n, 3), np.int32)
    r_idx, k_idx = np.nonzero(node_act)
    pos = new_idx[r_idx, k_idx]
    canon[r_idx, pos, 0] = m0[r_idx, k_idx]
    canon[r_idx, pos, 1] = m1[r_idx, k_idx]
    canon[r_idx, pos, 2] = func[r_idx, k_idx]
    canon_outs = remap(outs).astype(np.int32)
    return canon, canon_outs, n_active


def phenotype_digests(nodes: np.ndarray, outs: np.ndarray,
                      spec: CGPSpec) -> list[bytes]:
    """Stable per-genome phenotype digests of a stacked population.

    Identical for genotypes with the same active subgraph; used as the
    dedup-cache key (``core.evalcache``).  Host-side by design — the dedup
    path runs between jit segments (DESIGN.md §8).
    """
    canon, canon_outs, n_active = canonicalize_phenotypes_np(nodes, outs,
                                                             spec)
    digests = []
    for r in range(canon.shape[0]):
        na = int(n_active[r])
        h = hashlib.blake2b(digest_size=PHENOTYPE_DIGEST_SIZE)
        h.update(na.to_bytes(4, "little"))
        h.update(canon[r, :na].tobytes())
        h.update(canon_outs[r].tobytes())
        digests.append(h.digest())
    return digests


def phenotype_digest(genome: Genome, spec: CGPSpec) -> bytes:
    """Single-genome convenience wrapper around ``phenotype_digests``."""
    return phenotype_digests(np.asarray(genome.nodes)[None],
                             np.asarray(genome.outs)[None], spec)[0]


def genome_to_flat(genome: Genome) -> jax.Array:
    """Flatten to the paper's integer string (n_n*(n_a+1)+n_o ints)."""
    return jnp.concatenate([genome.nodes.reshape(-1), genome.outs])


def flat_to_genome(flat: jax.Array, spec: CGPSpec) -> Genome:
    nodes = flat[: spec.n_n * 3].reshape(spec.n_n, 3)
    outs = flat[spec.n_n * 3:]
    return Genome(nodes, outs)
