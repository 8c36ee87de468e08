"""Token-choice top-k Mixture-of-Experts with expert parallelism.

Dispatch is the sort-based capacity scheme (no (T, E, C) one-hot tensors —
those are impossible at E=384): flatten (token, k) assignments, sort by
expert, compute the position-in-expert by segment offsets, drop beyond
capacity, scatter into an (E, C, D) buffer, run the batched expert FFN, and
combine back with router weights.

Distribution: experts shard over the "tp"/model mesh axis.  Two paths:

  * ``moe_ffn`` — single-shard math (smoke tests, and the pjit fallback
    where GSPMD inserts the collectives for the sharded expert einsums).
  * ``moe_ffn_ep`` — explicit shard_map: every model shard routes the
    (replicated) token block, computes ONLY its local experts and psums the
    partial combine — the collective-light EP scheme whose roofline term is
    analyzed in EXPERIMENTS.md (§Perf iterates on it).

Aux losses: the standard load-balancing loss (mean_e f_e · p_e · E) is
returned so train steps can add it.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, matmul, rms_norm

Tree = Any


def init_moe(key, cfg: ModelConfig) -> Tree:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    dt = cfg.pdtype()
    ks = jax.random.split(key, 4)
    scale = 1.0 / (d ** 0.5)
    mk = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                           * scale).astype(dt)
    return {
        "norm": jnp.ones((d,), dt),
        "router": dense_init(ks[0], d, E, jnp.float32),  # router in fp32
        "w_gate": mk(ks[1], (E, d, f)),
        "w_up": mk(ks[2], (E, d, f)),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                   / (f ** 0.5)).astype(dt),
    }


def moe_specs(cfg: ModelConfig) -> Tree:
    return {"norm": (None,), "router": ("fsdp", None),
            "w_gate": ("tp", "fsdp", None), "w_up": ("tp", "fsdp", None),
            "w_down": ("tp", None, "fsdp")}


def _route(h2: jax.Array, router: jax.Array, top_k: int):
    """h2: (T, D) -> (weights (T,K), ids (T,K), aux_loss)."""
    logits = h2.astype(jnp.float32) @ router.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    E = router.shape[1]
    # load-balance aux: E * Σ_e fraction_e * prob_e
    frac = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / ids.size)
    aux = E * jnp.sum(frac * probs.mean(0))
    return w, ids, aux


def _dispatch_indices(ids: jax.Array, E: int, capacity: int):
    """Sort-based positions.  ids: (T, K) -> scatter indices + keep mask."""
    TK = ids.size
    flat_e = ids.reshape(-1)
    order = jnp.argsort(flat_e)                      # stable
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(TK) - seg_start                 # position within expert
    keep = pos < capacity
    buf_idx = sorted_e * capacity + jnp.minimum(pos, capacity - 1)
    return order, buf_idx, keep


def _expert_ffn(buf: jax.Array, params: Tree, cfg: ModelConfig) -> jax.Array:
    """buf: (E, C, D) -> (E, C, D) batched expert SwiGLU."""
    w_g = params["w_gate"].astype(buf.dtype)
    w_u = params["w_up"].astype(buf.dtype)
    w_d = params["w_down"].astype(buf.dtype)
    gate = jnp.einsum("ecd,edf->ecf", buf, w_g)
    up = jnp.einsum("ecd,edf->ecf", buf, w_u)
    inner = jax.nn.silu(gate.astype(jnp.float32)).astype(buf.dtype) * up
    return jnp.einsum("ecf,efd->ecd", inner, w_d)


def _capacity(T: int, cfg: ModelConfig, full: bool) -> int:
    m = cfg.moe
    if full:
        return T  # decode: an expert can receive every token — no drops
    return int(max(1, T * m.top_k * m.capacity_factor / m.n_experts))


def moe_ffn(params: Tree, x: jax.Array, cfg: ModelConfig,
            full_capacity: bool = False):
    """Single-shard / pjit path.  x: (B, S, D) -> (B, S, D), aux."""
    B, S, d = x.shape
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    out, aux = _moe_math_dyn(h.reshape(B * S, d), params, cfg, 0,
                             cfg.moe.n_experts,
                             capacity=_capacity(B * S, cfg, full_capacity))
    return x + out.reshape(B, S, d), aux


def moe_ffn_ep(params: Tree, x: jax.Array, cfg: ModelConfig,
               mesh, expert_axis: str = "model",
               full_capacity: bool = False):
    """Explicit expert-parallel path (shard_map over the model axis).

    Token block is replicated across the model axis; every shard computes
    its local expert slice and the combine is a psum — collective cost is
    one (B,S,D) psum, identical to a TP FFN reduce, with no (T,E,C) tensor
    ever materialized globally.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    n_shards = mesh.shape[expert_axis]
    assert m.n_experts % n_shards == 0
    e_per = m.n_experts // n_shards

    def local(x_l, norm, router, w_gate, w_up, w_down):
        B, S, d = x_l.shape
        p_l = {"norm": norm, "router": router, "w_gate": w_gate,
               "w_up": w_up, "w_down": w_down}
        shard = jax.lax.axis_index(expert_axis)
        h = rms_norm(x_l, norm, cfg.norm_eps)
        e_lo = shard * e_per
        out, aux = _moe_math_dyn(h.reshape(B * S, d), p_l, cfg, e_lo, e_per,
                                 capacity=_capacity(B * S, cfg,
                                                    full_capacity))
        out = jax.lax.psum(out, expert_axis)
        aux = jax.lax.pmean(aux, expert_axis)
        return x_l + out.reshape(B, S, d), aux

    # shard the token batch over data only when divisible (decode at tiny
    # batch replicates tokens instead — the expert math still splits over
    # the model axis)
    dp_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    data_spec = (P(dp, None, None) if x.shape[0] % dp_size == 0
                 else P(None, None, None))
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(data_spec, P(None), P(None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=(data_spec, P()),
        check_vma=False)
    return fn(x, params["norm"], params["router"], params["w_gate"],
              params["w_up"], params["w_down"])


def _moe_math_dyn(h2, params, cfg, e_lo, e_per: int, capacity: int):
    """Dispatch->ffn->combine for experts [e_lo, e_lo+e_per) (e_lo may be a
    traced shard_map axis_index)."""
    m = cfg.moe
    w, ids, aux = _route(h2, params["router"], m.top_k)
    order, buf_idx, keep = _dispatch_indices(ids, m.n_experts, capacity)
    sorted_e = ids.reshape(-1)[order]
    local = (sorted_e >= e_lo) & (sorted_e < e_lo + e_per)
    keep = keep & local
    buf_idx = buf_idx - e_lo * capacity
    buf_idx = jnp.where(keep, buf_idx, e_per * capacity)
    tok_idx = order // m.top_k
    gathered = h2[tok_idx] * keep[:, None].astype(h2.dtype)
    buf = jnp.zeros((e_per * capacity + 1, h2.shape[1]), h2.dtype)
    buf = buf.at[buf_idx].set(gathered)
    out_buf = _expert_ffn(buf[:-1].reshape(e_per, capacity, -1), params, cfg)
    out_buf = jnp.concatenate(
        [out_buf.reshape(e_per * capacity, -1),
         jnp.zeros((1, h2.shape[1]), h2.dtype)], axis=0)
    back = out_buf[jnp.where(keep, buf_idx, e_per * capacity)]
    wk = w.reshape(-1)[order] * keep.astype(jnp.float32)
    out = jnp.zeros_like(h2).at[tok_idx].add(
        back * wk[:, None].astype(h2.dtype))
    return out, aux
