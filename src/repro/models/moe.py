"""Mixture-of-Experts layer: top-k routing with capacity-bucketed dispatch.

TPU-native dropless-ish design (MaxText-style): token->expert assignments are
sorted, tokens scattered into fixed (E, capacity, d) buckets, experts applied
as one stacked einsum (so FLOPs count only *active* experts — important for
roofline honesty), results gathered back with routing weights.  Tokens
overflowing an expert's capacity are dropped (capacity_factor 1.25 default,
standard practice).

Supports shared experts (DeepSeek) that every token passes through densely.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["moe_ffn", "router_topk"]


def router_topk(x: jax.Array, w_router: jax.Array, top_k: int):
    """x: (T, d) -> (weights (T, k), ids (T, k), router probs (T, E))."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights.astype(jnp.float32), ids.astype(jnp.int32), probs


def moe_ffn_sharded(x: jax.Array, params: dict, *, top_k: int,
                    capacity_factor: float, mesh, dp_axes, mp_axis: str,
                    parallelism: str) -> jax.Array:
    """Sharded-dispatch MoE (shard_map region inside jit).

    Why: the scatter-based token->bucket dispatch has data-dependent
    indices, which GSPMD cannot partition — under plain jit every device
    replays the *global* MoE (measured: ~125x flop inflation on the
    256-chip mesh).  Here each data shard buckets only its local tokens;
    activations are replicated across the model axis inside a data shard,
    so expert parallelism needs **no all-to-all**: each model shard either
    owns E/mp experts (EP) and computes just their buckets, or owns a
    d_ff/mp slice of every expert (TP) — one psum over "model" combines
    outputs, the same collective the dense MLP's TP already pays.
    """
    from jax.sharding import PartitionSpec as P

    dp = tuple(dp_axes)
    ep = parallelism == "ep"
    x_spec = P(dp, None, None)
    w_specs = {"router": P()}
    for k in ("w_gate", "w_up", "w_down"):
        if ep:
            w_specs[k] = P(mp_axis, None, None)
        else:
            w_specs[k] = P(None, None, mp_axis) if k != "w_down" \
                else P(None, mp_axis, None)
    if "shared_gate" in params:
        w_specs["shared_gate"] = P(None, mp_axis)
        w_specs["shared_up"] = P(None, mp_axis)
        w_specs["shared_down"] = P(mp_axis, None)
    E = params["router"].shape[-1]

    def body(xb, pw):
        out = _moe_local(xb, pw, top_k=top_k,
                         capacity_factor=capacity_factor, ep=ep,
                         mp_axis=mp_axis, num_experts=E)
        return jax.lax.psum(out, mp_axis)

    return jax.shard_map(body, mesh=mesh, in_specs=(x_spec, w_specs),
                         out_specs=x_spec, check_vma=False)(x, params)


def _moe_local(xb, pw, *, top_k, capacity_factor, ep, mp_axis, num_experts):
    """Per-device MoE on local tokens.  xb: (B_loc, S, d), replicated
    across the model axis within a data shard.  Returns this shard's
    *partial* output (combined by the caller's psum)."""
    B, S, d = xb.shape
    T = B * S
    xt = xb.reshape(T, d)
    E = num_experts
    weights, ids, _ = router_topk(xt, pw["router"], top_k)

    cap = max(top_k, int(capacity_factor * T * top_k / E))
    flat_ids = ids.reshape(-1)
    flat_w = weights.reshape(-1)
    tok_of = jnp.arange(T * top_k) // top_k
    onehot_e = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot_e, axis=0) - onehot_e
    pos = jnp.take_along_axis(pos_in_e, flat_ids[:, None], 1)[:, 0]
    keep = pos < cap

    if ep:
        # this shard owns experts [off, off + E_loc)
        E_loc = pw["w_gate"].shape[0]
        off = jax.lax.axis_index(mp_axis) * E_loc
        local = (flat_ids >= off) & (flat_ids < off + E_loc)
        keep = keep & local
        slot = jnp.where(keep, (flat_ids - off) * cap + pos, E_loc * cap)
        n_slots = E_loc * cap
        eff_E = E_loc
    else:
        slot = jnp.where(keep, flat_ids * cap + pos, E * cap)
        n_slots = E * cap
        eff_E = E

    buckets = jnp.zeros((n_slots + 1, d), xt.dtype).at[slot].set(xt[tok_of])
    buckets = buckets[:-1].reshape(eff_E, cap, d)
    h = jnp.einsum("ecd,edf->ecf", buckets, pw["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buckets, pw["w_up"])
    out_b = jnp.einsum("ecf,efd->ecd", h, pw["w_down"]).reshape(n_slots, d)

    gathered = jnp.where(keep[:, None],
                         out_b[jnp.minimum(slot, n_slots - 1)], 0.0)
    contrib = gathered * flat_w[:, None].astype(gathered.dtype)
    out = jnp.zeros((T, d), contrib.dtype).at[tok_of].add(contrib)

    if "shared_gate" in pw:   # TP-sliced shared experts join the same psum
        hs = jax.nn.silu(xt @ pw["shared_gate"]) * (xt @ pw["shared_up"])
        out = out + hs @ pw["shared_down"]
    return out.reshape(B, S, d).astype(xb.dtype)


@functools.partial(jax.jit, static_argnames=("top_k", "capacity_factor"))
def moe_ffn(x: jax.Array, params: dict, *, top_k: int,
            capacity_factor: float = 1.25) -> jax.Array:
    """x: (B, S, d).  params:
      router (d, E); w_gate/w_up (E, d, ff); w_down (E, ff, d);
      optional shared_gate/shared_up (d, ff_s), shared_down (ff_s, d).
    Returns (B, S, d)."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E = params["router"].shape[-1]
    weights, ids, _ = router_topk(xt, params["router"], top_k)

    # ---- capacity bucketing ----
    cap = max(top_k, int(capacity_factor * T * top_k / E))
    flat_ids = ids.reshape(-1)                         # (T*k,)
    flat_w = weights.reshape(-1)
    tok_of = jnp.arange(T * top_k) // top_k            # originating token
    # position of each assignment within its expert (stable order)
    onehot_e = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)   # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot_e, axis=0) - onehot_e)      # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_ids[:, None], 1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, flat_ids * cap + pos, E * cap)     # E*cap = drop

    buckets = jnp.zeros((E * cap + 1, d), xt.dtype).at[slot].set(xt[tok_of])
    buckets = buckets[:-1].reshape(E, cap, d)

    # ---- expert ffn (active tokens only) ----
    h = jnp.einsum("ecd,edf->ecf", buckets, params["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buckets, params["w_up"])
    out_b = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_b = out_b.reshape(E * cap, d)

    # ---- gather back, weighted combine over the k slots ----
    gathered = jnp.where(keep[:, None],
                         out_b[jnp.minimum(slot, E * cap - 1)], 0.0)
    contrib = gathered * flat_w[:, None].astype(gathered.dtype)
    out = jnp.zeros((T, d), contrib.dtype).at[tok_of].add(contrib)

    # ---- shared experts (dense path) ----
    if "shared_gate" in params:
        hs = jax.nn.silu(xt @ params["shared_gate"]) * (xt @ params["shared_up"])
        out = out + hs @ params["shared_down"]
    return out.reshape(B, S, d).astype(x.dtype)
