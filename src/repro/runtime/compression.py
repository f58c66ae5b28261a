"""Gradient compression: int8 reduce-scatter -> all-gather with error
feedback.

Why this shape: a plain ``psum`` of int8 would overflow (127 * n_shards),
so real compressed data-parallel all-reduce is RS/AG: each shard owns 1/n of
the vector, receives int8 *chunks* from peers (wire bytes / 4 vs f32),
accumulates locally in f32, then all-gathers its int8 result.  Both
collectives move int8 — visible in the lowered HLO as s8 all-to-all /
all-gather, which is how the dry-run's collective-bytes accounting credits
the 4x reduction.

Error feedback (residual carried to the next step) keeps SGD/Adam
convergence intact under quantization (Karimireddy et al., 2019).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["quantize_int8", "dequantize_int8", "compressed_psum_mean"]


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization: returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(x: jax.Array, axis_name: str,
                         err: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Mean-all-reduce of ``x`` (flat f32 vector, length divisible by the
    axis size) with int8 wire format and error feedback.

    Must run inside shard_map with ``axis_name`` bound.  Returns
    (mean, new_err); ``err`` is this shard's residual from the previous call
    (same shape as x).
    """
    n = jax.lax.axis_size(axis_name)
    xe = x + err
    q, scale = quantize_int8(xe)
    new_err = xe - dequantize_int8(q, scale)

    # reduce-scatter in int8: all_to_all the n chunks, dequant, local sum
    L = q.shape[0]
    chunks = q.reshape(n, L // n)                       # [peer, chunk]
    recv = jax.lax.all_to_all(chunks, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis_name)       # (n,) tiny, f32
    local_sum = jnp.sum(recv.astype(jnp.float32)
                        * scales[:, None], axis=0) / n  # (L/n,) mean chunk

    # all-gather the owned chunk in int8
    q2, s2 = quantize_int8(local_sum)
    gathered = jax.lax.all_gather(q2, axis_name)        # (n, L/n) int8 wire
    s_all = jax.lax.all_gather(s2, axis_name)
    mean = (gathered.astype(jnp.float32)
            * s_all[:, None]).reshape(L)
    return mean, new_err
