"""Pallas TPU flash attention (train/prefill): online-softmax with the
score tile resident in VMEM — the (Sq, Sk) score pipeline never touches
HBM, which is exactly the term that dominates the HLO-level memory roofline
of the train/prefill cells (EXPERIMENTS.md §Perf H8).

Tiling: grid = (B*H, Sq/BQ, Sk/BK), kv innermost; the running max /
normalizer / accumulator live in VMEM scratch across the kv sweep.
BQ=BK=128 aligns the MXU contraction (hd is 64..256 for all assigned archs).

Supports causal + sliding-window masking (window <= 0 = full) and ragged
Sk via position masking.  ``ops.flash_attention`` is the padded/GQA
wrapper; ``models.attention.flash_attention`` is the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_SCRATCH = pltpu.VMEM     # VMEM scratch works in interpret mode too

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            scale: float, window: int, causal: bool, sk_valid: int,
            bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0]                                   # (BQ, hd)
    k = k_ref[0]                                   # (BK, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < sk_valid
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (BQ, BK)
    corr = jnp.exp(m_prev - m_new)                 # (BQ, 1)
    l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "causal", "sk_valid",
                                             "bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           window: int = 0, causal: bool = True,
                           sk_valid: int = -1, bq: int = 128, bk: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (BH, Sq, hd), k/v: (BH, Sk, hd) pre-padded so Sq % bq == 0,
    Sk % bk == 0 (use ops.flash_attention for the GQA/padding wrapper).
    ``sk_valid``: true KV length before padding (-1 = Sk)."""
    BH, Sq, hd = q.shape
    _, Sk, _ = k.shape
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    sk_valid = Sk if sk_valid < 0 else sk_valid
    scale = hd ** -0.5
    grid = (BH, Sq // bq, Sk // bk)
    scratch = [_SCRATCH((bq, 1), jnp.float32),
               _SCRATCH((bq, 1), jnp.float32),
               _SCRATCH((bq, hd), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          causal=causal, sk_valid=sk_valid, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
