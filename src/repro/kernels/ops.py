"""Jit'd public wrappers around the Pallas kernels with shape padding and
implementation dispatch: ``impl='pallas'`` runs the kernel (compiled with
Mosaic on a TPU, in interpret mode anywhere else — see :func:`interpret`),
``impl='jnp'`` the oracle.  The caller chooses; ``engine.make`` owns the
choice for the samplers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .minibatch_energy import bucket_energy_pallas
from .flash_attention import flash_attention_pallas
from .fused_sweep import (VMEM_LIMIT_BYTES, mgpmh_sweep_pallas,
                          gibbs_sweep_pallas, min_gibbs_sweep_pallas,
                          double_min_sweep_pallas)
from .ref import (bucket_energy_ref, mgpmh_sweep_ref, gibbs_sweep_ref,
                  min_gibbs_sweep_ref, double_min_sweep_ref)

__all__ = ["bucket_energy", "flash_attention", "mgpmh_sweep", "gibbs_sweep",
           "min_gibbs_sweep", "double_min_sweep", "interpret",
           "KernelTooLarge", "sweep_vmem_bytes", "check_sweep_vmem"]


def interpret() -> bool:
    """True where the Pallas kernels run in interpret mode: on any backend
    but a TPU, where they compile with Mosaic (``Engine.describe()``
    reports it)."""
    return jax.default_backend() != "tpu"


def _check_impl(impl: str):
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"impl must be 'pallas' or 'jnp', got {impl!r}")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _named(scope: str):
    """Trace-time ``jax.named_scope`` around a sweep wrapper so kernel time
    attributes to a named phase in profiler captures.  Applied *under*
    ``jax.jit`` (scopes the traced computation, costs nothing at run time).
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return wrapper
    return deco


@functools.partial(jax.jit, static_argnames=("D", "impl"))
def bucket_energy(w: jax.Array, v: jax.Array, D: int, *,
                  impl: str) -> jax.Array:
    """E[c,u] = sum_k w[c,k] * 1[v[c,k]==u]; see kernels/ref.py.

    impl: 'pallas' — the kernel (interpret mode off-TPU), 'jnp' — the
    oracle.  Handles arbitrary (C, K): pads C to 8 and K to the block size
    with zero weights / out-of-range values.
    """
    _check_impl(impl)
    if impl == "jnp":
        return bucket_energy_ref(w, v, D)
    C, K = w.shape
    dp = max(128, _round_up(D, 128))
    # choose BK so the transient one-hot block stays within ~2 MiB of VMEM
    bc = 8
    bk = max(128, min(512, _round_up((2 * 1024 * 1024) // (4 * bc * dp), 128)))
    Cp, Kp = _round_up(C, bc), _round_up(K, bk)
    # jnp.pad only touches the pad region (no full extra copy of the
    # inputs); aligned shapes skip padding entirely.
    wp = w.astype(jnp.float32)
    vp = v.astype(jnp.int32)
    if (Cp, Kp) != (C, K):
        pad = ((0, Cp - C), (0, Kp - K))
        wp = jnp.pad(wp, pad)                                # zero weight
        vp = jnp.pad(vp, pad, constant_values=D)             # D = no bucket
    out = bucket_energy_pallas(wp, vp, D, bc=bc, bk=bk,
                               interpret=interpret())
    return out[:C, :D] if (Cp, dp) != (C, D) else out


# ---------------------------------------------------------------------------
# Fused multi-site sweep (kernels/fused_sweep.py)
# ---------------------------------------------------------------------------

def _sweep_pads(C, n, S, D, bc=8):
    """(Cp, Np, Sp, Dp): chain/site/sub-step/domain padded dims.  The
    sub-step axis is a lane axis for the (C, S) streams, hence 128."""
    return (_round_up(C, bc), max(128, _round_up(n, 128)),
            max(128, _round_up(S, 128)), max(128, _round_up(D, 128)))


def _pad2(a, Cp, Sp, value=0):
    C, S = a.shape
    if (Cp, Sp) == (C, S):
        return a
    return jnp.pad(a, ((0, Cp - C), (0, Sp - S)), constant_values=value)


def _pad3(a, Cp, Lp):
    """Pad a (C, S, L) stream: chains to Cp, sub-steps to a sublane multiple
    of 8, the trailing lane axis to Lp."""
    C, S, L = a.shape
    Sp = _round_up(S, 8)
    if (Cp, Sp, Lp) == (C, S, L):
        return a
    return jnp.pad(a, ((0, Cp - C), (0, Sp - S), (0, Lp - L)))


class KernelTooLarge(ValueError):
    """A fused sweep kernel's VMEM working set exceeds the limit it
    compiles under (``fused_sweep.VMEM_LIMIT_BYTES``)."""


def _tile_bytes(*shape) -> int:
    """Bytes of a 4-byte block in the (8, 128) tiled VMEM layout."""
    *lead, rows, cols = shape
    return (4 * int(np.prod(lead, dtype=np.int64)) * _round_up(rows, 8)
            * _round_up(cols, 128))


def sweep_vmem_bytes(kernel: str, *, n: int, D: int, S: int, K: int = 0,
                     K2: int = 0) -> int:
    """Upper estimate of the VMEM one fused sweep kernel takes on a chain
    block: every pipelined input/output block twice (double buffering),
    plus the in-kernel working set: one copy of an (Np, Np) table (two for
    gibbs, which at Np = 2048 copies W whole) and, per site lane, 20 KiB
    for the (BC, Np, Dp) temporaries of the proposal kernels, 150 bytes per
    local alias draw lane and 256 per two-stage pair draw lane (the
    f32-exact one-hot gathers split their operands).  The constants bound
    what Mosaic allocates for v5e at potts-20x20 shapes and at the largest
    shape each kernel is admitted at (``tests/test_tpu_compile.py``).

    ``kernel`` is 'gibbs' | 'mgpmh' | 'min-gibbs' | 'doublemin'; ``K`` is
    the draw capacity (doublemin: the proposal's, ``K2`` the second
    batch's); ``S`` the sub-steps per call."""
    _, Np, Sp, Dp = _sweep_pads(8, n, S, D)
    S8 = _round_up(S, 8)
    Kp, K2p = (max(128, _round_up(k, 128)) for k in (K, K2))
    row, lane = _tile_bytes(8, Np), _tile_bytes(8, Sp)
    table, out = _tile_bytes(Np, Np), _tile_bytes(8, Dp)
    gumbel = _tile_bytes(8, S8, Dp)
    if kernel == "gibbs":
        blocks = 2 * row + table + lane + gumbel + out
        copies, site, alias_lanes, pair_lanes = 2, 0, 0, 0
    elif kernel == "mgpmh":
        blocks = (2 * row + 3 * table + 3 * lane + 2 * _tile_bytes(8, S8, Kp)
                  + gumbel + out)
        copies, site, alias_lanes, pair_lanes = 1, 20480, Kp, 0
    elif kernel == "min-gibbs":
        blocks = (4 * row + 2 * table + lane + 2 * gumbel
                  + 4 * _tile_bytes(8, S * D, Kp) + 2 * out)
        copies, site, alias_lanes, pair_lanes = 1, 0, 0, Kp
    elif kernel == "doublemin":
        blocks = (4 * row + 2 * table + 4 * lane + 2 * _tile_bytes(8, S8, Kp)
                  + gumbel + 4 * _tile_bytes(8, S8, K2p) + 3 * out)
        copies, site, alias_lanes, pair_lanes = 1, 20480, Kp, K2p
    else:
        raise ValueError(f"unknown fused sweep kernel {kernel!r}")
    return (2 * blocks + copies * table
            + Np * (site + 150 * alias_lanes + 256 * pair_lanes))


def check_sweep_vmem(kernel: str, **dims) -> None:
    """Raise :class:`KernelTooLarge`, naming the bytes, when the fused
    ``kernel`` cannot hold ``dims`` (see :func:`sweep_vmem_bytes`) in VMEM.
    Past the limit Mosaic refuses the kernel, or spends minutes and tens of
    GiB of host memory compiling before it does."""
    need = sweep_vmem_bytes(kernel, **dims)
    if need > VMEM_LIMIT_BYTES:
        shape = ", ".join(f"{k}={v}" for k, v in dims.items())
        raise KernelTooLarge(
            f"the fused {kernel} sweep kernel needs about {need} bytes of "
            f"VMEM at {shape}, over its limit of {VMEM_LIMIT_BYTES} bytes; "
            f"use backend='jnp' or 'dist' (or a smaller lam)")


def _pad_square(t, Np):
    n = t.shape[0]
    if n == Np:
        return t
    return jnp.pad(t, ((0, Np - n), (0, Np - n)))


@functools.partial(jax.jit, static_argnames=("D", "scale", "impl"))
@_named("repro.kernel/mgpmh_sweep")
def mgpmh_sweep(x, W, row_prob, row_alias, i_sites, B, u_idx, u_alias,
                gumbel, logu, *, D: int, scale: float, impl: str):
    """S fused sequential MGPMH site updates per chain (see kernels/ref.py
    ``mgpmh_sweep_ref`` for exact semantics).

    x (C, n) i32; W/row_prob/row_alias (n, n); i_sites/B/logu (C, S);
    u_idx/u_alias (C, S, K) f32 uniforms; gumbel (C, S, D) f32.
    ``scale`` = L/lambda.
    impl: 'pallas' — the kernel (interpret mode off-TPU),
          'jnp'    — the oracle (kernels/ref.py).
    Returns (x_out (C, n) i32, accepts (C,) i32).

    Padding: chains to 8, sites to 128 lanes with x = D (one-hots into a
    masked lane), draws to 128 with zero weight, the sub-step axis of the
    (C, S) streams to 128 lanes (the kernel only loops the real S).
    """
    _check_impl(impl)
    if impl == "jnp":
        return mgpmh_sweep_ref(x, W, row_prob, row_alias, i_sites, B,
                               u_idx, u_alias, gumbel, logu, D, scale)
    C, n = x.shape
    S = i_sites.shape[1]
    K = u_idx.shape[-1]
    Cp, Np, Sp, Dp = _sweep_pads(C, n, S, D)
    Kp = max(128, _round_up(K, 128))
    xp = x
    if (Cp, Np) != (C, n):
        xp = jnp.pad(x, ((0, Cp - C), (0, Np - n)), constant_values=D)
    out_x, out_acc = mgpmh_sweep_pallas(
        xp, _pad_square(W, Np), _pad_square(row_prob, Np),
        _pad_square(row_alias, Np), _pad2(i_sites, Cp, Sp),
        _pad2(B, Cp, Sp), _pad3(u_idx, Cp, Kp), _pad3(u_alias, Cp, Kp),
        _pad3(gumbel, Cp, Dp), _pad2(logu, Cp, Sp),
        n=n, D=D, S=S, scale=scale,
        interpret=interpret())
    return out_x[:C, :n], out_acc[:C, 0]


@functools.partial(jax.jit, static_argnames=("D", "impl"))
@_named("repro.kernel/gibbs_sweep")
def gibbs_sweep(x, W, i_sites, gumbel, *, D: int, impl: str):
    """S fused sequential vanilla-Gibbs site updates per chain (exact
    conditionals; see kernels/ref.py ``gibbs_sweep_ref``).

    x (C, n) i32; W (n, n); i_sites (C, S); gumbel (C, S, D).
    Returns x_out (C, n) i32.  impl and padding as in mgpmh_sweep.
    """
    _check_impl(impl)
    if impl == "jnp":
        return gibbs_sweep_ref(x, W, i_sites, gumbel, D)
    C, n = x.shape
    S = i_sites.shape[1]
    Cp, Np, Sp, Dp = _sweep_pads(C, n, S, D)
    xp = x
    if (Cp, Np) != (C, n):
        xp = jnp.pad(x, ((0, Cp - C), (0, Np - n)), constant_values=D)
    out_x, _ = gibbs_sweep_pallas(
        xp, _pad_square(W, Np), _pad2(i_sites, Cp, Sp),
        _pad3(gumbel, Cp, Dp), n=n, D=D, S=S,
        interpret=interpret())
    return out_x[:C, :n]


def _pad_cand_streams(streams, Cp, Kp):
    """Pad (C, S, D, K) per-candidate draw streams to (Cp, SD8, Kp): draws
    to Kp lanes, (sub-step, candidate) flattened onto the sublane axis
    (sub-step s, candidate u in row s*D + u)."""
    out = []
    for u in streams:
        C, S, D_, K = u.shape
        if K != Kp:
            u = jnp.pad(u, ((0, 0), (0, 0), (0, 0), (0, Kp - K)))
        out.append(_pad3(u.reshape(C, S * D_, Kp), Cp, Kp))
    return out


def _pad_cache(cache, Cp, Dp):
    """(C,) per-chain scalar cache -> (Cp, Dp) lane-broadcast block."""
    c = jnp.broadcast_to(cache[:, None], (cache.shape[0], Dp))
    C = cache.shape[0]
    if Cp != C:
        c = jnp.pad(c, ((0, Cp - C), (0, 0)))
    return c


def _pad_node_table(t, n, Np):
    """(n,) node alias-table vector -> (8, Np) replicated-row block (the
    kernel reads row 0; 8 sublanes keep the f32 tile shape)."""
    if Np != n:
        t = jnp.pad(t, (0, Np - n))
    return jnp.broadcast_to(t[None, :], (8, Np))


@functools.partial(jax.jit, static_argnames=("D", "lscale", "impl"))
@_named("repro.kernel/min_gibbs_sweep")
def min_gibbs_sweep(x, node_prob, node_alias, row_prob, row_alias, i_sites,
                    B, u_node, u_nacc, u_row, u_racc, gumbel, cache, *,
                    D: int, lscale: float, impl: str):
    """S fused sequential MIN-Gibbs site updates per chain with the cached
    energy estimate threaded through (see kernels/ref.py
    ``min_gibbs_sweep_ref`` for exact semantics).

    x (C, n) i32; node_prob/node_alias (n,); row_prob/row_alias (n, n);
    i_sites (C, S); B (C, S, D) i32; u_node/u_nacc/u_row/u_racc
    (C, S, D, K) f32 uniforms; gumbel (C, S, D) f32; cache (C,) f32.
    ``lscale`` = log1p(Psi/lam).  impl as in mgpmh_sweep.
    Returns (x_out (C, n) i32, cache_out (C,) f32).

    Padding: chains to 8, sites to 128 lanes with x = D, per-candidate draw
    blocks to Kp=128-multiples with zero uniforms (masked by B), the
    (sub-step, candidate) axes flattened onto one sublane axis.
    """
    _check_impl(impl)
    if impl == "jnp":
        return min_gibbs_sweep_ref(x, node_prob, node_alias, row_prob,
                                   row_alias, i_sites, B, u_node, u_nacc,
                                   u_row, u_racc, gumbel, cache, D, lscale)
    C, n = x.shape
    S = i_sites.shape[1]
    K = u_node.shape[-1]
    Cp, Np, Sp, Dp = _sweep_pads(C, n, S, D)
    Kp = max(128, _round_up(K, 128))
    xp = x
    if (Cp, Np) != (C, n):
        xp = jnp.pad(x, ((0, Cp - C), (0, Np - n)), constant_values=D)
    un, una, ur, ura = _pad_cand_streams([u_node, u_nacc, u_row, u_racc],
                                         Cp, Kp)
    out_x, out_cache = min_gibbs_sweep_pallas(
        xp, _pad_node_table(node_prob, n, Np),
        _pad_node_table(node_alias, n, Np), _pad_square(row_prob, Np),
        _pad_square(row_alias, Np), _pad2(i_sites, Cp, Sp),
        _pad3(B, Cp, Dp), un, una, ur, ura, _pad3(gumbel, Cp, Dp),
        _pad_cache(cache, Cp, Dp), n=n, D=D, S=S, lscale=lscale,
        interpret=interpret())
    return out_x[:C, :n], out_cache[:C, 0]


@functools.partial(jax.jit, static_argnames=("D", "scale1", "lscale2",
                                             "impl"))
@_named("repro.kernel/double_min_sweep")
def double_min_sweep(x, row_prob, row_alias, node_prob, node_alias, i_sites,
                     B1, u_idx, u_alias, gumbel, B2, u_node, u_nacc, u_row,
                     u_racc, logu, cache, *, D: int, scale1: float,
                     lscale2: float, impl: str):
    """S fused sequential DoubleMIN site updates per chain with the cached
    xi_x threaded through (see kernels/ref.py ``double_min_sweep_ref``).

    x (C, n) i32; row/node tables as in min_gibbs_sweep; i_sites/B1/B2/logu
    (C, S); u_idx/u_alias (C, S, K1) f32; u_node/u_nacc/u_row/u_racc
    (C, S, K2) f32; gumbel (C, S, D) f32; cache (C,) f32.
    ``scale1`` = L/lam1, ``lscale2`` = log1p(Psi/lam2).  impl and padding
    as in mgpmh_sweep.  Returns (x_out (C, n) i32, cache_out (C,) f32,
    accepts (C,) i32).
    """
    _check_impl(impl)
    if impl == "jnp":
        return double_min_sweep_ref(x, row_prob, row_alias, node_prob,
                                    node_alias, i_sites, B1, u_idx, u_alias,
                                    gumbel, B2, u_node, u_nacc, u_row,
                                    u_racc, logu, cache, D, scale1, lscale2)
    C, n = x.shape
    S = i_sites.shape[1]
    K1 = u_idx.shape[-1]
    K2 = u_node.shape[-1]
    Cp, Np, Sp, Dp = _sweep_pads(C, n, S, D)
    K1p = max(128, _round_up(K1, 128))
    K2p = max(128, _round_up(K2, 128))
    xp = x
    if (Cp, Np) != (C, n):
        xp = jnp.pad(x, ((0, Cp - C), (0, Np - n)), constant_values=D)
    out_x, out_cache, out_acc = double_min_sweep_pallas(
        xp, _pad_square(row_prob, Np), _pad_square(row_alias, Np),
        _pad_node_table(node_prob, n, Np),
        _pad_node_table(node_alias, n, Np), _pad2(i_sites, Cp, Sp),
        _pad2(B1, Cp, Sp), _pad3(u_idx, Cp, K1p), _pad3(u_alias, Cp, K1p),
        _pad3(gumbel, Cp, Dp), _pad2(B2, Cp, Sp), _pad3(u_node, Cp, K2p),
        _pad3(u_nacc, Cp, K2p), _pad3(u_row, Cp, K2p),
        _pad3(u_racc, Cp, K2p), _pad2(logu, Cp, Sp),
        _pad_cache(cache, Cp, Dp), n=n, D=D, S=S, scale1=scale1,
        lscale2=lscale2, interpret=interpret())
    return out_x[:C, :n], out_cache[:C, 0], out_acc[:C, 0]


@functools.partial(jax.jit, static_argnames=("window", "causal"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0, causal: bool = True) -> jax.Array:
    """GQA flash attention via the Pallas kernel.

    q: (B, Sq, H, hd); k/v: (B, Sk, KVH, hd).  Handles GQA head expansion
    and padding to the 128-tile grid; interpret mode off-TPU.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    # expand kv heads to H (wrapper-level; a production layout keeps kv
    # shared per group and indexes inside the kernel)
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    pq = (-Sq) % 128
    pk = (-Sk) % 128
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    out = flash_attention_pallas(
        qf, kf, vf, window=window, causal=causal, sk_valid=Sk,
        interpret=interpret())
    out = out[:, :Sq].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out
