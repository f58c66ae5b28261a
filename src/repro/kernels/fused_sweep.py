"""Pallas TPU kernels: fused multi-site sweeps for all four fused samplers
(vanilla Gibbs, MGPMH, MIN-Gibbs, DoubleMIN-Gibbs).

Each kernel updates ``S`` sites per chain in ONE launch instead of one
launch per site — the chain state (and, for the minibatched-estimator
algorithms, the cached eps/xi augmented state) lives in VMEM across all
``S`` sequentially composed sub-steps, so the per-update cost is pure
compute (the paper's O(lambda)) instead of kernel-dispatch latency.

The kernels are instances of one *template*: a per-algorithm substep
plugged into the shared S-step ``fori_loop`` driver, built from shared
primitives —

  * **alias draws** — ``_alias_row_draw`` (per-chain row table, MGPMH's
    local minibatch over A[i]) and ``_pair_draw`` (global factor draw as a
    *two-stage* chain: endpoint ``a`` from a node table with p_a = L_a/2Psi,
    endpoint ``b`` from row a's table with p_b = W_ab/L_a, so
    p({a,b}) = M_phi/Psi exactly without the O(n^2) flat factor table).
    All gathers are realized as one-hot GEMMs so the MXU does the indexing,
    at full f32 precision so they stay exact;
  * **bucket-energy reductions** — ``_bucket``: weighted one-hot
    contractions (the MXU trick of kernels/minibatch_energy.py);
  * **Gumbel-argmax proposal** — ``_argmax_lanes`` over masked lanes
    (categorical(exp eps) == argmax(eps + gumbel));
  * **MH accept** — ``_pick_lane`` two-point energy reads + the log-uniform
    threshold.

Per-algorithm substeps:
  gibbs      exact conditional pass -> Gumbel-argmax (no accept);
  mgpmh      local alias minibatch -> bucket energies -> proposal -> exact
             conditional pass -> MH accept;
  min-gibbs  D independent global minibatches (two-stage pair draws) with
             candidate substitution -> cached-eps slot overwrite (Alg 2's
             augmented state, carried in VMEM) -> Gumbel-argmax (no accept);
  doublemin  MGPMH proposal (no exact pass) -> second global minibatch at
             the proposed state -> MH accept against the cached xi_x
             (Thm 5's augmented state, carried in VMEM).

Randomness: ``host_rng=True`` (default, and the only option off-TPU /
interpret mode) consumes pre-drawn uniforms so each kernel is
bit-comparable to its jnp oracle (kernels/ref.py).  ``host_rng=False``
(the ``*_rng`` entry points) generates the uniforms in-kernel from
``pltpu.prng_random_bits`` seeded per chain-block — identical arithmetic,
only the bit source changes.  For MIN-Gibbs / DoubleMIN this is the
memory fix the ROADMAP called for: the O(C·S·D·lam) resp. O(C·S·lam2)
draw streams never exist in HBM; only the O(C·S·D) Poisson totals (no
lambda factor) stay host-drawn.  It cannot run in interpret mode
(``prng_seed`` has no CPU lowering), so it is TPU-compiled-only.

Tiling / VMEM budget (per grid step, grid = (C/BC,)):
  resident:  the (Np, Np) tables each algorithm needs (W and/or
             row_prob/row_alias; MIN-Gibbs and DoubleMIN skip W entirely),
             x (BC x Np), the per-sub-step uniform/weight blocks
             (host-rng path only), each double-buffered;
  transient: one-hot blocks (BC, L, Np) where L is the draw-lane width
             (Kp for mgpmh/doublemin; MIN-Gibbs loops over its D candidate
             minibatches, Kp lanes each).
  Np/Kp/Dp are 128-multiples (lane width), BC a multiple of 8 (sublanes).
  Every kernel compiles under ``VMEM_LIMIT_BYTES``; ``ops.sweep_vmem_bytes``
  bounds the total from above and the engine refuses a graph past it.
  Per-sub-step scalars are read from their (BC, Sp) blocks by a masked
  lane reduction: Mosaic refuses dynamic unaligned lane slices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["mgpmh_sweep_pallas", "mgpmh_sweep_pallas_rng",
           "gibbs_sweep_pallas",
           "min_gibbs_sweep_pallas", "min_gibbs_sweep_pallas_rng",
           "double_min_sweep_pallas", "double_min_sweep_pallas_rng"]

_NEG = -1e30

# Scoped-VMEM limit every fused kernel compiles under (v5e has 128 MiB of
# VMEM; Mosaic's default scoped limit is 16 MiB).  ``ops.sweep_vmem_bytes``
# must stay an upper bound of what the compiler allocates under it.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# Shared template primitives
# ---------------------------------------------------------------------------

def _uniform_from_bits(bits):  # pragma: no cover - TPU-compiled path
    """uint32 random bits -> f32 uniform in [0, 1) with 24-bit mantissa."""
    return (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))


def _lane_at(block, s):
    """``block[:, s]`` of a (BC, Sp) block as (BC, 1), by a masked lane
    reduction: Mosaic refuses a dynamic lane slice it cannot prove is a
    multiple of 128, which ``block[:, pl.ds(s, 1)]`` is not."""
    iota = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(iota == s, block, jnp.zeros_like(block)),
                   axis=1, keepdims=True)


# The one-hot GEMMs below are gathers and exact sums, so they contract at
# full f32 precision: at Mosaic's default precision the weights, row
# probabilities and alias indices (> 256 on potts-20x20) lose bits, and on
# a v5e chip only 11-98% of chains matched the jnp oracle (CHANGES.md).
_EXACT = jax.lax.Precision.HIGHEST


def _row_select(oh_i, table):
    """Gather rows table[i] for per-chain site ids via one-hot GEMM."""
    return jax.lax.dot(oh_i, table, precision=_EXACT,
                       preferred_element_type=jnp.float32)


def _bucket(w, onehot):
    """Batched ``E[c, u] = sum_k w[c, k] onehot[c, k, u]`` on the MXU."""
    acc = jax.lax.dot_general(
        w[:, None, :], onehot,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=_EXACT, preferred_element_type=jnp.float32)
    return acc[:, 0, :]


def _gather_rows(oh, table):
    """Rows ``table[ids]`` for per-lane ids: (BC, L, Np) one-hot contracted
    with an (Np, Np) table -> (BC, L, Np)."""
    return jax.lax.dot_general(
        oh, table, dimension_numbers=(((2,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)


def _gather_state(oh, x_f):
    """``x[ids]`` for per-lane ids via their one-hot: (BC, L, Np) x
    (BC, Np) -> (BC, L) int32 (values < 2^24: exact in f32)."""
    return jnp.sum(oh * x_f[:, None, :], axis=2).astype(jnp.int32)


def _argmax_lanes(scores, iota_d, width):
    """First-max index over lanes, as (BC, 1) int32 (Mosaic-safe argmax)."""
    m = jnp.max(scores, axis=1, keepdims=True)
    return jnp.min(jnp.where(scores == m, iota_d, width),
                   axis=1, keepdims=True).astype(jnp.int32)


def _pick_lane(vec, iota_d, lane):
    """vec[c, lane[c]] as (BC, 1) f32 via a one-hot reduction."""
    return jnp.sum(jnp.where(iota_d == lane, vec, 0.0), axis=1,
                   keepdims=True)


def _alias_row_draw(u_idx, u_alias, prob_row, alias_row, n):
    """Alias-table draw from per-chain (already row-selected) tables —
    MGPMH's local minibatch over A[i].  u_idx/u_alias (BC, K) uniforms;
    prob_row/alias_row (BC, Np) f32.  Returns (BC, K) int32 ids."""
    BC, K = u_idx.shape
    Np = prob_row.shape[1]
    idx = jnp.minimum((u_idx * n).astype(jnp.int32), n - 1)
    # transposed one-hot so the table gathers are plain _bucket contractions
    iota_nk = jax.lax.broadcasted_iota(jnp.int32, (BC, Np, K), 1)
    oh_idx_t = (idx[:, None, :] == iota_nk).astype(jnp.float32)
    p_g = _bucket(prob_row, oh_idx_t)
    a_g = _bucket(alias_row, oh_idx_t)
    return jnp.where(u_alias < p_g, idx, a_g.astype(jnp.int32))


def _pair_draw(u_node, u_nacc, u_row, u_racc, node_prob, node_alias,
               RP, RA, n):
    """Two-stage global factor draw: endpoint ``a`` from the node alias
    table (p_a = L_a / 2Psi), endpoint ``b`` from row a's alias table
    (p_b = W_ab / L_a); the product is M_phi / Psi (see kernels/ref.py).

    u_* (BC, L) uniforms; node_prob/node_alias (BC, Np) broadcast rows;
    RP/RA the (Np, Np) per-row tables.  Returns (a, b, oh_a, oh_b):
    endpoint ids (BC, L) int32 plus their state-gather one-hots
    (BC, L, Np) f32 (reused by the callers' x[a]/x[b] gathers).
    """
    BC, L = u_node.shape
    Np = RP.shape[0]
    idx1 = jnp.minimum((u_node * n).astype(jnp.int32), n - 1)
    iota_nl = jax.lax.broadcasted_iota(jnp.int32, (BC, Np, L), 1)
    oh1_t = (idx1[:, None, :] == iota_nl).astype(jnp.float32)
    p1 = _bucket(node_prob, oh1_t)
    a1 = _bucket(node_alias, oh1_t)
    a = jnp.where(u_nacc < p1, idx1, a1.astype(jnp.int32))
    iota_ln = jax.lax.broadcasted_iota(jnp.int32, (BC, L, Np), 2)
    oh_a = (a[:, :, None] == iota_ln).astype(jnp.float32)
    prob_a = _gather_rows(oh_a, RP)            # row_prob[a_k] per draw
    alias_a = _gather_rows(oh_a, RA)
    idx2 = jnp.minimum((u_row * n).astype(jnp.int32), n - 1)
    oh_i2 = (idx2[:, :, None] == iota_ln).astype(jnp.float32)
    p2 = jnp.sum(prob_a * oh_i2, axis=2)       # row_prob[a_k, idx2_k]
    a2 = jnp.sum(alias_a * oh_i2, axis=2)
    b = jnp.where(u_racc < p2, idx2, a2.astype(jnp.int32))
    oh_b = (b[:, :, None] == iota_ln).astype(jnp.float32)
    return a, b, oh_a, oh_b


# Host/device-switchable randomness: each returns a per-sub-step source.
# The host variants slice the pre-drawn streams (bit-comparable to the jnp
# oracles); the device variants draw from the in-kernel PRNG in the same
# call order, so only the bit source changes.

def _uniform_stream(host_rng, ref, BC, L):
    if host_rng:
        return lambda s: ref[:, s, :]
    return lambda s: _uniform_from_bits(  # pragma: no cover - TPU path
        pltpu.prng_random_bits((BC, L)))


def _gumbel_stream(host_rng, ref, BC, Dp):
    if host_rng:
        return lambda s: ref[:, s, :]

    def dev(s):  # pragma: no cover - TPU-compiled path
        u = _uniform_from_bits(pltpu.prng_random_bits((BC, Dp)))
        return -jnp.log(-jnp.log(u + 1e-20) + 1e-20)
    return dev


def _logu_stream(host_rng, ref, BC):
    if host_rng:
        return lambda s: _lane_at(ref[...], s)

    def dev(s):  # pragma: no cover - TPU-compiled path
        u = _uniform_from_bits(pltpu.prng_random_bits((BC, 128)))
        return jnp.log(u[:, :1] + 1e-20)
    return dev


def _run_substeps(S, substep, carry):
    """The template driver: S sequentially composed sub-steps in VMEM."""
    return jax.lax.fori_loop(0, S, substep, carry)


# ---------------------------------------------------------------------------
# Gibbs / MGPMH kernel (exact conditional pass; MGPMH adds the local
# minibatch proposal + MH accept)
# ---------------------------------------------------------------------------

def _sweep_kernel(*refs, n: int, D: int, S: int, Kp: int, scale: float,
                  mh: bool, host_rng: bool):
    """One (BC, Np) chain block: S fused sequential site updates."""
    if mh:
        if host_rng:
            (x_ref, w_ref, rp_ref, ra_ref, i_ref, b_ref, u1_ref, u2_ref,
             g_ref, lu_ref, xo_ref, acc_ref) = refs
        else:  # pragma: no cover - TPU-compiled path
            (x_ref, w_ref, rp_ref, ra_ref, i_ref, b_ref, seed_ref,
             xo_ref, acc_ref) = refs
    else:
        if host_rng:
            x_ref, w_ref, i_ref, g_ref, xo_ref, acc_ref = refs
        else:  # pragma: no cover - TPU-compiled path
            x_ref, w_ref, i_ref, seed_ref, xo_ref, acc_ref = refs

    BC, Np = x_ref.shape
    Dp = acc_ref.shape[1]
    W = w_ref[...]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (BC, Np), 1)
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (BC, Dp), 1)
    lane_pad = iota_d >= D
    if mh:
        RP = rp_ref[...]
        RA = ra_ref[...].astype(jnp.float32)  # int-valued, < n <= 2^24: exact
    if not host_rng:  # pragma: no cover - TPU-compiled path
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    if mh:
        rand_u1 = _uniform_stream(host_rng, u1_ref if host_rng else None,
                                  BC, Kp)
        rand_u2 = _uniform_stream(host_rng, u2_ref if host_rng else None,
                                  BC, Kp)
        rand_logu = _logu_stream(host_rng, lu_ref if host_rng else None, BC)
    rand_gumbel = _gumbel_stream(host_rng, g_ref if host_rng else None,
                                 BC, Dp)

    def substep(s, carry):
        x, acc = carry                                     # (BC,Np), (BC,1)
        i_s = _lane_at(i_ref[...], s)                      # (BC, 1)
        oh_i = (iota_n == i_s).astype(jnp.float32)         # (BC, Np)
        w_row = _row_select(oh_i, W)                       # (BC, Np)
        # shared one-hot of the current state (stage 2 + stage 3 operand);
        # padded sites hold D which one-hots into a masked lane.
        iota_nd = jax.lax.broadcasted_iota(jnp.int32, (BC, Np, Dp), 2)
        oh_x = (x[:, :, None] == iota_nd).astype(jnp.float32)
        exact = _bucket(w_row, oh_x)                       # (BC, Dp)

        if mh:
            # stage 1: local alias minibatch over A[i_s]
            prob_row = _row_select(oh_i, RP)               # (BC, Np)
            alias_row = _row_select(oh_i, RA)
            j = _alias_row_draw(rand_u1(s), rand_u2(s), prob_row,
                                alias_row, n)              # (BC, Kp)
            b_s = _lane_at(b_ref[...], s)                  # (BC, 1)
            iota_k = jax.lax.broadcasted_iota(jnp.int32, (BC, Kp), 1)
            w_k = scale * (iota_k < b_s).astype(jnp.float32)
            # stage 2: draws -> per-site counts -> bucket energies over D
            iota_kn = jax.lax.broadcasted_iota(jnp.int32, (BC, Kp, Np), 2)
            oh_j = (j[:, :, None] == iota_kn).astype(jnp.float32)
            cnt = _bucket(w_k, oh_j)                       # (BC, Np)
            eps = _bucket(cnt, oh_x)                       # (BC, Dp)
            scores = eps + rand_gumbel(s)
        else:
            eps = exact
            scores = exact + rand_gumbel(s)

        # stage 4: Gumbel-max proposal + MH accept, state update in VMEM
        scores = jnp.where(lane_pad, _NEG, scores)
        v = _argmax_lanes(scores, iota_d, Dp)              # (BC, 1)
        if mh:
            xi = jnp.sum(jnp.where(iota_n == i_s, x, 0), axis=1,
                         keepdims=True)                    # (BC, 1)
            log_a = (_pick_lane(exact, iota_d, v)
                     - _pick_lane(exact, iota_d, xi)
                     + _pick_lane(eps, iota_d, xi)
                     - _pick_lane(eps, iota_d, v))
            accept = rand_logu(s) < log_a                  # (BC, 1)
            new_v = jnp.where(accept, v, xi)
            acc = acc + accept.astype(jnp.int32)
        else:
            new_v = v
        x = jnp.where(iota_n == i_s, new_v, x)
        return x, acc

    x, acc = _run_substeps(
        S, substep, (x_ref[...], jnp.zeros((BC, 1), jnp.int32)))
    xo_ref[...] = x
    acc_ref[...] = jnp.broadcast_to(acc, (BC, Dp))


# ---------------------------------------------------------------------------
# MIN-Gibbs kernel (Algorithm 2: D independent global minibatches per
# sub-step, cached eps in the VMEM carry, no MH accept)
# ---------------------------------------------------------------------------

def _min_gibbs_kernel(*refs, n: int, D: int, S: int, Kp: int,
                      lscale: float, host_rng: bool):
    if host_rng:
        (x_ref, np_ref, na_ref, rp_ref, ra_ref, i_ref, b_ref, un_ref,
         una_ref, ur_ref, ura_ref, g_ref, c_ref, xo_ref, co_ref) = refs
    else:  # pragma: no cover - TPU-compiled path
        (x_ref, np_ref, na_ref, rp_ref, ra_ref, i_ref, b_ref, c_ref,
         seed_ref, xo_ref, co_ref) = refs

    BC, Np = x_ref.shape
    Dp = co_ref.shape[1]
    RP = rp_ref[...]
    RA = ra_ref[...].astype(jnp.float32)
    nprob = jnp.broadcast_to(np_ref[0:1, :], (BC, Np))
    nalias = jnp.broadcast_to(na_ref[0:1, :], (BC, Np)).astype(jnp.float32)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (BC, Np), 1)
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (BC, Dp), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (BC, Kp), 1)
    lane_pad = iota_d >= D
    if not host_rng:  # pragma: no cover - TPU-compiled path
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))

    def cand_draws(s, u):
        """The four uniform blocks of candidate u's minibatch, (BC, Kp)."""
        if host_rng:
            return [r[:, s * D + u, :] for r in (un_ref, una_ref, ur_ref,
                                                 ura_ref)]
        return [_uniform_from_bits(  # pragma: no cover - TPU path
            pltpu.prng_random_bits((BC, Kp))) for _ in range(4)]
    rand_gumbel = _gumbel_stream(host_rng, g_ref if host_rng else None,
                                 BC, Dp)

    def substep(s, carry):
        x, cache = carry                                   # (BC,Np), (BC,1)
        i_s = _lane_at(i_ref[...], s)                      # (BC, 1)
        x_f = x.astype(jnp.float32)
        b_s = b_ref[:, s, :]                               # (BC, Dp) B_u

        # D independent global minibatches, one per candidate value u, one
        # Kp-lane block at a time (row s*D + u of the host streams)
        def candidate(u, cnt):
            a, b, oh_a, oh_b = _pair_draw(*cand_draws(s, u), nprob, nalias,
                                          RP, RA, n)       # (BC, Kp)
            xa = _gather_state(oh_a, x_f)
            xb = _gather_state(oh_b, x_f)
            # candidate substitution: endpoints hitting i_s read value u
            xa = jnp.where(a == i_s, u, xa)
            xb = jnp.where(b == i_s, u, xb)
            match = (xa == xb) & (iota_k < _lane_at(b_s, u))
            m = jnp.sum(match.astype(jnp.float32), axis=1, keepdims=True)
            return jnp.where(iota_d == u, m, cnt)

        cnt = jax.lax.fori_loop(0, D, candidate,
                                jnp.zeros((BC, Dp), jnp.float32))
        eps = lscale * cnt
        xi = jnp.sum(jnp.where(iota_n == i_s, x, 0), axis=1,
                     keepdims=True)                        # (BC, 1)
        eps = jnp.where(iota_d == xi, cache, eps)  # Alg 2: eps_{x(i)}<-cache
        scores = jnp.where(lane_pad, _NEG, eps + rand_gumbel(s))
        v = _argmax_lanes(scores, iota_d, Dp)              # (BC, 1)
        cache = _pick_lane(eps, iota_d, v)
        x = jnp.where(iota_n == i_s, v, x)
        return x, cache

    x, cache = _run_substeps(S, substep, (x_ref[...], c_ref[:, :1]))
    xo_ref[...] = x
    co_ref[...] = jnp.broadcast_to(cache, (BC, Dp))


# ---------------------------------------------------------------------------
# DoubleMIN kernel (Algorithm 5: MGPMH proposal + second global minibatch
# in the accept test, cached xi_x in the VMEM carry)
# ---------------------------------------------------------------------------

def _double_min_kernel(*refs, n: int, D: int, S: int, K1p: int, K2p: int,
                       scale1: float, lscale2: float, host_rng: bool):
    if host_rng:
        (x_ref, rp_ref, ra_ref, np_ref, na_ref, i_ref, b1_ref, u1_ref,
         u2_ref, g_ref, b2_ref, vn_ref, vna_ref, vr_ref, vra_ref, lu_ref,
         c_ref, xo_ref, co_ref, acc_ref) = refs
    else:  # pragma: no cover - TPU-compiled path
        (x_ref, rp_ref, ra_ref, np_ref, na_ref, i_ref, b1_ref, b2_ref,
         c_ref, seed_ref, xo_ref, co_ref, acc_ref) = refs

    BC, Np = x_ref.shape
    Dp = co_ref.shape[1]
    RP = rp_ref[...]
    RA = ra_ref[...].astype(jnp.float32)
    nprob = jnp.broadcast_to(np_ref[0:1, :], (BC, Np))
    nalias = jnp.broadcast_to(na_ref[0:1, :], (BC, Np)).astype(jnp.float32)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (BC, Np), 1)
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (BC, Dp), 1)
    iota_k1 = jax.lax.broadcasted_iota(jnp.int32, (BC, K1p), 1)
    iota_k2 = jax.lax.broadcasted_iota(jnp.int32, (BC, K2p), 1)
    lane_pad = iota_d >= D
    if not host_rng:  # pragma: no cover - TPU-compiled path
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    rand_u1 = _uniform_stream(host_rng, u1_ref if host_rng else None,
                              BC, K1p)
    rand_u2 = _uniform_stream(host_rng, u2_ref if host_rng else None,
                              BC, K1p)
    rand_gumbel = _gumbel_stream(host_rng, g_ref if host_rng else None,
                                 BC, Dp)
    rand_vn = _uniform_stream(host_rng, vn_ref if host_rng else None,
                              BC, K2p)
    rand_vna = _uniform_stream(host_rng, vna_ref if host_rng else None,
                               BC, K2p)
    rand_vr = _uniform_stream(host_rng, vr_ref if host_rng else None,
                              BC, K2p)
    rand_vra = _uniform_stream(host_rng, vra_ref if host_rng else None,
                               BC, K2p)
    rand_logu = _logu_stream(host_rng, lu_ref if host_rng else None, BC)

    def substep(s, carry):
        x, cache, acc = carry                    # (BC,Np), (BC,1), (BC,1)
        i_s = _lane_at(i_ref[...], s)                      # (BC, 1)
        oh_i = (iota_n == i_s).astype(jnp.float32)
        # MGPMH proposal: local alias minibatch -> bucket energies.  The
        # scale is applied to the exact integer counts so the values are
        # bit-identical to the oracle's ``scale1 * count``.
        prob_row = _row_select(oh_i, RP)
        alias_row = _row_select(oh_i, RA)
        j = _alias_row_draw(rand_u1(s), rand_u2(s), prob_row, alias_row, n)
        b1_s = _lane_at(b1_ref[...], s)                    # (BC, 1)
        w_k = (iota_k1 < b1_s).astype(jnp.float32)
        iota_kn = jax.lax.broadcasted_iota(jnp.int32, (BC, K1p, Np), 2)
        oh_j = (j[:, :, None] == iota_kn).astype(jnp.float32)
        cnt = _bucket(w_k, oh_j)                           # (BC, Np)
        iota_nd = jax.lax.broadcasted_iota(jnp.int32, (BC, Np, Dp), 2)
        oh_x = (x[:, :, None] == iota_nd).astype(jnp.float32)
        eps = scale1 * _bucket(cnt, oh_x)                  # (BC, Dp)
        scores = jnp.where(lane_pad, _NEG, eps + rand_gumbel(s))
        v = _argmax_lanes(scores, iota_d, Dp)              # (BC, 1)
        # second (global) minibatch evaluated at y = x[i_s <- v]
        a, b, oh_a, oh_b = _pair_draw(
            rand_vn(s), rand_vna(s), rand_vr(s), rand_vra(s),
            nprob, nalias, RP, RA, n)                      # (BC, K2p)
        x_f = x.astype(jnp.float32)
        ya = _gather_state(oh_a, x_f)
        yb = _gather_state(oh_b, x_f)
        ya = jnp.where(a == i_s, v, ya)
        yb = jnp.where(b == i_s, v, yb)
        b2_s = _lane_at(b2_ref[...], s)                    # (BC, 1)
        m = jnp.sum(((ya == yb) & (iota_k2 < b2_s)).astype(jnp.float32),
                    axis=1, keepdims=True)
        xi_y = lscale2 * m                                 # (BC, 1)
        # MH accept against the cached xi_x (no exact pass anywhere)
        xi = jnp.sum(jnp.where(iota_n == i_s, x, 0), axis=1,
                     keepdims=True)
        log_a = ((xi_y - cache)
                 + (_pick_lane(eps, iota_d, xi) - _pick_lane(eps, iota_d, v)))
        accept = rand_logu(s) < log_a                      # (BC, 1)
        new_v = jnp.where(accept, v, xi)
        x = jnp.where(iota_n == i_s, new_v, x)
        cache = jnp.where(accept, xi_y, cache)
        acc = acc + accept.astype(jnp.int32)
        return x, cache, acc

    x, cache, acc = _run_substeps(
        S, substep,
        (x_ref[...], c_ref[:, :1], jnp.zeros((BC, 1), jnp.int32)))
    xo_ref[...] = x
    co_ref[...] = jnp.broadcast_to(cache, (BC, Dp))
    acc_ref[...] = jnp.broadcast_to(acc, (BC, Dp))


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _grid_specs(BC, shapes):
    """BlockSpecs taking the ci-th chain block of each (C, ...) input and
    the full array for (n, n) tables (leading dim not C)."""
    specs = []
    for shp, chain_major in shapes:
        if chain_major:
            block = (BC,) + shp[1:]
            nones = (0,) * (len(shp) - 1)
            specs.append(pl.BlockSpec(block, lambda ci, _n=nones: (ci,) + _n))
        else:
            specs.append(pl.BlockSpec(shp, lambda ci, _z=(0,) * len(shp): _z))
    return specs


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "scale", "bc", "interpret"))
def mgpmh_sweep_pallas(x, W, row_prob, row_alias, i_sites, B, u_idx, u_alias,
                       gumbel, logu, *, n: int, D: int, S: int, scale: float,
                       bc: int = 8, interpret: bool = True):
    """Fused S-site MGPMH sweep; pre-padded inputs (see ops.mgpmh_sweep).

    x (C, Np) i32; W/row_prob/row_alias (Np, Np); i_sites/B/logu (C, Sp);
    u_idx/u_alias (C, Sp, Kp) f32; gumbel (C, Sp, Dp) f32.  C % bc == 0,
    Np/Kp/Dp % 128 == 0, S <= Sp.  Returns (x_out (C, Np) i32,
    accepts (C, Dp) i32 — count broadcast over lanes).
    """
    C, Np = x.shape
    Kp = u_idx.shape[-1]
    Dp = gumbel.shape[-1]
    ins = [(x.shape, True), (W.shape, False), (row_prob.shape, False),
           (row_alias.shape, False), (i_sites.shape, True), (B.shape, True),
           (u_idx.shape, True), (u_alias.shape, True), (gumbel.shape, True),
           (logu.shape, True)]
    kernel = functools.partial(_sweep_kernel, n=n, D=D, S=S, Kp=Kp,
                               scale=scale, mh=True, host_rng=True)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins),
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.int32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(x, W.astype(jnp.float32), row_prob.astype(jnp.float32),
      row_alias.astype(jnp.int32), i_sites.astype(jnp.int32),
      B.astype(jnp.int32), u_idx.astype(jnp.float32),
      u_alias.astype(jnp.float32), gumbel.astype(jnp.float32),
      logu.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "Kp", "Dp", "scale", "bc"))
def mgpmh_sweep_pallas_rng(x, W, row_prob, row_alias, i_sites, B, seed,
                           *, n: int, D: int, S: int, Kp: int, Dp: int,
                           scale: float, bc: int = 8):
    """TPU-only variant with in-kernel PRNG (``host_rng=False``): the alias
    draw, Gumbel proposal and MH accept uniforms come from
    ``pltpu.prng_random_bits`` seeded per chain block, so no (C, S, K)
    random streams leave HBM.  ``seed`` is a (1,) int32.  Same pre-padded
    input contract as ``mgpmh_sweep_pallas`` otherwise; cannot run in
    interpret mode (``prng_seed`` has no CPU lowering) — this is the
    ROADMAP's TPU-compiled bench entry point.
    """
    C, Np = x.shape
    ins = [(x.shape, True), (W.shape, False), (row_prob.shape, False),
           (row_alias.shape, False), (i_sites.shape, True), (B.shape, True)]
    kernel = functools.partial(_sweep_kernel, n=n, D=D, S=S, Kp=Kp,
                               scale=scale, mh=True, host_rng=False)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.int32)],
        interpret=False,
        compiler_params=_COMPILER_PARAMS,
    )(x, W.astype(jnp.float32), row_prob.astype(jnp.float32),
      row_alias.astype(jnp.int32), i_sites.astype(jnp.int32),
      B.astype(jnp.int32), seed.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "bc", "interpret"))
def gibbs_sweep_pallas(x, W, i_sites, gumbel, *, n: int, D: int, S: int,
                       bc: int = 8, interpret: bool = True):
    """Fused S-site vanilla-Gibbs sweep; pre-padded inputs.

    Shapes as in mgpmh_sweep_pallas minus the minibatch streams.
    Returns (x_out (C, Np) i32, accepts (C, Dp) i32 — always zero).
    """
    C, Np = x.shape
    Dp = gumbel.shape[-1]
    ins = [(x.shape, True), (W.shape, False), (i_sites.shape, True),
           (gumbel.shape, True)]
    kernel = functools.partial(_sweep_kernel, n=n, D=D, S=S, Kp=0,
                               scale=1.0, mh=False, host_rng=True)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins),
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.int32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(x, W.astype(jnp.float32), i_sites.astype(jnp.int32),
      gumbel.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "lscale", "bc", "interpret"))
def min_gibbs_sweep_pallas(x, node_prob, node_alias, row_prob, row_alias,
                           i_sites, B, u_node, u_nacc, u_row, u_racc,
                           gumbel, cache, *, n: int, D: int, S: int,
                           lscale: float, bc: int = 8,
                           interpret: bool = True):
    """Fused S-site MIN-Gibbs sweep; pre-padded inputs (see
    ops.min_gibbs_sweep).

    x (C, Np) i32; node_prob/node_alias (8, Np) replicated rows;
    row_prob/row_alias (Np, Np); i_sites (C, Sp); B (C, Sp', Dp) i32;
    u_node/u_nacc/u_row/u_racc (C, SD', Kp) f32 — sub-step s's draws for
    candidate u in row s*D + u (SD' = S*D rounded up to 8); gumbel
    (C, Sp', Dp) f32; cache (C, Dp) f32 (lane-broadcast).  Returns
    (x_out (C, Np) i32, cache_out (C, Dp) f32 — value broadcast over
    lanes).
    """
    C, Np = x.shape
    Kp = u_node.shape[-1]
    Dp = gumbel.shape[-1]
    ins = [(x.shape, True), (node_prob.shape, False),
           (node_alias.shape, False), (row_prob.shape, False),
           (row_alias.shape, False), (i_sites.shape, True), (B.shape, True),
           (u_node.shape, True), (u_nacc.shape, True), (u_row.shape, True),
           (u_racc.shape, True), (gumbel.shape, True), (cache.shape, True)]
    kernel = functools.partial(_min_gibbs_kernel, n=n, D=D, S=S, Kp=Kp,
                               lscale=lscale, host_rng=True)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins),
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.float32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(x, node_prob.astype(jnp.float32), node_alias.astype(jnp.int32),
      row_prob.astype(jnp.float32), row_alias.astype(jnp.int32),
      i_sites.astype(jnp.int32), B.astype(jnp.int32),
      u_node.astype(jnp.float32), u_nacc.astype(jnp.float32),
      u_row.astype(jnp.float32), u_racc.astype(jnp.float32),
      gumbel.astype(jnp.float32), cache.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "Kp", "Dp", "lscale", "bc"))
def min_gibbs_sweep_pallas_rng(x, node_prob, node_alias, row_prob, row_alias,
                               i_sites, B, cache, seed, *, n: int, D: int,
                               S: int, Kp: int, Dp: int, lscale: float,
                               bc: int = 8):
    """TPU-only MIN-Gibbs variant with in-kernel PRNG: the four per-draw
    uniform streams — the O(C·S·D·lam) buffers that block paper-scale
    lambda — never exist in HBM; only the O(C·S·D) Poisson totals ``B``
    stay host-drawn.  ``seed`` is a (1,) int32; otherwise the pre-padded
    contract of ``min_gibbs_sweep_pallas``.  TPU-compiled-only.
    """
    C, Np = x.shape
    ins = [(x.shape, True), (node_prob.shape, False),
           (node_alias.shape, False), (row_prob.shape, False),
           (row_alias.shape, False), (i_sites.shape, True), (B.shape, True),
           (cache.shape, True)]
    kernel = functools.partial(_min_gibbs_kernel, n=n, D=D, S=S, Kp=Kp,
                               lscale=lscale, host_rng=False)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.float32)],
        interpret=False,
        compiler_params=_COMPILER_PARAMS,
    )(x, node_prob.astype(jnp.float32), node_alias.astype(jnp.int32),
      row_prob.astype(jnp.float32), row_alias.astype(jnp.int32),
      i_sites.astype(jnp.int32), B.astype(jnp.int32),
      cache.astype(jnp.float32), seed.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "scale1", "lscale2", "bc", "interpret"))
def double_min_sweep_pallas(x, row_prob, row_alias, node_prob, node_alias,
                            i_sites, B1, u_idx, u_alias, gumbel, B2, u_node,
                            u_nacc, u_row, u_racc, logu, cache, *, n: int,
                            D: int, S: int, scale1: float, lscale2: float,
                            bc: int = 8, interpret: bool = True):
    """Fused S-site DoubleMIN sweep; pre-padded inputs (see
    ops.double_min_sweep).

    x (C, Np) i32; row_prob/row_alias (Np, Np); node_prob/node_alias
    (8, Np) replicated rows; i_sites/B1/B2/logu (C, Sp); u_idx/u_alias
    (C, Sp', K1p) f32; u_node/u_nacc/u_row/u_racc (C, Sp', K2p) f32;
    gumbel (C, Sp', Dp) f32; cache (C, Dp) f32 (lane-broadcast).
    Returns (x_out (C, Np) i32, cache_out (C, Dp) f32, accepts (C, Dp)
    i32 — scalars broadcast over lanes).
    """
    C, Np = x.shape
    K1p = u_idx.shape[-1]
    K2p = u_node.shape[-1]
    Dp = gumbel.shape[-1]
    ins = [(x.shape, True), (row_prob.shape, False),
           (row_alias.shape, False), (node_prob.shape, False),
           (node_alias.shape, False), (i_sites.shape, True),
           (B1.shape, True), (u_idx.shape, True), (u_alias.shape, True),
           (gumbel.shape, True), (B2.shape, True), (u_node.shape, True),
           (u_nacc.shape, True), (u_row.shape, True), (u_racc.shape, True),
           (logu.shape, True), (cache.shape, True)]
    kernel = functools.partial(_double_min_kernel, n=n, D=D, S=S, K1p=K1p,
                               K2p=K2p, scale1=scale1, lscale2=lscale2,
                               host_rng=True)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins),
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.float32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.int32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(x, row_prob.astype(jnp.float32), row_alias.astype(jnp.int32),
      node_prob.astype(jnp.float32), node_alias.astype(jnp.int32),
      i_sites.astype(jnp.int32), B1.astype(jnp.int32),
      u_idx.astype(jnp.float32), u_alias.astype(jnp.float32),
      gumbel.astype(jnp.float32), B2.astype(jnp.int32),
      u_node.astype(jnp.float32), u_nacc.astype(jnp.float32),
      u_row.astype(jnp.float32), u_racc.astype(jnp.float32),
      logu.astype(jnp.float32), cache.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "n", "D", "S", "K1p", "K2p", "Dp", "scale1", "lscale2", "bc"))
def double_min_sweep_pallas_rng(x, row_prob, row_alias, node_prob,
                                node_alias, i_sites, B1, B2, cache, seed, *,
                                n: int, D: int, S: int, K1p: int, K2p: int,
                                Dp: int, scale1: float, lscale2: float,
                                bc: int = 8):
    """TPU-only DoubleMIN variant with in-kernel PRNG: the proposal and
    second-batch uniform streams — O(C·S·lam1) + O(C·S·lam2) — never exist
    in HBM; only the (C, Sp) Poisson totals stay host-drawn.  ``seed`` is a
    (1,) int32; otherwise the pre-padded contract of
    ``double_min_sweep_pallas``.  TPU-compiled-only.
    """
    C, Np = x.shape
    ins = [(x.shape, True), (row_prob.shape, False),
           (row_alias.shape, False), (node_prob.shape, False),
           (node_alias.shape, False), (i_sites.shape, True),
           (B1.shape, True), (B2.shape, True), (cache.shape, True)]
    kernel = functools.partial(_double_min_kernel, n=n, D=D, S=S, K1p=K1p,
                               K2p=K2p, scale1=scale1, lscale2=lscale2,
                               host_rng=False)
    return pl.pallas_call(
        kernel,
        grid=(C // bc,),
        in_specs=_grid_specs(bc, ins)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((bc, Np), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0)),
                   pl.BlockSpec((bc, Dp), lambda ci: (ci, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, Np), jnp.int32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.float32),
                   jax.ShapeDtypeStruct((C, Dp), jnp.int32)],
        interpret=False,
        compiler_params=_COMPILER_PARAMS,
    )(x, row_prob.astype(jnp.float32), row_alias.astype(jnp.int32),
      node_prob.astype(jnp.float32), node_alias.astype(jnp.int32),
      i_sites.astype(jnp.int32), B1.astype(jnp.int32),
      B2.astype(jnp.int32), cache.astype(jnp.float32),
      seed.astype(jnp.int32))
