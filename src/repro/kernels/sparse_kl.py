"""Fused top-k-gather + sparse-KL Pallas kernel (the SparseDML hot path).

Computes, for live logits (Kl, B, V) against J received top-k prediction
sets idx/logp_top (J, B, k) with pair weights (Kl, J):

    out[i, b] = sum_j w[i, j] * KL(P_i(b) || ~Q_j(b))

where ~Q_j is the SparseDML reconstruction (top-k mass + uniform tail over
the V - k residual).  Per pair the KL decomposes into terms that only ever
need a single streaming pass over the vocabulary:

    KL_ij = -H(P_i) - c_j (1 - s_ij) - sum_t p_i[idx_j,t] logp_j[t]

  * -H(P_i) via flash-style online softmax: running max m (Kl, bb),
    rescaled partition A and entropy accumulator U = sum_v e^{g-m} g
    (so  -H = U/A - Z  with  Z = m + log A);
  * the gathers via a raw scaled-logit accumulator gat[i, j, b, t]
    += sum_v 1[idx_jt == v] g_ibv — each received index lands in exactly
    ONE vocab block, so gat accumulates without rescaling and
    p_i[idx] = exp(gat - Z) at the end;
  * c_j, s_ij and the cross term close the formula in the final block.

No softmax tensor ever hits HBM: FLOPs and traffic are O(B·V·(Kl + J·k/bv))
for the streaming pass versus the unfused XLA path's softmax
materialisation + J separate (K, B, k) gathers over a resident (K, B, V)
probability tensor.  With k << V the per-round mutual-step cost scales
with k, matching the comm-side V/(2k) reduction (EXPERIMENTS.md §Perf).

Grid: (B / bb, V / bv), vocab block innermost + sequential; scratch
(m, A, U, gat) persists across vocab blocks in VMEM.  The backward is a
plain-JAX streamed pass (``jax.custom_vjp``; one (Kl, B, bv) block
resident), mirroring ``kl_mutual._streaming_pair_bwd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _sparse_kl_kernel(live_ref, idx_ref, logp_ref, w_ref, out_ref,
                      m_ref, a_ref, u_ref, gat_ref, *,
                      n_v_blocks: int, inv_temp: float, V: int, k: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        a_ref[...] = jnp.zeros_like(a_ref)
        u_ref[...] = jnp.zeros_like(u_ref)
        gat_ref[...] = jnp.zeros_like(gat_ref)

    g = live_ref[...].astype(jnp.float32) * inv_temp     # (Kl, bb, bv)
    bv = g.shape[-1]

    m_prev = m_ref[...]                                  # (Kl, bb)
    m_new = jnp.maximum(m_prev, jnp.max(g, axis=-1))
    scale = jnp.exp(m_prev - m_new)
    e = jnp.exp(g - m_new[..., None])                    # (Kl, bb, bv)
    a_ref[...] = a_ref[...] * scale + jnp.sum(e, axis=-1)
    # entropy accumulator U = sum_v e^{g - m} g, rescaled alongside A
    u_ref[...] = u_ref[...] * scale + jnp.sum(e * g, axis=-1)
    m_ref[...] = m_new

    # top-k gather: every received index lives in exactly one vocab block,
    # so the raw scaled logits accumulate with no rescaling.  The j loop is
    # static (J = #peers, small); the one-hot contraction lowers to a
    # batched dot — no (bb, k, bv) product tensor persists across blocks.
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bv), 2) + iv * bv
    J = idx_ref.shape[0]
    for j in range(J):
        match = (idx_ref[j][:, :, None] == col).astype(jnp.float32)
        hit = jnp.einsum("ibv,btv->ibt", g, match,
                         preferred_element_type=jnp.float32)
        gat_ref[:, j] = gat_ref[:, j] + hit

    @pl.when(iv == n_v_blocks - 1)
    def _finish():
        a = a_ref[...]
        z = m_ref[...] + jnp.log(a)                      # (Kl, bb)
        neg_h = u_ref[...] / a - z                       # -H(P_i)
        logp = logp_ref[...].astype(jnp.float32)         # (J, bb, k)
        p_at = jnp.exp(gat_ref[...] - z[:, None, :, None])   # (Kl,J,bb,k)
        residual = jnp.clip(1.0 - jnp.sum(jnp.exp(logp), axis=-1),
                            1e-9, 1.0)                   # (J, bb)
        c = jnp.log(residual / max(V - k, 1))            # true V, not padded
        s = jnp.sum(p_at, axis=-1)                       # (Kl, J, bb)
        cross = jnp.sum(p_at * logp[None], axis=-1)      # (Kl, J, bb)
        kl = neg_h[:, None, :] - c[None] * (1.0 - s) - cross
        w = w_ref[...].astype(jnp.float32)               # (Kl, J)
        out_ref[0] = jnp.sum(kl * w[:, :, None],
                             axis=1).astype(out_ref.dtype)


# bytes the kernel's (bb, k, bv) fp32 one-hot gather operand may take in
# VMEM (the TPU's scoped VMEM limit is 16 MiB; the live block, its exp
# and the double-buffered inputs share it)
_GATHER_VMEM_BYTES = 4 * 2 ** 20


def _gather_block_v(bb: int, k: int) -> int:
    """Largest vocab block (a multiple of 128) whose one-hot gather
    operand fits ``_GATHER_VMEM_BYTES``."""
    return max(128, _GATHER_VMEM_BYTES // (bb * k * 4) // 128 * 128)


def _sparse_kl_forward(live, idx, logp_top, pair_w, temperature: float,
                       interpret: bool, block_b: int, block_v: int):
    Kl, B, V = live.shape
    J, _, k = idx.shape
    bb = min(block_b, B)
    bv = min(block_v, V, _gather_block_v(bb, k))
    pad_b = (-B) % bb
    pad_v = (-V) % bv
    if pad_b or pad_v:
        # vocab padding uses NEG_INF (e -> 0, products stay 0); padded
        # indices never match padded columns (idx < V <= col)
        live = jnp.pad(live, ((0, 0), (0, pad_b), (0, pad_v)),
                       constant_values=NEG_INF)
    if pad_b:
        idx = jnp.pad(idx, ((0, 0), (0, pad_b), (0, 0)))
        logp_top = jnp.pad(logp_top, ((0, 0), (0, pad_b), (0, 0)))
    Bp, Vp = B + pad_b, V + pad_v
    n_b, n_v = Bp // bb, Vp // bv

    kernel = functools.partial(_sparse_kl_kernel, n_v_blocks=n_v,
                               inv_temp=1.0 / temperature, V=V, k=k)
    out = pl.pallas_call(
        kernel,
        grid=(n_b, n_v),
        in_specs=[pl.BlockSpec((Kl, bb, bv), lambda ib, iv: (0, ib, iv)),
                  pl.BlockSpec((J, bb, k), lambda ib, iv: (0, ib, 0)),
                  pl.BlockSpec((J, bb, k), lambda ib, iv: (0, ib, 0)),
                  pl.BlockSpec((Kl, J), lambda ib, iv: (0, 0))],
        # one (Kl, bb) tile per batch block, on a leading block axis: a
        # (Kl, bb) window of a (Kl, Bp) array needs bb % 128 == 0 on the
        # TPU, and bb = 128 would double the (bb, k, bv) gather operand
        out_specs=pl.BlockSpec((1, Kl, bb), lambda ib, iv: (ib, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_b, Kl, bb), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((Kl, bb), jnp.float32),           # running max m
            pltpu.VMEM((Kl, bb), jnp.float32),           # partition A
            pltpu.VMEM((Kl, bb), jnp.float32),           # entropy acc U
            pltpu.VMEM((Kl, J, bb, k), jnp.float32),     # gathered logits
        ],
        name="sparse_kl_topk",
        interpret=interpret,
    )(live, idx, logp_top, pair_w)
    return out.transpose(1, 0, 2).reshape(Kl, Bp)[:, :B]


def _streaming_lse_entropy(blocks):
    """Blocked (Z, -H): (nv, Kl, B, bv) -> ((Kl, B), (Kl, B)).

    One block resident; carries (m, A, U) with U = sum_v e^{g - m} g so
    Z = m + log A and -H = U/A - Z.
    """
    Kl, B = blocks.shape[1], blocks.shape[2]

    def step(carry, blk):
        m, a, u = carry
        m_new = jnp.maximum(m, jnp.max(blk, axis=-1))
        sc = jnp.exp(m - m_new)
        e = jnp.exp(blk - m_new[..., None])
        a = a * sc + jnp.sum(e, axis=-1)
        u = u * sc + jnp.sum(e * blk, axis=-1)
        return (m_new, a, u), None

    (m, a, u), _ = jax.lax.scan(
        step, (jnp.full((Kl, B), NEG_INF, jnp.float32),
               jnp.zeros((Kl, B), jnp.float32),
               jnp.zeros((Kl, B), jnp.float32)), blocks)
    z = m + jnp.log(a)
    return z, u / a - z


def _streaming_sparse_bwd(live, idx, logp_top, pair_w, g_bar,
                          temperature: float, block_v: int):
    """Backward of the pair-weighted sparse KL, streamed over vocab blocks.

    With p/lp the live softmax, a^j_v = sum_t 1[idx_jt == v] (index
    multiplicity), l^j_v = sum_t 1[idx_jt == v] logp_jt, R_i = sum_j w_ij
    and C1_ib = sum_j w_ij (c_jb s_ijb - cross_ijb):

        dlive[i,b,v] = (1/T) gbar_ib p_v [ R_i (lp_v - (-H_ib))
                        + sum_j w_ij (c_jb a^j_v - l^j_v) - C1_ib ]

    Only per-(client, example) statistics and the (J, B, k) received sets
    carry cross-block state; one (Kl, B, bv) block is resident at a time.
    """
    Kl, B, V = live.shape
    J, _, k = idx.shape
    st = 1.0 / temperature
    w = pair_w.astype(jnp.float32)
    L = logp_top.astype(jnp.float32)
    g = live.astype(jnp.float32) * st
    bv = min(block_v, V)
    pad_v = (-V) % bv
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, pad_v)),
                 constant_values=NEG_INF) if pad_v else g
    n_v = (V + pad_v) // bv
    gb = jnp.moveaxis(gp.reshape(Kl, B, n_v, bv), 2, 0)  # (nv, Kl, B, bv)

    z, neg_h = _streaming_lse_entropy(gb)                # (Kl, B) each
    gval = jax.vmap(lambda gi: jax.vmap(
        lambda ij: jnp.take_along_axis(gi, ij, axis=-1))(idx))(g)
    p_at = jnp.exp(gval - z[:, None, :, None])           # (Kl, J, B, k)
    s = jnp.sum(p_at, axis=-1)                           # (Kl, J, B)
    cross = jnp.sum(p_at * L[None], axis=-1)             # (Kl, J, B)
    residual = jnp.clip(1.0 - jnp.sum(jnp.exp(L), axis=-1), 1e-9, 1.0)
    c = jnp.log(residual / max(V - k, 1))                # (J, B)
    r = jnp.sum(w, axis=1)                               # (Kl,)
    c1 = jnp.einsum("ij,ijb->ib", w, c[None] * s - cross)
    gbar = g_bar.astype(jnp.float32)                     # (Kl, B)

    def step(_, xs):
        blk, ivb = xs                                    # (Kl, B, bv)
        col = ivb * bv + jnp.arange(bv)
        lp = blk - z[..., None]
        p = jnp.exp(lp)                                  # 0 on NEG_INF pad
        wterm = jnp.zeros((Kl, B, bv), jnp.float32)
        for j in range(J):
            match = (idx[j][:, :, None] ==
                     col[None, None, :]).astype(jnp.float32)   # (B, k, bv)
            a_j = jnp.sum(match, axis=1)                 # (B, bv)
            l_j = jnp.einsum("btv,bt->bv", match, L[j])  # (B, bv)
            wterm = wterm + w[:, j, None, None] * \
                (c[j][None, :, None] * a_j[None] - l_j[None])
        d = st * gbar[..., None] * p * (
            r[:, None, None] * (lp - neg_h[..., None]) + wterm
            - c1[..., None])
        return None, d

    _, dl = jax.lax.scan(step, None, (gb, jnp.arange(n_v)))
    dl = jnp.moveaxis(dl, 0, 2).reshape(Kl, B, V + pad_v)[:, :, :V]
    return dl.astype(live.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sparse_kl(live, idx, logp_top, pair_w, temperature, interpret,
               block_b, block_v):
    return _sparse_kl_forward(live, idx, logp_top, pair_w, temperature,
                              interpret, block_b, block_v)


def _sparse_kl_fwd(live, idx, logp_top, pair_w, temperature, interpret,
                   block_b, block_v):
    out = _sparse_kl_forward(live, idx, logp_top, pair_w, temperature,
                             interpret, block_b, block_v)
    return out, (live, idx, logp_top, pair_w)


def _sparse_kl_bwd(temperature, interpret, block_b, block_v, res, g_bar):
    live, idx, logp_top, pair_w = res
    dlive = _streaming_sparse_bwd(live, idx, logp_top, pair_w, g_bar,
                                  temperature, block_v)
    # received indices are integers (tangent space is float0); the received
    # log-probs and pair weights are data (shared constants), not parameters
    return (dlive, np.zeros(idx.shape, jax.dtypes.float0),
            jnp.zeros_like(logp_top), jnp.zeros_like(pair_w))


_sparse_kl.defvjp(_sparse_kl_fwd, _sparse_kl_bwd)


def sparse_kl_topk(live, idx, logp_top, pair_w, *, temperature: float = 1.0,
                   block_b: int = 64, block_v: int = 512,
                   interpret: bool = False):
    """Differentiable pair-weighted sparse KL via the fused streaming kernel.

    live (Kl, B, V) x received top-k sets idx/logp_top (J, B, k) with
    (Kl, J) pair weights -> (Kl, B).  Carries a ``jax.custom_vjp`` whose
    backward streams over vocab blocks (``_streaming_sparse_bwd``);
    cotangents for the received sets and the weights are defined as zero
    (received predictions are data that crossed the client boundary).

    Default blocks are smaller than ``kl_mutual``'s: the gather scratch is
    (Kl, J, bb, k) and must fit VMEM next to the (Kl, bb, bv) live block.
    """
    return _sparse_kl(live, idx, logp_top, pair_w, float(temperature),
                      bool(interpret), int(block_b), int(block_v))
