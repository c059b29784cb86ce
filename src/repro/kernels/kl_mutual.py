"""Fused mutual-learning KL Pallas kernel (paper Eq. 2 at vocab scale).

Computes, for client-stacked logits (K, B, V):

    out[i, b] = 1/(K-1) * sum_{j != i} KL(P_i(b) || P_j(b))

in ONE streaming pass over the vocabulary — no K softmax tensors ever hit
HBM.  Uses a flash-style online decomposition:

    KL(P_i || P_j) = (Z_j - Z_i) + (1/A_i) * sum_v e^{g_i - m_i} (g_i - g_j)

with running max m_i, rescaled partition A_i = sum_v e^{g_i - m_i}
(so Z_i = m_i + log A_i) and a (K x K) cross-accumulator
T_ij = sum_v e^{g_i - m_i} (g_i - g_j), all rescaled when m_i grows.

Grid: (B / bb, V / bv) with the vocab block innermost + sequential; scratch
(m, A, T) persists across vocab blocks in VMEM.  K is small (#clients), so
the T accumulator is (K, K, bb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kl_kernel(logits_ref, out_ref, m_ref, a_ref, t_ref, *,
               K: int, n_v_blocks: int, inv_temp: float):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        a_ref[...] = jnp.zeros_like(a_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    g = logits_ref[...].astype(jnp.float32) * inv_temp   # (K, bb, bv)

    m_prev = m_ref[...]                                  # (K, bb)
    m_new = jnp.maximum(m_prev, jnp.max(g, axis=-1))
    scale = jnp.exp(m_prev - m_new)                      # (K, bb)
    e = jnp.exp(g - m_new[..., None])                    # (K, bb, bv)

    a_ref[...] = a_ref[...] * scale + jnp.sum(e, axis=-1)
    m_ref[...] = m_new
    # T_ij += sum_v e_i * (g_i - g_j);   rescale rows by scale_i
    diff = g[:, None, :, :] - g[None, :, :, :]           # (K, K, bb, bv)
    t_ref[...] = t_ref[...] * scale[:, None, :] + \
        jnp.sum(e[:, None, :, :] * diff, axis=-1)

    @pl.when(iv == n_v_blocks - 1)
    def _finish():
        m = m_ref[...]
        a = a_ref[...]
        z = m + jnp.log(a)                               # (K, bb)
        # KL(i||j) = (Z_j - Z_i) + T_ij / A_i
        kl = (z[None, :, :] - z[:, None, :]) + t_ref[...] / a[:, None, :]
        mask = 1.0 - jnp.eye(K, dtype=jnp.float32)       # zero the diagonal
        avg = jnp.sum(kl * mask[:, :, None], axis=1) / max(K - 1, 1)
        out_ref[...] = avg.astype(out_ref.dtype)


def _kl_pair_kernel(live_ref, fixed_ref, w_ref, out_ref,
                    m_ref, a_ref, mf_ref, af_ref, t_ref, *,
                    n_v_blocks: int, inv_temp: float):
    """Rectangular, pair-weighted variant of ``_kl_kernel``:

        out[i, b] = sum_j w[i, j] * KL(P_i(b) || Q_j(b))

    live (Kl, bb, bv) and fixed (Kg, bb, bv) stream together; scratch adds
    a second (m, A) pair for the fixed side and widens the cross
    accumulator to (Kl, Kg, bb).  The training path (Eq. 2 with the j-side
    received) hits this kernel with ``fixed = stop_gradient(live)`` and the
    participation-masked pair weights.
    """
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        a_ref[...] = jnp.zeros_like(a_ref)
        mf_ref[...] = jnp.full_like(mf_ref, NEG_INF)
        af_ref[...] = jnp.zeros_like(af_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    g = live_ref[...].astype(jnp.float32) * inv_temp     # (Kl, bb, bv)
    h = fixed_ref[...].astype(jnp.float32) * inv_temp    # (Kg, bb, bv)

    m_prev = m_ref[...]                                  # (Kl, bb)
    m_new = jnp.maximum(m_prev, jnp.max(g, axis=-1))
    scale = jnp.exp(m_prev - m_new)
    e = jnp.exp(g - m_new[..., None])                    # (Kl, bb, bv)
    a_ref[...] = a_ref[...] * scale + jnp.sum(e, axis=-1)
    m_ref[...] = m_new

    mf_prev = mf_ref[...]                                # (Kg, bb)
    mf_new = jnp.maximum(mf_prev, jnp.max(h, axis=-1))
    ef = jnp.exp(h - mf_new[..., None])
    af_ref[...] = af_ref[...] * jnp.exp(mf_prev - mf_new) + \
        jnp.sum(ef, axis=-1)
    mf_ref[...] = mf_new

    # T_ij += sum_v e_i * (g_i - h_j);   rescale rows by scale_i
    diff = g[:, None, :, :] - h[None, :, :, :]           # (Kl, Kg, bb, bv)
    t_ref[...] = t_ref[...] * scale[:, None, :] + \
        jnp.sum(e[:, None, :, :] * diff, axis=-1)

    @pl.when(iv == n_v_blocks - 1)
    def _finish():
        z = m_ref[...] + jnp.log(a_ref[...])             # (Kl, bb)
        zf = mf_ref[...] + jnp.log(af_ref[...])          # (Kg, bb)
        kl = (zf[None, :, :] - z[:, None, :]) + \
            t_ref[...] / a_ref[...][:, None, :]
        w = w_ref[...].astype(jnp.float32)               # (Kl, Kg)
        out_ref[...] = jnp.sum(kl * w[:, :, None],
                               axis=1).astype(out_ref.dtype)


# bytes the pair kernel's (Kl, Kg, bb, bv) fp32 difference tensor may take
# in VMEM: the TPU's scoped VMEM limit is 16 MiB, and the difference, its
# product with exp(g) and the double-buffered input blocks share it
_DIFF_VMEM_BYTES = 4 * 2 ** 20


def _pair_block_v(Kl: int, Kg: int, bb: int) -> int:
    """Largest vocab block (a multiple of 128) whose difference tensor
    fits ``_DIFF_VMEM_BYTES``: 2048 at Kl = Kg = 2, 512 at Kl = 2, Kg = 8
    (one device of a four-chip mesh holding two of eight clients)."""
    return max(128, _DIFF_VMEM_BYTES // (Kl * Kg * bb * 4) // 128 * 128)


def _kl_pair_forward(live, fixed, pair_w, temperature: float,
                     interpret: bool, block_b: int, block_v: int):
    Kl, B, V = live.shape
    Kg = fixed.shape[0]
    bb = min(block_b, B)
    bv = min(block_v, V, _pair_block_v(Kl, Kg, bb))
    pad_b = (-B) % bb
    pad_v = (-V) % bv
    if pad_b or pad_v:
        pad = ((0, 0), (0, pad_b), (0, pad_v))
        live = jnp.pad(live, pad, constant_values=NEG_INF)
        fixed = jnp.pad(fixed, pad, constant_values=NEG_INF)
    Bp, Vp = B + pad_b, V + pad_v
    n_b, n_v = Bp // bb, Vp // bv

    kernel = functools.partial(_kl_pair_kernel, n_v_blocks=n_v,
                               inv_temp=1.0 / temperature)
    out = pl.pallas_call(
        kernel,
        grid=(n_b, n_v),
        in_specs=[pl.BlockSpec((Kl, bb, bv), lambda ib, iv: (0, ib, iv)),
                  pl.BlockSpec((Kg, bb, bv), lambda ib, iv: (0, ib, iv)),
                  pl.BlockSpec((Kl, Kg), lambda ib, iv: (0, 0))],
        out_specs=pl.BlockSpec((Kl, bb), lambda ib, iv: (0, ib)),
        out_shape=jax.ShapeDtypeStruct((Kl, Bp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((Kl, bb), jnp.float32),
            pltpu.VMEM((Kl, bb), jnp.float32),
            pltpu.VMEM((Kg, bb), jnp.float32),
            pltpu.VMEM((Kg, bb), jnp.float32),
            pltpu.VMEM((Kl, Kg, bb), jnp.float32),
        ],
        name="kl_mutual_pair",
        interpret=interpret,
    )(live, fixed, pair_w)
    return out[:, :B]


def _streaming_lse(blocks):
    """Blocked logsumexp: (nv, K, B, bv) -> (K, B), one block resident."""
    K, B = blocks.shape[1], blocks.shape[2]

    def step(carry, blk):
        m, a = carry
        m_new = jnp.maximum(m, jnp.max(blk, axis=-1))
        a = a * jnp.exp(m - m_new) + \
            jnp.sum(jnp.exp(blk - m_new[..., None]), axis=-1)
        return (m_new, a), None

    (m, a), _ = jax.lax.scan(
        step, (jnp.full((K, B), NEG_INF, jnp.float32),
               jnp.zeros((K, B), jnp.float32)), blocks)
    return m + jnp.log(a)


def _streaming_pair_bwd(live, fixed, pair_w, out, g_bar,
                        temperature: float, block_v: int):
    """Backward of the pair-weighted Eq. 2, streamed over vocab blocks.

    Never materialises softmax tensors beyond one (K, B, bv) block; per-
    (client, example) statistics (logsumexp Z, the forward output, the
    weight-contracted cotangents) carry the cross terms:

        dlive[c]  = s * gbar_c * p_c * (R_c*lp_c - (W lq)_c - out_c)
        dfixed[c] = -s * ((W^T (gbar*p))_c - q_c * (W^T gbar)_c)

    with s = 1/T, R = W.sum(1), p/lp live softmax, q/lq fixed softmax.
    """
    Kl, B, V = live.shape
    Kg = fixed.shape[0]
    s = 1.0 / temperature
    w = pair_w.astype(jnp.float32)
    bv = min(block_v, V)
    pad_v = (-V) % bv
    gl = live.astype(jnp.float32) * s
    gf = fixed.astype(jnp.float32) * s
    if pad_v:
        pad = ((0, 0), (0, 0), (0, pad_v))
        gl = jnp.pad(gl, pad, constant_values=NEG_INF)
        gf = jnp.pad(gf, pad, constant_values=NEG_INF)
    n_v = (V + pad_v) // bv
    lb = jnp.moveaxis(gl.reshape(Kl, B, n_v, bv), 2, 0)  # (nv, Kl, B, bv)
    fb = jnp.moveaxis(gf.reshape(Kg, B, n_v, bv), 2, 0)

    z = _streaming_lse(lb)                               # (Kl, B)
    zf = _streaming_lse(fb)                              # (Kg, B)
    r = jnp.sum(w, axis=1)                               # (Kl,)
    gbar = g_bar.astype(jnp.float32)                     # (Kl, B)
    col_gbar = jnp.einsum("ic,ib->cb", w, gbar)          # (Kg, B)
    gs = gbar * s

    def step(_, xs):
        glb, gfb = xs
        lp = glb - z[..., None]                          # (Kl, B, bv)
        p = jnp.exp(lp)
        lq = gfb - zf[..., None]                         # (Kg, B, bv)
        q = jnp.exp(lq)
        # NEG_INF padding is finite (-1e30): p == 0 there, products stay 0
        wlq = jnp.einsum("cj,jbv->cbv", w, lq)
        dlive = gs[..., None] * (r[:, None, None] * p * lp
                                 - p * (wlq + out[..., None]))
        gp = gbar[..., None] * p                         # (Kl, B, bv)
        dfixed = -s * (jnp.einsum("ic,ibv->cbv", w, gp)
                       - q * col_gbar[..., None])
        return None, (dlive, dfixed)

    _, (dl, df) = jax.lax.scan(step, None, (lb, fb))
    dl = jnp.moveaxis(dl, 0, 2).reshape(Kl, B, V + pad_v)[:, :, :V]
    df = jnp.moveaxis(df, 0, 2).reshape(Kg, B, V + pad_v)[:, :, :V]
    return dl.astype(live.dtype), df.astype(fixed.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _kl_pair(live, fixed, pair_w, temperature, interpret, block_b, block_v):
    return _kl_pair_forward(live, fixed, pair_w, temperature, interpret,
                            block_b, block_v)


def _kl_pair_fwd(live, fixed, pair_w, temperature, interpret, block_b,
                 block_v):
    out = _kl_pair_forward(live, fixed, pair_w, temperature, interpret,
                           block_b, block_v)
    return out, (live, fixed, pair_w, out)


def _kl_pair_bwd(temperature, interpret, block_b, block_v, res, g_bar):
    live, fixed, pair_w, out = res
    dlive, dfixed = _streaming_pair_bwd(live, fixed, pair_w, out, g_bar,
                                        temperature, block_v)
    # pair weights are data (masks/averaging constants), not parameters
    return dlive, dfixed, jnp.zeros_like(pair_w)


_kl_pair.defvjp(_kl_pair_fwd, _kl_pair_bwd)


def kl_mutual_pair(live, fixed, pair_w, *, temperature: float = 1.0,
                   block_b: int = 128, block_v: int = 2048,
                   interpret: bool = False):
    """Differentiable pair-weighted Eq. 2 via the fused streaming kernel.

    live (Kl, B, V) x fixed (Kg, B, V) with (Kl, Kg) pair weights ->
    (Kl, B).  Carries a ``jax.custom_vjp`` whose backward streams over
    vocab blocks (``_streaming_pair_bwd``) — the Eq.-2 TRAINING path: pass
    ``fixed = stop_gradient(live)`` (or received predictions) and the
    fixed-side cotangent is simply dropped.  Cotangent for ``pair_w`` is
    defined as zero.
    """
    return _kl_pair(live, fixed, pair_w, float(temperature),
                    bool(interpret), int(block_b), int(block_v))


def kl_mutual(logits, *, temperature: float = 1.0,
              block_b: int = 128, block_v: int = 2048,
              interpret: bool = False):
    """logits: (K, B, V) -> (K, B) average pairwise KL per example."""
    K, B, V = logits.shape
    bb = min(block_b, B)
    bv = min(block_v, V)
    pad_b = (-B) % bb
    pad_v = (-V) % bv
    if pad_b or pad_v:
        # vocab padding uses NEG_INF so e -> 0 and (identical) diffs -> 0
        logits = jnp.pad(logits, ((0, 0), (0, pad_b), (0, pad_v)),
                         constant_values=NEG_INF)
    Bp, Vp = B + pad_b, V + pad_v
    n_b, n_v = Bp // bb, Vp // bv

    kernel = functools.partial(_kl_kernel, K=K, n_v_blocks=n_v,
                               inv_temp=1.0 / temperature)
    out = pl.pallas_call(
        kernel,
        grid=(n_b, n_v),
        in_specs=[pl.BlockSpec((K, bb, bv), lambda ib, iv: (0, ib, iv))],
        out_specs=pl.BlockSpec((K, bb), lambda ib, iv: (0, ib)),
        out_shape=jax.ShapeDtypeStruct((K, Bp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((K, bb), jnp.float32),
            pltpu.VMEM((K, bb), jnp.float32),
            pltpu.VMEM((K, K, bb), jnp.float32),
        ],
        name="kl_mutual",
        interpret=interpret,
    )(logits)
    return out[:, :B]
