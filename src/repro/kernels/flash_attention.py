"""Blockwise (flash) causal attention Pallas kernel, TPU-targeted.

Layout: q (B, Hq, S, hd), k/v (B, Hkv, T, hd) — head-major so the last two
dims are the MXU matmul operands.  Grid (B, Hq, S/bq, T/bk) with the KV block
index innermost and sequential; running max / denominator / accumulator live
in VMEM scratch and persist across KV iterations (the standard TPU flash
pattern).  GQA is handled in the k/v index_map (query head h reads KV head
h // group) so KV is never materialised per-query-head.

Causal + sliding-window masking is done blockwise: fully-masked KV blocks are
skipped with pl.when, diagonal blocks masked via iota.

DIFFERENTIABLE: the forward additionally emits the per-row logsumexp, and
``flash_attention`` carries a ``jax.custom_vjp`` whose backward recomputes
the blockwise softmax from the saved (q, k, v, out, lse) residuals and
streams dq/dk/dv over KV blocks (``_streaming_attn_bwd``) — the same
recompute-not-materialise pattern as ``kernels.kl_mutual`` /
``kernels.sparse_kl``, so the O(S·T) score matrix never hits HBM in either
direction.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                 *, bq: int, bk: int, n_kv_blocks: int, causal: bool,
                 window: Optional[int], sm_scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk

    # Block-level reachability: any (qpos, kpos) pair with kpos <= qpos and
    # qpos - kpos < window?  Max qpos in block = q_start+bq-1; min kpos = k_start.
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window is not None:
        live = jnp.logical_and(live, (q_start) - (k_start + bk - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        scale = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * scale + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        # per-row logsumexp Z = m + log(l): the backward's softmax residual
        lse_ref[0, 0] = m_ref[...] + jnp.log(denom)


def _flash_forward(q, k, v, causal: bool, window: Optional[int],
                   block_q: int, block_k: int, interpret: bool):
    """One pallas_call -> (out (B, Hq, S, hd), lse (B, Hq, S) fp32)."""
    B, Hq, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    group = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, T)
    pad_q = (-S) % bq
    pad_k = (-T) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded KV positions are masked out via causal (kpos > qpos) only if
        # they trail every query; with padding at the end this holds for
        # causal attention, which is the only mode the kernel serves.
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq, Tk = S + pad_q, T + pad_k
    n_q, n_k = Sq // bq, Tk // bk

    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, n_kv_blocks=n_k, causal=causal,
        window=window, sm_scale=hd ** -0.5)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            # running max, denominator, output accumulator (fp32, VMEM)
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S], lse[:, :, :S, 0]


def _streaming_attn_bwd(q, k, v, out, lse, dout, causal: bool,
                        window: Optional[int], block_k: int):
    """Flash backward, streamed over KV blocks in plain JAX (lax.scan).

    Recomputes each (S, bk) score block from the saved row logsumexp
    instead of materialising the O(S·T) probability matrix:

        delta = sum_d dout * out                         (per row)
        p     = exp(s_masked - lse)
        dv_j  = p^T . dout ;  dp = dout . v_j^T
        ds    = p * (dp - delta) * sm_scale
        dq   += ds . k_j ;  dk_j = ds^T . q

    GQA folds the query-group axis into the einsums (dk/dv sum over the
    group); masked entries have s = NEG_INF so p underflows to exactly 0.
    """
    B, Hq, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    sm_scale = hd ** -0.5
    qf = q.reshape(B, Hkv, G, S, hd).astype(jnp.float32)
    doutf = dout.reshape(B, Hkv, G, S, hd).astype(jnp.float32)
    outf = out.reshape(B, Hkv, G, S, hd).astype(jnp.float32)
    lsef = lse.reshape(B, Hkv, G, S)
    delta = jnp.sum(doutf * outf, axis=-1)               # (B,Hkv,G,S)

    bk = min(block_k, T)
    pad = (-T) % bk
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_k = (T + pad) // bk
    kb = jnp.moveaxis(kf.reshape(B, Hkv, n_k, bk, hd), 2, 0)  # (nk,B,Hkv,bk,hd)
    vb = jnp.moveaxis(vf.reshape(B, Hkv, n_k, bk, hd), 2, 0)
    qpos = jnp.arange(S)

    def step(dq, xs):
        kblk, vblk, j = xs
        s = jnp.einsum("bkgsh,bkth->bkgst", qf, kblk) * sm_scale
        kpos = j * bk + jnp.arange(bk)
        mask = kpos[None, :] < T                         # k-padding
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lsef[..., None])                 # (B,Hkv,G,S,bk)
        dv = jnp.einsum("bkgst,bkgsh->bkth", p, doutf)
        dp = jnp.einsum("bkgsh,bkth->bkgst", doutf, vblk)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bkgst,bkth->bkgsh", ds, kblk)
        dk = jnp.einsum("bkgst,bkgsh->bkth", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Hkv, G, S, hd), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(step, dq0, (kb, vb, jnp.arange(n_k)))
    dq = dq.reshape(B, Hq, S, hd).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 2).reshape(B, Hkv, T + pad, hd)[:, :, :T]
    dv = jnp.moveaxis(dv, 0, 2).reshape(B, Hkv, T + pad, hd)[:, :, :T]
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, causal, window, block_q, block_k,
                            interpret)
    return out


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, window, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, dout):
    q, k, v, out, lse = res
    return _streaming_attn_bwd(q, k, v, out, lse, dout, causal, window,
                               block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Hq, S, hd); k, v: (B, Hkv, T, hd).  Returns (B, Hq, S, hd).

    Differentiable: carries a ``jax.custom_vjp`` (streamed recompute
    backward, ``_streaming_attn_bwd``) so training steps run the Pallas
    forward unmodified.
    """
    return _flash(q, k, v, bool(causal),
                  None if window is None else int(window),
                  int(block_q), int(block_k), bool(interpret))
