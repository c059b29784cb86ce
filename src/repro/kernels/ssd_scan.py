"""Mamba2 SSD chunked-scan Pallas kernel.

Grid (B, H, n_chunks) with the chunk index innermost + sequential; the
(P x N) SSM state lives in VMEM scratch and is carried across chunk
iterations — the TPU-native replacement for the paper-family's CUDA
selective-scan: sequential grid + VMEM-resident state instead of
warp-level scans.

Per chunk of length L (math identical to ref.ssd):
    y_intra[t] = sum_{s<=t} (C_t . B_s) e^{cs_t - cs_s} dt_s x_s
    y_inter[t] = e^{cs_t} * C_t . state_in
    state_out  = e^{cs_L} state_in + sum_t e^{cs_L - cs_t} dt_t B_t x_t^T

Inputs are pre-chunked by the wrapper to (B, H, nc, L, ...) so every block
is contiguous; B/C arrive group-expanded per head (the wrapper indexes the
group in the BlockSpec index_map, so no materialised repeat).

DIFFERENTIABLE: the forward additionally emits every chunk's ENTRY state
(B, H, nc, P, N), and ``ssd_scan`` carries a ``jax.custom_vjp`` whose
backward replays the chunks in reverse (``_ssd_chunk_bwd``): each chunk's
local VJP is recomputed from its saved boundary state via ``jax.vjp`` of
the plain-jnp chunk map, and the state cotangent flows chunk-to-chunk in
the carry — the chunked analogue of the flash recompute backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref,
                state_out_ref, states_in_ref, state_ref, *, L: int,
                n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    # record the chunk-ENTRY state before updating it: the backward's
    # boundary residual (one (P, N) tile per chunk, nothing per-token)
    states_in_ref[0, 0, 0] = state_ref[...]

    x = x_ref[0, 0, 0].astype(jnp.float32)               # (L, P)
    # dt arrives as a column (L, 1) and as a row (1, L): the TPU has no
    # in-kernel cumsum or 1-D vectors, so every per-token quantity stays
    # 2-D and the cumsums are triangular matmuls in both orientations
    dt = dt_ref[0, 0, 0].astype(jnp.float32)             # (L, 1)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)        # (1, L)
    a = a_ref[pl.program_id(1), 0]                       # scalar (SMEM)
    bmat = b_ref[0, 0, 0].astype(jnp.float32)            # (L, N)
    cmat = c_ref[0, 0, 0].astype(jnp.float32)            # (L, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = (row >= col).astype(jnp.float32)               # lower incl. diag
    hi = jax.lax.Precision.HIGHEST
    cs = jnp.dot(tri, dt * a, precision=hi)              # (L, 1) cumsum
    cs_row = jax.lax.dot_general(dt_row * a, tri, (((1,), (1,)), ((), ())),
                                 precision=hi)           # (1, L)
    cs_last = jnp.sum(dt_row * a, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())))  # (L,L)
    # clamp the (masked) upper triangle before exp: inf * 0 would be NaN
    decay = jnp.exp(jnp.minimum(cs - cs_row, 0.0))
    w = scores * decay * dt_row * tri
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))            # (L,P)

    # inter-chunk
    state = state_ref[...]                               # (P, N)
    y += jnp.exp(cs) * jax.lax.dot_general(
        cmat, state, (((1,), (1,)), ((), ())))           # (L,N)x(P,N)^T

    # state update
    tail = jnp.exp(cs_last - cs) * dt                    # (L, 1)
    state_ref[...] = jnp.exp(cs_last) * state + jax.lax.dot_general(
        x, bmat * tail, (((0,), (0,)), ((), ())))        # (P, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


def _ssd_forward(x, dt, A, B_mat, C_mat, chunk: int, interpret: bool):
    """Pallas SSD -> (y, final_state, chunk-entry states (B,H,nc,P,N))."""
    Bb, S, H, Pd = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        zf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, B_mat, C_mat = map(zf, (x, dt, B_mat, C_mat))
    Sp = S + pad
    nc = Sp // L

    # pre-chunk to (B, H, nc, L, ...) / (B, G, nc, L, N)
    xc = x.reshape(Bb, nc, L, H, Pd).transpose(0, 3, 1, 2, 4)
    dtc = dt.reshape(Bb, nc, L, H).transpose(0, 3, 1, 2)    # (B,H,nc,L)
    bc = B_mat.reshape(Bb, nc, L, G, N).transpose(0, 3, 1, 2, 4)
    cc = C_mat.reshape(Bb, nc, L, G, N).transpose(0, 3, 1, 2, 4)
    a_col = A.astype(jnp.float32).reshape(H, 1)

    kernel = functools.partial(_ssd_kernel, L=L, n_chunks=nc)
    y, state, states_in = pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, Pd), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, h, c: (b, h, c, 0, 0)),
            # the whole (H, 1) decay column as scalars: a one-head window
            # of it is below the TPU's (8, 128) tile, and a whole (H,)
            # vector stops being whole once the client vmap batches it
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, L, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, Pd), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Pd, N), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Pd, N), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, L, Pd), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, Pd, N), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, nc, Pd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Pd, N), jnp.float32)],
        name="ssd_scan_fwd",
        interpret=interpret,
    )(xc, dtc[..., None], dtc[..., None, :], a_col, bc, cc)
    y = y.transpose(0, 2, 3, 1, 4).reshape(Bb, Sp, H, Pd)[:, :S]
    return y, state, states_in


def _ssd_chunk(x_c, dt_c, b_c, c_c, a, state):
    """One chunk of the SSD map in plain jnp — ref.ssd's chunk_body with the
    head-group repeat folded in.  x_c (B,L,H,P), dt_c (B,L,H), b_c/c_c
    (B,L,G,N), a (H,), state (B,H,P,N) -> (y (B,L,H,P), state_out)."""
    L, H = x_c.shape[1], x_c.shape[2]
    rep = H // b_c.shape[2]
    Bc_ = jnp.repeat(b_c, rep, axis=2)
    Cc_ = jnp.repeat(c_c, rep, axis=2)
    cs_ = jnp.cumsum(dt_c * a, axis=1)                   # (B,L,H)
    scores = jnp.einsum("blhn,bshn->bhls", Cc_, Bc_)
    expo = cs_[:, :, None, :] - cs_[:, None, :, :]       # (B,t,s,H)
    decay = jnp.transpose(jnp.exp(jnp.minimum(expo, 0.0)), (0, 3, 1, 2))
    tri = jnp.tril(jnp.ones((L, L), jnp.float32))
    w = scores * decay * jnp.transpose(dt_c, (0, 2, 1))[:, :, None, :] * tri
    y = jnp.einsum("bhls,bshp->blhp", w, x_c)
    y += jnp.einsum("blhn,bhpn->blhp", Cc_, state) * jnp.exp(cs_)[..., None]
    tail = jnp.exp(cs_[:, -1:, :] - cs_) * dt_c          # (B,L,H)
    state = jnp.exp(cs_[:, -1, :])[:, :, None, None] * state + \
        jnp.einsum("blhn,blhp,blh->bhpn", Bc_, x_c, tail)
    return y, state


def _ssd_chunk_bwd(x, dt, A, B_mat, C_mat, states_in, dy, dstate_out,
                   chunk: int):
    """Backward of the chunked scan: reverse lax.scan over chunks, each
    chunk's VJP recomputed (``jax.vjp`` of ``_ssd_chunk``) from the
    forward's saved chunk-ENTRY state; the state cotangent is the carry and
    dA accumulates across chunks."""
    Bb, S, H, Pd = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    L = min(chunk, S)
    pad = (-S) % L
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = B_mat.astype(jnp.float32)
    cf = C_mat.astype(jnp.float32)
    af = A.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    if pad:
        zf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        xf, dtf, bf, cf, dyf = map(zf, (xf, dtf, bf, cf, dyf))
    Sp = S + pad
    nc = Sp // L
    # chunk axis leading: (nc, B, L, ...) / (nc, B, H, P, N)
    xc = jnp.moveaxis(xf.reshape(Bb, nc, L, H, Pd), 1, 0)
    dtc = jnp.moveaxis(dtf.reshape(Bb, nc, L, H), 1, 0)
    bc = jnp.moveaxis(bf.reshape(Bb, nc, L, G, N), 1, 0)
    cc = jnp.moveaxis(cf.reshape(Bb, nc, L, G, N), 1, 0)
    stc = jnp.moveaxis(states_in.astype(jnp.float32), 2, 0)
    dyc = jnp.moveaxis(dyf.reshape(Bb, nc, L, H, Pd), 1, 0)

    def step(carry, xs):
        dstate, da_acc = carry
        x_c, dt_c, b_c, c_c, st_in, dy_c = xs
        _, vjp = jax.vjp(_ssd_chunk, x_c, dt_c, b_c, c_c, af, st_in)
        dx_c, ddt_c, db_c, dc_c, da_c, dstate_prev = vjp((dy_c, dstate))
        return (dstate_prev, da_acc + da_c), (dx_c, ddt_c, db_c, dc_c)

    (_, da), (dxc, ddtc, dbc, dcc) = jax.lax.scan(
        step, (dstate_out.astype(jnp.float32), jnp.zeros_like(af)),
        (xc, dtc, bc, cc, stc, dyc), reverse=True)
    dx = jnp.moveaxis(dxc, 0, 1).reshape(Bb, Sp, H, Pd)[:, :S]
    ddt = jnp.moveaxis(ddtc, 0, 1).reshape(Bb, Sp, H)[:, :S]
    db = jnp.moveaxis(dbc, 0, 1).reshape(Bb, Sp, G, N)[:, :S]
    dc = jnp.moveaxis(dcc, 0, 1).reshape(Bb, Sp, G, N)[:, :S]
    return (dx.astype(x.dtype), ddt.astype(dt.dtype), da.astype(A.dtype),
            db.astype(B_mat.dtype), dc.astype(C_mat.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, B_mat, C_mat, chunk, interpret):
    y, state, _ = _ssd_forward(x, dt, A, B_mat, C_mat, chunk, interpret)
    return y, state


def _ssd_fwd(x, dt, A, B_mat, C_mat, chunk, interpret):
    y, state, states_in = _ssd_forward(x, dt, A, B_mat, C_mat, chunk,
                                       interpret)
    return (y, state), (x, dt, A, B_mat, C_mat, states_in)


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, A, B_mat, C_mat, states_in = res
    dy, dstate_out = cts
    return _ssd_chunk_bwd(x, dt, A, B_mat, C_mat, states_in, dy, dstate_out,
                          chunk)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B_mat, C_mat, *, chunk: int = 256,
             interpret: bool = False):
    """Pallas SSD.  Same contract as ref.ssd (zero initial state).

    x (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,G,N) -> y (B,S,H,P),
    final_state (B,H,P,N) fp32.  Differentiable in every tensor input
    (``jax.custom_vjp`` with the chunked reverse-scan backward).
    """
    return _ssd(x, dt, A, B_mat, C_mat, int(chunk), bool(interpret))
