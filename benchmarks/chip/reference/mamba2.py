"""Plain float32 reference of the Mamba-2 language model (SSD mixer, no
MLP), written from the published description (Dao & Gu, "Transformers
are SSMs", 2024: the block of its Figure 6 and the chunked SSD algorithm
of its Listing 1).  It imports nothing of the program.

Per layer: x + out_proj(gated_norm(SSD(conv(in_proj(norm(x)))))), where
in_proj gives (z, xBC, dt), a causal depthwise convolution of width
``d_conv`` with bias and SiLU runs over xBC, dt = softplus(dt + dt_bias),
A = -exp(A_log), and

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = C_t h_t + D x_t,

followed by RMSNorm(y * silu(z)).  The embedding is tied to the LM head.

Departures from the published description, all of them the deployment
the configuration file states:

- Depth is the configuration's cut, and the embedding has the rows the
  configuration states (``vocab_size`` padded to
  ``pad_vocab_size_multiple``).
- Every RMSNorm stores its scale as an offset from one (``common.rms_norm``).
- The weights are random draws from the seed (``init``), not the
  checkpoint.

Parameters of one client (reference layout)::

    embed (V, d), final_norm (d,),
    layers[i]: norm (d,), in_proj (d, 2 di + 2 G N + nh),
               conv_w (d_conv, di + 2 G N), conv_b (di + 2 G N,),
               A_log (nh,), D (nh,), dt_bias (nh,), gate_norm (di,),
               out_proj (di, d)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (matmul, rms_norm, silu, softplus, truncated_normal)

# the configuration the CPU tests run, in a configuration file's schema:
# the program's registry ``reduced()`` preset of mamba2-780m, float32
TINY = {
    "reference": "mamba2",
    "program": {"registry": "mamba2-780m", "preset": "reduced"},
    "d_model": 128, "n_layer": 2, "vocab_size": 512, "d_state": 16,
    "d_conv": 4, "expand": 2, "headdim": 32, "ngroups": 1,
    "chunk_size": 32, "dt_min": 0.001, "dt_max": 0.1,
    "rms_norm_eps": 1e-5}


def dims(cfg: dict) -> dict:
    """Sizes by the published names; the embedding has ``vocab_size``
    rows rounded up to a multiple of ``pad_vocab_size_multiple``."""
    d = cfg["d_model"]
    pad = cfg.get("pad_vocab_size_multiple", 1)
    di = cfg["expand"] * d
    P = cfg["headdim"]
    return {"d": d, "di": di, "P": P, "nh": di // P, "N": cfg["d_state"],
            "G": cfg["ngroups"], "K": cfg["d_conv"],
            "chunk": cfg["chunk_size"], "L": cfg["n_layer"],
            "V": -(-cfg["vocab_size"] // pad) * pad,
            "eps": float(cfg["rms_norm_eps"]),
            "dt_min": float(cfg["dt_min"]), "dt_max": float(cfg["dt_max"])}


def init(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """One client's weights, drawn from ``key`` and rounded to ``dtype``:
    fan-in scaled truncated normals for the projections and the
    convolution, N(0, 0.02) for the embedding, A = -(1..nh), D = 1, and
    dt_bias = softplus^-1(dt) with dt log-uniform in [dt_min, dt_max]."""
    m = dims(cfg)
    d, di, nh, N, G, K = m["d"], m["di"], m["nh"], m["N"], m["G"], m["K"]
    conv_ch = di + 2 * G * N
    k_embed, k_layers = jax.random.split(key)
    p = {"embed": 0.02 * jax.random.normal(k_embed, (m["V"], d)),
         "final_norm": jnp.zeros((d,))}
    layers = []
    for lk in jax.random.split(k_layers, m["L"]):
        k = jax.random.split(lk, 4)
        lo, hi = jnp.log(m["dt_min"]), jnp.log(m["dt_max"])
        dt = jnp.exp(jax.random.uniform(k[2], (nh,)) * (hi - lo) + lo)
        layers.append({
            "norm": jnp.zeros((d,)),
            "in_proj": truncated_normal(k[0], (d, 2 * di + 2 * G * N + nh),
                                        d ** -0.5),
            "conv_w": truncated_normal(k[1], (K, conv_ch), K ** -0.5),
            "conv_b": jnp.zeros((conv_ch,)),
            "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
            "D": jnp.ones((nh,)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "gate_norm": jnp.zeros((di,)),
            "out_proj": truncated_normal(k[3], (di, d), di ** -0.5),
        })
    p["layers"] = layers
    return jax.tree.map(lambda x: x.astype(dtype), p)


def causal_conv(x, w, b):
    """Depthwise causal convolution over time: x (B, S, C), w (K, C);
    out[t] = sum_k w[k] x[t - (K - 1) + k] + b."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    S = x.shape[1]
    return sum(xp[:, k:k + S] * w[k] for k in range(K)) + b


def segsum(a):
    """a (..., T) -> (..., T, T) with out[i, j] = sum_{j < t <= i} a[t]
    below the diagonal and -inf above it."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    low = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    return jnp.where(low, seg, -jnp.inf)


def ssd(x, dt, A, Bm, Cm, chunk: int, precision):
    """The chunked SSD algorithm (Listing 1 of the Mamba-2 paper) with a
    zero initial state: x (B, S, H, P), dt (B, S, H), A (H,), Bm/Cm
    (B, S, G, N) -> y (B, S, H, P)."""
    Bb, S, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    c = S // chunk
    X = (x * dt[..., None]).reshape(Bb, c, chunk, H, P)
    a = (dt * A).reshape(Bb, c, chunk, H).transpose(0, 3, 1, 2)  # b h c l
    Bc = Bh.reshape(Bb, c, chunk, H, -1)
    Cc = Ch.reshape(Bb, c, chunk, H, -1)
    a_cs = jnp.cumsum(a, axis=-1)
    # 1. within each chunk
    Lmat = jnp.exp(segsum(a))                                    # b h c l s
    cb = matmul("bclhn,bcshn->bhcls", Cc, Bc, precision)
    y_diag = matmul("bhcls,bcshp->bclhp", cb * Lmat, X, precision)
    # 2. the state each chunk leaves behind
    decay = jnp.exp(a_cs[..., -1:] - a_cs)                       # b h c l
    states = matmul("bclhn,bclhp->bchpn",
                    Bc * decay.transpose(0, 2, 3, 1)[..., None], X, precision)
    # 3. the recurrence over chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = matmul("bhzc,bchpn->bzhpn", chunk_decay, states,
                    precision)[:, :-1]
    # 4. the entering state's contribution to each position
    y_off = matmul("bclhn,bchpn->bclhp",
                   Cc * jnp.exp(a_cs).transpose(0, 2, 3, 1)[..., None],
                   states, precision)
    return (y_diag + y_off).reshape(Bb, S, H, P)


def layer(x, lp, m, precision):
    di, nh, P, N, G = m["di"], m["nh"], m["P"], m["N"], m["G"]
    Bb, S, _ = x.shape
    h = rms_norm(x, lp["norm"], m["eps"])
    zxbcdt = matmul("bsd,de->bse", h, lp["in_proj"], precision)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * G * N]
    dt = zxbcdt[..., 2 * di + 2 * G * N:]
    xbc = silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs = xbc[..., :di].reshape(Bb, S, nh, P)
    Bm = xbc[..., di:di + G * N].reshape(Bb, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(Bb, S, G, N)
    dt = softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    y = ssd(xs, dt, A, Bm, Cm, m["chunk"], precision)
    y = y + lp["D"][None, None, :, None] * xs
    y = rms_norm(y.reshape(Bb, S, di) * silu(z), lp["gate_norm"], m["eps"])
    return x + matmul("bse,ed->bsd", y, lp["out_proj"], precision)


def forward(params, cfg: dict, tokens, precision: str = "fp32"):
    """tokens (B, S) int -> logits (B, S, V) float32, the LM head tied to
    the embedding.  Each layer is recomputed in the backward pass."""
    m = dims(cfg)
    if tokens.shape[1] % m["chunk"]:
        raise ValueError(f"sequence {tokens.shape[1]} is not a multiple of "
                         f"the SSD chunk {m['chunk']}")
    params = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x = params["embed"][tokens]
    step = jax.checkpoint(lambda x, lp: layer(x, lp, m, precision))
    for lp in params["layers"]:
        x = step(x, lp)
    x = rms_norm(x, params["final_norm"], m["eps"])
    return matmul("bsd,vd->bsv", x, params["embed"], precision)


# -- the program's parameter layout (data only: no program import) ---------

_LAYER_KEYS = {"norm": ("norm1",), "in_proj": ("mixer", "in_proj"),
               "conv_w": ("mixer", "conv_w"), "conv_b": ("mixer", "conv_b"),
               "A_log": ("mixer", "A_log"), "D": ("mixer", "D"),
               "dt_bias": ("mixer", "dt_bias"),
               "gate_norm": ("mixer", "norm"),
               "out_proj": ("mixer", "out_proj")}


def to_program(params: dict) -> dict:
    """Reference layout -> the program's stacked layout (one Mamba slot
    per period, layers on a leading axis)."""
    slot = {"mixer": {}}
    for name, path in _LAYER_KEYS.items():
        stacked = jnp.stack([lp[name] for lp in params["layers"]])
        if len(path) == 1:
            slot[path[0]] = stacked
        else:
            slot[path[0]][path[1]] = stacked
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "periods": {"slot0": slot}}


def from_program(tree: dict) -> dict:
    """The program's layout of one client -> reference layout."""
    slot = tree["periods"]["slot0"]
    layers = []
    for i in range(slot["norm1"].shape[0]):
        lp = {}
        for name, path in _LAYER_KEYS.items():
            t = slot[path[0]] if len(path) == 1 else slot[path[0]][path[1]]
            lp[name] = t[i]
        layers.append(lp)
    return {"embed": tree["embed"], "final_norm": tree["final_norm"],
            "layers": layers}


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations in matrix products, per client:
    in_proj, out_proj and the tied LM head (not the embedding lookup, the
    convolution or the per-head scalars)."""
    m = dims(cfg)
    d, di, nh, N, G = m["d"], m["di"], m["nh"], m["N"], m["G"]
    per_layer = d * (2 * di + 2 * G * N + nh) + di * d
    return m["L"] * per_layer + d * m["V"]


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Mamba-2 has no attention: its sequence mixing is counted in the
    SSD kernel's roofline, not here."""
    return 0.0
