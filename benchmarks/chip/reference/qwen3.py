"""Plain float32 reference of the Qwen3 decoder (dense, grouped-query
attention with per-head q/k RMSNorm, SwiGLU MLP), written from the
published description (Qwen3 technical report; the Hugging Face
``Qwen3ForCausalLM`` layout).  It imports nothing of the program.

Departures from the published description, all of them the deployment
the configuration file states:

- Depth and vocabulary are the configuration's cut (its ``reduced`` keys).
- ``tie_word_embeddings`` and ``rms_norm_eps`` are read from the
  configuration file, which states what is run.
- Every RMSNorm stores its scale as an offset from one (``common.rms_norm``).
- The weights are random draws from the seed (``init``), not the
  checkpoint.

Parameters of one client (reference layout)::

    embed (V, d), final_norm (d,), lm_head (d, V) unless tied,
    layers[i]: attn_norm (d,), w_qkv (d, H + 2 Hkv, hd), q_norm (hd,),
               k_norm (hd,), w_o (H, hd, d), mlp_norm (d,),
               w_gate (d, ff), w_up (d, ff), w_down (ff, d)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import matmul, rms_norm, silu, truncated_normal


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "tied": bool(cfg["tie_word_embeddings"])}


def init(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """One client's weights, drawn from ``key`` and rounded to ``dtype``:
    fan-in scaled truncated normals for the projections, N(0, 0.02) for
    the embedding, zero norm offsets."""
    m = dims(cfg)
    d, H, Hkv, hd, ff = m["d"], m["H"], m["Hkv"], m["hd"], m["ff"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    p = {"embed": 0.02 * jax.random.normal(k_embed, (m["V"], d)),
         "final_norm": jnp.zeros((d,))}
    if not m["tied"]:
        p["lm_head"] = truncated_normal(k_head, (d, m["V"]), d ** -0.5)
    layers = []
    for lk in jax.random.split(k_layers, m["L"]):
        k = jax.random.split(lk, 5)
        layers.append({
            "attn_norm": jnp.zeros((d,)),
            "w_qkv": truncated_normal(k[0], (d, H + 2 * Hkv, hd), d ** -0.5),
            "q_norm": jnp.zeros((hd,)),
            "k_norm": jnp.zeros((hd,)),
            "w_o": truncated_normal(k[1], (H, hd, d), (H * hd) ** -0.5),
            "mlp_norm": jnp.zeros((d,)),
            "w_gate": truncated_normal(k[2], (d, ff), d ** -0.5),
            "w_up": truncated_normal(k[3], (d, ff), d ** -0.5),
            "w_down": truncated_normal(k[4], (ff, d), ff ** -0.5),
        })
    p["layers"] = layers
    return jax.tree.map(lambda x: x.astype(dtype), p)


def rope(x, theta: float):
    """Rotary embedding on x (B, S, heads, hd), rotating the two halves of
    the head dimension (the published ``rotate_half`` convention)."""
    S, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, precision):
    """Causal grouped-query attention: q (B, S, H, hd), k/v (B, S, Hkv,
    hd); query head h reads key/value head h // (H / Hkv)."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    qg = q.reshape(B, S, k.shape[2], G, hd)
    s = matmul("bskgh,btkh->bkgst", qg, k, precision) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = matmul("bkgst,btkh->bskgh", p, v, precision)
    return o.reshape(B, S, H, hd)


def layer(x, lp, m, precision):
    H, Hkv = m["H"], m["Hkv"]
    h = rms_norm(x, lp["attn_norm"], m["eps"])
    qkv = matmul("bsd,dnh->bsnh", h, lp["w_qkv"], precision)
    q = rms_norm(qkv[:, :, :H], lp["q_norm"], m["eps"])
    k = rms_norm(qkv[:, :, H:H + Hkv], lp["k_norm"], m["eps"])
    v = qkv[:, :, H + Hkv:]
    o = attention(rope(q, m["theta"]), rope(k, m["theta"]), v, precision)
    x = x + matmul("bsnh,nhd->bsd", o, lp["w_o"], precision)
    h = rms_norm(x, lp["mlp_norm"], m["eps"])
    g = silu(matmul("bsd,df->bsf", h, lp["w_gate"], precision))
    u = matmul("bsd,df->bsf", h, lp["w_up"], precision)
    return x + matmul("bsf,fd->bsd", g * u, lp["w_down"], precision)


def forward(params, cfg: dict, tokens, precision: str = "fp32"):
    """tokens (B, S) int -> logits (B, S, V) float32.  Each layer is
    recomputed in the backward pass (``jax.checkpoint``) so that the
    float32 activations of a whole model fit one chip."""
    m = dims(cfg)
    params = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x = params["embed"][tokens]
    step = jax.checkpoint(lambda x, lp: layer(x, lp, m, precision))
    for lp in params["layers"]:
        x = step(x, lp)
    x = rms_norm(x, params["final_norm"], m["eps"])
    head = params["embed"].T if m["tied"] else params["lm_head"]
    return matmul("bsd,dv->bsv", x, head, precision)


# -- the program's parameter layout (data only: no program import) ---------

_LAYER_KEYS = {"attn_norm": ("norm1",), "w_qkv": ("mixer", "w_qkv"),
               "q_norm": ("mixer", "q_norm"), "k_norm": ("mixer", "k_norm"),
               "w_o": ("mixer", "w_o"), "mlp_norm": ("norm2",),
               "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_up"),
               "w_down": ("ffn", "w_down")}


def to_program(params: dict) -> dict:
    """Reference layout -> the program's stacked layout (one period of one
    attention + MLP slot; layers on a leading axis)."""
    slot = {"mixer": {}, "ffn": {}}
    for name, path in _LAYER_KEYS.items():
        stacked = jnp.stack([lp[name] for lp in params["layers"]])
        if len(path) == 1:
            slot[path[0]] = stacked
        else:
            slot[path[0]][path[1]] = stacked
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "periods": {"slot0": slot}}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def from_program(tree: dict) -> dict:
    """The program's layout of one client -> reference layout."""
    slot = tree["periods"]["slot0"]
    n = slot["norm1"].shape[0]
    layers = []
    for i in range(n):
        lp = {}
        for name, path in _LAYER_KEYS.items():
            t = slot[path[0]] if len(path) == 1 else slot[path[0]][path[1]]
            lp[name] = t[i]
        layers.append(lp)
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"],
           "layers": layers}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"]
    return out


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations in matrix products, per client:
    the projections and the LM head (not the embedding lookup)."""
    m = dims(cfg)
    d, H, Hkv, hd, ff = m["d"], m["H"], m["Hkv"], m["hd"], m["ff"]
    per_layer = d * (H + 2 * Hkv) * hd + H * hd * d + 3 * d * ff
    return m["L"] * per_layer + d * m["V"]


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs per token of the causal score and value products,
    averaged over a sequence of ``seq``: 2 products x 2 FLOPs x H x hd x
    (seq + 1) / 2 keys per query, per layer."""
    m = dims(cfg)
    return m["L"] * 4.0 * m["H"] * m["hd"] * (seq + 1) / 2.0
