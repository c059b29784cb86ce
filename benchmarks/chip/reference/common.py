"""Plain float32 building blocks shared by the reference models.

Everything here is straightforward ``jax.numpy`` in float32.  Matrix
products go through ``matmul``, which runs at ``Precision.HIGHEST`` (on a
TPU a float32 product otherwise runs in bfloat16 passes).  The one knob
is ``precision``:

  "fp32"  the reference: float32 operands, highest-precision products;
  "fp8"   the control: every product's operands rounded to float8 e4m3
          first (one step below the bfloat16 the configurations state),
          still accumulated in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("fp32", "fp8")


def _operand(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def matmul(spec: str, a, b, precision: str = "fp32"):
    """``einsum(spec, a, b)`` in float32 at the highest precision."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def rms_norm(x, w, eps: float):
    """RMSNorm with the weight stored as an offset from one: x / rms(x) *
    (1 + w).  (The published models store the scale itself, initialised
    to one; the two are the same function of the stored number + 1.)"""
    x = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * (1.0 + w.astype(jnp.float32))


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def log_softmax(logits):
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    return z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))


def next_token_ce(logits, tokens):
    """Mean cross-entropy of logits[:, t] predicting tokens[:, t + 1]."""
    logp = log_softmax(logits[:, :-1].astype(jnp.float32))
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def truncated_normal(key, shape, std):
    """Normal(0, std) cut at two standard deviations."""
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def seed_key(seed: int):
    """A PRNG key from any whole seed up to 2**62: the low 31 bits seed
    the key, the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % 2 ** 31)
    return jax.random.fold_in(key, seed // 2 ** 31)


def bigram_stream(n_seqs: int, seq_len: int, vocab: int, seed: int,
                  domain: int, noise: float = 0.15) -> np.ndarray:
    """Synthetic token streams: next = (a * t + b) % vocab with
    probability 1 - noise, else a uniform draw.  Each domain has its own
    (a, b) and random stream.  This is the workload's data definition:
    the federation's clients train on exactly these streams."""
    rng = np.random.default_rng(seed + 7919 * domain)
    a = 31 + 2 * domain
    b = 7 + domain
    toks = np.empty((n_seqs, seq_len), np.int32)
    toks[:, 0] = rng.integers(0, vocab, n_seqs)
    for t in range(1, seq_len):
        nxt = (a * toks[:, t - 1] + b) % vocab
        rand = rng.integers(0, vocab, n_seqs)
        use_rand = rng.random(n_seqs) < noise
        toks[:, t] = np.where(use_rand, rand, nxt)
    return toks
