"""Plain reference of a federation's first rounds of deep mutual learning.

K clients each hold a model of one family (``qwen3``, ``mamba2``).  In
round t every client c, with all clients' public-batch logits of round t
received as data (no gradient flows into them), descends

    L_c = CE(private_c) + CE(public) + w * KLD_avg_c          (paper Eq. 1)

    KLD_avg_c = mean over public positions of
                1/(K-1) sum_{j != c} KL(P_c || P_j)            (paper Eq. 2)

with P the softmax of the logits.  Under the sparse strategy
(``sparse-dml``, k) client j publishes at each public position only the
indices of its k most likely classes and their log-probabilities, and
client c takes P_j to be the distribution it rebuilds from them: the k
published classes at their probabilities, the rest of the mass spread
evenly over the other V - k classes.

The gradients are clipped as the traffic file's ``optimizer.clip_scope``
says: ``fleet`` (the default) to one global norm over the whole fleet,
``per_client`` each client's to its own norm.  They are fed to AdamW with
a linear warm-up then cosine schedule, bias-corrected moments, and
decoupled weight decay on the matrices only.

The client's batches are the workload's bigram streams (``common``):
client c's private rows of round r come from seed 1000 r + s in domain c,
the public rows from seed 1000 (10000 + r) + s in domain K.

Everything is float32 at the highest matmul precision; the control runs
the same code with ``precision="fp8"``.  Given ``devices``, client c's
weights, moments, gradient and starting weights live on device c mod n,
and each published payload is copied to the devices of the clients that
receive it; the clip's sums of squares are the only numbers that cross
between clients otherwise, and they are added on the host.

``fault`` plants one of the faults a federated step can have, so that the
comparison can be shown to fail on it:

  "half_batch"   the private CE over the first half of the rows only;
  "no_exchange"  every client receives its own public logits in place of
                 the others' (the payload exchange left out).

Where the sparse Eq. 2 departs from the strategy's definition: a top-k
that holds all of a position's mass leaves the tail a floor of the
smallest normal float32 in place of nothing, so that its logarithm stays
finite; and where classes tie at the k-th place, ``jax.lax.top_k`` takes
the lower indices.

This module imports nothing of the program under test.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .common import bigram_stream, log_softmax, next_token_ce, seed_key

FAULTS = (None, "half_batch", "no_exchange")
CLIP_SCOPES = ("fleet", "per_client")
STRATEGIES = ("dml", "sparse-dml")


def client_key(seed: int, client: int):
    return jax.random.fold_in(seed_key(seed), client)


def clip_scope(opt: dict) -> str:
    """How the traffic file's optimizer clips: ``fleet`` unless it says."""
    scope = opt.get("clip_scope", "fleet")
    if scope not in CLIP_SCOPES:
        raise ValueError(f"unknown clip scope {scope!r}")
    return scope


def private_rows(traffic: dict, vocab: int, seed: int, r: int) -> np.ndarray:
    """(K, B, S) private tokens of round r."""
    K, B, S = traffic["clients"], traffic["batch"], traffic["seq"]
    return np.stack([bigram_stream(B, S + 1, vocab, 1000 * r + seed, c)[:, :S]
                     for c in range(K)])


def public_rows(traffic: dict, vocab: int, seed: int, r: int) -> np.ndarray:
    """(B_pub, S) public tokens of round r, from the domain no client
    trains on."""
    K, S = traffic["clients"], traffic["seq"]
    return bigram_stream(traffic["public_batch"], S + 1, vocab,
                         1000 * (10_000 + r) + seed, K)[:, :S]


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then a cosine decay
    to ``final_frac`` of it at ``total_steps``."""
    lr, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    f = opt["final_frac"]
    return lr * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * prog)))


def leaf_names(tree) -> List[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in paths]


def leaf_norms(tree) -> jax.Array:
    """(n_leaves,) float32 norms, in ``leaf_names`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def kl_dense(live, received, weights):
    """live (N, V), received (K, N, V), weights (K,) ->
    mean_n sum_j w_j KL(softmax(live_n) || softmax(received_jn))."""
    lp = log_softmax(live)
    lq = log_softmax(received)
    kl = jnp.sum(jnp.exp(lp)[None] * (lp[None] - lq), axis=-1)    # (K, N)
    return jnp.mean(jnp.sum(weights[:, None] * kl, axis=0))


def top_k(logits, k: int):
    """What a client publishes under the sparse strategy, at each row of
    ``logits`` (N, V): the indices (N, k) of its k most likely classes and
    their log-probabilities (N, k)."""
    logp, idx = jax.lax.top_k(log_softmax(logits), k)
    return idx, logp


def rebuilt(idx, logp, V: int):
    """The log-distribution (..., V) a receiver rebuilds from a published
    top-k: the k classes at their log-probabilities, the mass left over
    spread evenly over the other V - k classes."""
    k = idx.shape[-1]
    rest = jnp.maximum(1.0 - jnp.sum(jnp.exp(logp), axis=-1),
                       np.finfo(np.float32).tiny)
    tail = jnp.broadcast_to(jnp.log(rest / (V - k))[..., None],
                            idx.shape[:-1] + (V,))
    return jnp.put_along_axis(tail, idx, logp, axis=-1, inplace=False)


def kl_sparse(live, received, weights):
    """live (N, V), received top-k sets (idx, logp), each (K, N, k),
    weights (K,) -> mean_n sum_j w_j KL(softmax(live_n) || rebuilt_jn)."""
    lp = log_softmax(live)
    lq = rebuilt(*received, live.shape[-1])
    kl = jnp.sum(jnp.exp(lp)[None] * (lp[None] - lq), axis=-1)    # (K, N)
    return jnp.mean(jnp.sum(weights[:, None] * kl, axis=0))


class Federation:
    """The reference federation: K clients of one family, on ``devices``
    (client c on device c mod n), or all on JAX's default device."""

    def __init__(self, family, cfg: dict, traffic: dict,
                 precision: str = "fp32", fault: Optional[str] = None,
                 devices: Sequence = ()):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        strategy = traffic["strategy"]
        if strategy["name"] not in STRATEGIES:
            raise ValueError(f"unknown strategy {strategy['name']!r}")
        self.family, self.cfg, self.traffic = family, cfg, traffic
        self.precision, self.fault = precision, fault
        self.devices = list(devices)
        self.K = traffic["clients"]
        self.V = family.dims(cfg)["V"]
        self.kl_weight = strategy.get("kl_weight", 1.0)
        self.opt = traffic["optimizer"]
        self.clip_scope = clip_scope(self.opt)
        sparse_k = strategy["k"] if strategy["name"] == "sparse-dml" else 0
        kl_term = kl_sparse if sparse_k else kl_dense
        fwd = family.forward

        def logits(p, toks):
            return fwd(p, cfg, toks, precision)

        def publish(p, toks):
            flat = logits(p, toks).reshape(-1, self.V)
            return top_k(flat, sparse_k) if sparse_k else flat

        def loss(p, priv, pub, received, weights):
            if fault == "half_batch":
                priv = priv[: priv.shape[0] // 2]
            priv_ce = next_token_ce(logits(p, priv), priv)
            pub_logits = logits(p, pub)
            pub_ce = next_token_ce(pub_logits, pub)
            kl = kl_term(pub_logits.reshape(-1, self.V), received, weights)
            return priv_ce + pub_ce + self.kl_weight * kl, \
                jnp.stack([priv_ce, pub_ce, kl])

        def grads(p, priv, pub, received, weights):
            (_, parts), g = jax.value_and_grad(loss, has_aux=True)(
                p, priv, pub, received, weights)
            return g, parts, jnp.sum(jnp.square(leaf_norms(g)))

        def adamw(p, m, v, g, scale, lr, t):
            o = self.opt
            b1, b2 = o["b1"], o["b2"]
            g = jax.tree.map(lambda x: x * scale, g)
            m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
            v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

            def upd(w, a, b):
                u = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) +
                                           o["eps"])
                if w.ndim >= 2:                      # decay matrices only
                    u = u + o["weight_decay"] * w
                return w - lr * u
            return jax.tree.map(upd, p, m, v), m, v, leaf_norms(g)

        self._publish = jax.jit(publish)
        self._grads = jax.jit(grads)
        self._adamw = jax.jit(adamw)
        self._init = jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.float32), family.init(k, cfg)))
        self._zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        self._change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))

    def _on(self, x, c: int):
        """``x`` on client c's device (as it is, without devices)."""
        if not self.devices:
            return x
        return jax.device_put(x, self.devices[c % len(self.devices)])

    def init(self, seed: int) -> List[dict]:
        """Every client's float32 weights, from the seed, on its device."""
        return [self._init(self._on(client_key(seed, c), c))
                for c in range(self.K)]

    def _received(self, published, c: int):
        """Client c's view of the round's payloads, on its device: every
        client's (its own weighted 0), or under the fault its own in
        every slot."""
        pick = [c] * self.K if self.fault == "no_exchange" else range(self.K)
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[self._on(published[j], c) for j in pick])

    def _clip_scales(self, sums: List[float]) -> List[float]:
        """Each client's clip factor from the clients' gradient sums of
        squares: one factor from their total, or each from its own."""
        clip = self.opt["clip_norm"]

        def scale(sq):
            return min(1.0, clip / max(math.sqrt(sq), 1e-9))
        if self.clip_scope == "fleet":
            return [scale(sum(sums))] * self.K
        return [scale(sq) for sq in sums]

    def run(self, seed: int, steps: int) -> Dict[str, object]:
        """The first ``steps`` rounds from the seed.  Returns

          losses       (steps, K, 3): private CE, public CE, KLD_avg
          grad_norms   (K, n_leaves): step 1's gradient as the optimizer
                       takes it (after clipping), per leaf
          change_norms (K, n_leaves): |weights after ``steps`` - before|
          leaves       the leaf names
        """
        K, opt = self.K, self.opt
        params = self.init(seed)
        moments = [(self._zeros(p), self._zeros(p)) for p in params]
        w_rows = (1.0 - np.eye(K, dtype=np.float32)) / max(K - 1, 1)
        losses, grad_norms = [], None
        for r in range(steps):
            priv = private_rows(self.traffic, self.V, seed, r)
            pub = public_rows(self.traffic, self.V, seed, r)
            published = [self._publish(params[c], pub) for c in range(K)]
            out = [self._grads(params[c], priv[c], pub,
                               self._received(published, c), w_rows[c])
                   for c in range(K)]
            del published
            scales = self._clip_scales([float(o[2]) for o in out])
            t = r + 1
            lr = learning_rate(opt, t)
            norms = []
            for c in range(K):
                params[c], m, v, gn = self._adamw(
                    params[c], *moments[c], out[c][0], scales[c], lr, t)
                moments[c] = (m, v)
                norms.append(np.asarray(gn))
            if r == 0:
                grad_norms = np.stack(norms)
            losses.append([np.asarray(o[1], np.float64) for o in out])
            del out
        start = self.init(seed)
        change = np.stack([np.asarray(self._change(params[c], start[c]))
                           for c in range(K)])
        return {"losses": np.asarray(losses), "grad_norms": grad_norms,
                "change_norms": change, "leaves": leaf_names(params[0])}
