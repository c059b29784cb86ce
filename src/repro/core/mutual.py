"""Deep mutual learning losses — the paper's Eq. 1 and Eq. 2.

    Loss_i    = ModelLoss_i + KLD_avg_i                       (Eq. 1)
    KLD_avg_i = 1/(K-1) * sum_{j != i} KL(P_i || P_j)         (Eq. 2)

Two gradient semantics:
  - ``mutual_kl_terms(live, fixed)``: the *federated* semantics — each client
    descends its own loss with the received predictions held constant
    (``fixed`` should be stop_gradient'ed).  Used inside train steps.
  - ``ops.mutual_kl``: forward-only fused kernel — the sharing/eval hot path
    (what actually gets computed on the public set and broadcast).

Categorical KL over the vocab for LLMs; Bernoulli KL for the paper's
sigmoid VisionNet head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _pair_mask(K: int, part_mask):
    """(K, K) pair weights for the Eq.-2 average under partial participation.

    ``part_mask`` is a (K,) 0/1 participation vector (None -> everyone).
    Row i is zeroed when client i sits the round out; column j is excluded
    from every average when client j shared nothing; the 1/(K-1) denominator
    shrinks to 1/(M-1) where M = number of participants.
    """
    eye = jnp.eye(K, dtype=jnp.float32)
    if part_mask is None:
        return (1.0 - eye) / max(K - 1, 1)
    m = jnp.asarray(part_mask, jnp.float32)
    pair = m[:, None] * m[None, :] * (1.0 - eye)
    denom = jnp.maximum(jnp.sum(m) - 1.0, 1.0)
    return pair / denom


def mutual_kl_terms_vs(live_logits, fixed_logits, pair_w,
                       temperature: float = 1.0):
    """Rectangular Eq. 2: (Kl, B, V) live x (Kg, B, V) fixed -> (Kl, B).

    out[i, b] = sum_j pair_w[i, j] * KL(softmax(live_i) || softmax(fixed_j))
    with explicit (Kl, Kg) pair weights.  This is the device-local shard of
    ``mutual_kl_terms``: rows are this device's clients, columns the
    all-gathered fleet (``distributed.make_sharded_dml_step``), and
    ``pair_w`` the matching rows of ``_pair_mask``.  The math IS the
    kernel oracle.
    """
    return ref.mutual_kl_pair(live_logits, fixed_logits, pair_w,
                              temperature=temperature)


def mutual_kl_terms(live_logits, fixed_logits, temperature: float = 1.0,
                    part_mask=None, impl=None):
    """Eq. 2 with the j-side fixed.  (K, B, V) x (K, B, V) -> (K, B).

    out[i, b] = 1/(K-1) sum_{j != i} KL(softmax(live_i) || softmax(fixed_j)).
    Pass ``fixed_logits = jax.lax.stop_gradient(live_logits)`` for the
    federated gradient semantics (others' predictions are received data).
    ``part_mask`` (K,) 0/1 drops non-participants from both sides of the
    average (partial participation: M <= K clients per round).

    ``impl`` (default: ``ops.get_impl()``): 'ref' keeps the plain-JAX graph
    (AD-derived gradients); 'interpret'/'pallas' route through the fused
    streaming kernel with its custom-VJP blocked backward
    (``ops.mutual_kl_pair``) — the Eq.-2 TRAINING hot path at vocab scale.
    """
    K = live_logits.shape[0]
    impl = impl or ops.get_impl()
    pair_w = _pair_mask(K, part_mask)
    if impl != "ref":
        return ops.mutual_kl_pair(live_logits, fixed_logits, pair_w,
                                  temperature=temperature, impl=impl)
    return mutual_kl_terms_vs(live_logits, fixed_logits, pair_w,
                              temperature=temperature)


def mutual_kl_loss(all_logits, temperature: float = 1.0,
                   stop_grad_others: bool = True, part_mask=None,
                   impl=None):
    """Per-client mean Eq.-2 loss from a live stacked logits tensor.

    all_logits: (K, B, V) (flatten (B, S) upstream).  Returns (K,) scalars.
    ``impl`` routes the Eq.-2 term through the fused streaming kernel
    (see ``mutual_kl_terms``).
    """
    fixed = jax.lax.stop_gradient(all_logits) if stop_grad_others else all_logits
    terms = mutual_kl_terms(all_logits, fixed, temperature,
                            part_mask=part_mask, impl=impl)
    return jnp.mean(terms, axis=-1)


def kl_to_received(live_logits, received_logits, temperature: float = 1.0):
    """Eq. 2 for ONE client against the predictions it received.

    live_logits: (B, V) — local, differentiable.
    received_logits: (J, B, V) — the J other participants' shared logits
    (treated as constants; stop_gradient applied here).

    Returns (B,) = 1/J * sum_j KL(softmax(live) || softmax(received_j)).
    The heterogeneous engine uses this: clients with different pytrees
    cannot be stacked, so each computes its own Eq.-2 term against the
    logits tensor that actually crossed the client boundary.
    """
    rec = jax.lax.stop_gradient(received_logits.astype(jnp.float32))
    lp_live = jax.nn.log_softmax(
        live_logits.astype(jnp.float32) / temperature, axis=-1)
    p_live = jnp.exp(lp_live)
    lp_rec = jax.nn.log_softmax(rec / temperature, axis=-1)  # (J,B,V)
    self_term = jnp.sum(p_live * lp_live, axis=-1)           # (B,)
    cross = jnp.einsum("bv,jbv->jb", p_live, lp_rec)         # (J,B)
    J = received_logits.shape[0]
    return self_term - jnp.sum(cross, axis=0) / max(J, 1)


def mutual_kl_eval(all_logits, temperature: float = 1.0, impl=None):
    """Forward-only Eq. 2 via the fused kernel (sharing/benchmark path)."""
    return ops.mutual_kl(all_logits, temperature=temperature, impl=impl)


# ---------------------------------------------------------------------------
# sparse (top-k) prediction sharing — beyond-paper bandwidth optimisation.
# Clients publish only (indices, log-probs) of their top-k tokens; the
# receiver treats the residual mass as uniform over the tail.  Cross-client
# bytes drop by V/k (e.g. 152064/64 ≈ 2400x) at a small KL approximation
# error.  See EXPERIMENTS.md §Perf.

def _distributed_topk(logp, k: int):
    """Two-stage top-k that never gathers the vocab axis.

    XLA's SPMD partitioning of sort/top_k REPLICATES every non-sort dim
    (measured: the full (K, B, V) logits all-gathered across pods).  We
    instead shard_map: local top-k per vocab shard, all-gather only the
    k·n_shards candidates (tiny), then a final local top-k.  Falls back to
    plain top_k when there is no mesh / no sharded vocab axis.
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding import current_mesh, get_rules, shard_map
    mesh = current_mesh()
    if mesh is None:
        return jax.lax.top_k(logp, k)
    rules = get_rules()
    vocab_ax = rules.get("vocab")
    client_ax = rules.get("client")
    axes = mesh.axis_names
    if isinstance(client_ax, tuple):      # e.g. ("clients", "pod")
        client_ax = next((a for a in client_ax if a in axes), None)
    vocab_ax = vocab_ax if vocab_ax in axes else None
    client_ax = client_ax if (client_ax in axes and
                              logp.shape[0] % mesh.shape[client_ax] == 0) \
        else None
    if vocab_ax is None or logp.shape[-1] % mesh.shape[vocab_ax] != 0:
        return jax.lax.top_k(logp, k)

    def local(lp):                             # (K_loc, B, V_loc)
        v, i = jax.lax.top_k(lp, min(k, lp.shape[-1]))
        i = i + jax.lax.axis_index(vocab_ax) * lp.shape[-1]
        vg = jax.lax.all_gather(v, vocab_ax, axis=-1, tiled=True)
        ig = jax.lax.all_gather(i, vocab_ax, axis=-1, tiled=True)
        vv, sel = jax.lax.top_k(vg, k)
        return jnp.take_along_axis(ig, sel, axis=-1), vv

    spec_in = P(client_ax, *([None] * (logp.ndim - 2)), vocab_ax)
    spec_out = P(client_ax, *([None] * (logp.ndim - 1)))
    idx, vals = shard_map(local, mesh=mesh, in_specs=(spec_in,),
                          out_specs=(spec_out, spec_out))(logp)
    return vals, idx


def topk_predictions(logits, k: int, temperature: float = 1.0):
    """What a client publishes: (indices (..., k), log-probs (..., k))."""
    from repro.sharding import constrain
    lf = logits.astype(jnp.float32) / temperature
    logp = jax.nn.log_softmax(lf, axis=-1)
    vals, idx = _distributed_topk(logp, k)
    tail = (None,) * (logits.ndim - 1)
    return (constrain(idx, "client", *tail),
            constrain(vals, "client", *tail))


def sparse_mutual_kl_loss(live_logits, idx, logp_top,
                          temperature: float = 1.0, impl=None):
    """Eq. 2 against RECEIVED sparse predictions.

    live_logits: (K, B, V) — local, differentiable.
    idx, logp_top: (K, B, k) — received top-k sets (treated as constants).

    KL(P_i || ~P_j) with ~P_j = top-k of P_j + uniform tail:
        KL_ij = -H(P_i) - c_j (1 - s_ij) - sum_t p_i[idx_j,t] logp_j[t]
    where s_ij = sum_t p_i[idx_j,t] and c_j = log(residual_j / (V - k)).
    Returns (K,) per-client means over B.

    ``impl`` (default: ``ops.get_impl()``): 'ref' keeps the plain-JAX graph
    below with its explicit SPMD sharding constraints (AD-derived
    gradients); kernel impls route through the fused top-k-gather +
    streaming-softmax Pallas kernel (``ops.sparse_mutual_kl``) whose
    custom-VJP backward streams over vocab blocks — per-round FLOPs/HBM
    traffic then scale with k, not V.
    """
    K, B, V = live_logits.shape
    k = idx.shape[-1]
    impl = impl or ops.get_impl()
    idx = jax.lax.stop_gradient(idx)
    logp_top = jax.lax.stop_gradient(logp_top.astype(jnp.float32))
    if impl != "ref":
        pair_w = (1.0 - jnp.eye(K, dtype=jnp.float32)) / max(K - 1, 1)
        terms = ops.sparse_mutual_kl(live_logits, idx, logp_top, pair_w,
                                     temperature=temperature, impl=impl)
        return jnp.mean(terms, axis=-1)
    lp_live = jax.nn.log_softmax(
        live_logits.astype(jnp.float32) / temperature, axis=-1)
    p_live = jnp.exp(lp_live)                            # (K,B,V)
    neg_h = jnp.sum(p_live * lp_live, axis=-1)           # (K,B)

    residual = jnp.clip(1.0 - jnp.sum(jnp.exp(logp_top), axis=-1),
                        1e-9, 1.0)                       # (K,B)
    c = jnp.log(residual / max(V - k, 1))                # (K,B)

    # pairwise gather WITHOUT materialising a (K, K, B, V) operand: loop the
    # (small, static) j axis; each step gathers only (K, B, k) values.  The
    # broadcast of client j's indices must be re-constrained to the client
    # axis or SPMD un-shards K and all-gathers p_live across pods (measured:
    # 98 GiB/device — see EXPERIMENTS.md §Perf pick 3).
    from repro.sharding import constrain
    p_ats = []
    for j in range(K):
        idx_j = jnp.broadcast_to(idx[j][None], (K, B, k))
        idx_j = constrain(idx_j, "client", None, None)
        p_at_j = jnp.take_along_axis(p_live, idx_j, axis=-1)
        p_ats.append(constrain(p_at_j, "client", None, None))
    p_at = jnp.stack(p_ats, axis=1)                      # (i,j,B,k)
    p_at = constrain(p_at, "client", None, None, None)
    s = jnp.sum(p_at, axis=-1)                           # (i,j,B)
    cross_top = jnp.sum(p_at * logp_top[None], axis=-1)  # (i,j,B)
    kl = neg_h[:, None, :] - c[None] * (1.0 - s) - cross_top
    mask = (1.0 - jnp.eye(K))[:, :, None]
    terms = jnp.sum(kl * mask, axis=1) / max(K - 1, 1)   # (K,B)
    return jnp.mean(terms, axis=-1)


def sparse_kl_to_received(live_logits, idx, logp_top,
                          temperature: float = 1.0, impl=None):
    """Eq. 2 for ONE client against RECEIVED sparse (top-k) predictions.

    live_logits: (B, V) — local, differentiable.
    idx, logp_top: (J, B, k) — the J other participants' top-k sets
    (treated as constants; stop_gradient applied here).

    Same tail model as ``sparse_mutual_kl_loss`` (~P_j = top-k mass +
    uniform residual over the V-k tail):
        KL_j = -H(P_i) - c_j (1 - s_j) - sum_t p_i[idx_j,t] logp_j[t]
    with s_j = sum_t p_i[idx_j,t] and c_j = log(residual_j / (V - k)).
    Returns (B,) = 1/J * sum_j KL_j — the per-client form the
    heterogeneous engine descends (clients with different pytrees cannot
    be stacked, so each computes Eq. 2 against the sparse sets that
    actually crossed the client boundary).

    ``impl`` routes kernel impls through ``ops.sparse_mutual_kl`` with
    Kl = 1 and uniform 1/J weights — the fused gather+KL kernel.
    """
    J, B, k = idx.shape
    V = live_logits.shape[-1]
    impl = impl or ops.get_impl()
    idx = jax.lax.stop_gradient(idx)
    logp_top = jax.lax.stop_gradient(logp_top.astype(jnp.float32))
    if impl != "ref":
        pair_w = jnp.full((1, J), 1.0 / max(J, 1), jnp.float32)
        terms = ops.sparse_mutual_kl(live_logits[None], idx, logp_top,
                                     pair_w, temperature=temperature,
                                     impl=impl)
        return terms[0]
    lp_live = jax.nn.log_softmax(
        live_logits.astype(jnp.float32) / temperature, axis=-1)
    p_live = jnp.exp(lp_live)                            # (B,V)
    neg_h = jnp.sum(p_live * lp_live, axis=-1)           # (B,)
    residual = jnp.clip(1.0 - jnp.sum(jnp.exp(logp_top), axis=-1),
                        1e-9, 1.0)                       # (J,B)
    c = jnp.log(residual / max(V - k, 1))                # (J,B)
    p_at = jax.vmap(
        lambda ij: jnp.take_along_axis(p_live, ij, axis=-1))(idx)  # (J,B,k)
    s = jnp.sum(p_at, axis=-1)                           # (J,B)
    cross_top = jnp.sum(p_at * logp_top, axis=-1)        # (J,B)
    kl = neg_h[None] - c * (1.0 - s) - cross_top         # (J,B)
    return jnp.sum(kl, axis=0) / max(J, 1)


def sparse_share_bytes(n_clients: int, n_examples: int, k: int) -> int:
    """Per-round traffic of top-k sharing (int32 idx + fp32 logp, up+down)."""
    return 2 * n_clients * n_examples * k * 8


# ---------------------------------------------------------------------------
# Byzantine-robust Eq.-2 combiners — beyond-paper robustness leg.
# Plain DML averages the KL to every received prediction, so one
# confident-wrong (poisoned) payload pulls every honest client; the robust
# variants replace the mean with a coordinate-wise trimmed mean or median
# CONSENSUS TARGET over the received predictions and descend
# KL(P_i || target_i) instead.  Under no attack and t=0 the trimmed target
# is the plain mean of predictions (close to, but not identical with, the
# mean of KLs — KL is convex), so these are distinct Strategy variants
# ("trimmed-dml" / "median-dml"), not drop-in reparameterisations of DML.

_ABSENT = 1e9          # sort-key shift that pushes masked-out senders last


def robust_weighted_target(shared, recv_mask, mode: str, trim: int = 1):
    """Per-receiver robust consensus over received predictions.

    shared     (K, B) values shared by every client (Bernoulli probs, or
               any per-position scalar payload)
    recv_mask  (K_recv, K) 0/1 — row i selects the senders receiver i
               aggregates over (participants minus self)
    mode       'trimmed' (drop the ``trim`` largest and smallest values
               per position) or 'median'
    Returns (K_recv, B) targets.

    Trace-safe in the participant count: the number of live senders n_i
    is a traced scalar per row.  When n_i - 2*trim < 1 the trimmed mean
    FALLS BACK DETERMINISTICALLY to the untrimmed masked mean (trim
    effectively 0) — the degenerate-participation contract the tests pin.
    """
    if mode not in ("trimmed", "median"):
        raise ValueError(f"robust mode must be 'trimmed' or 'median', "
                         f"got {mode!r}")
    m = jnp.asarray(recv_mask, jnp.float32)            # (Kr, K)
    vals = shared[None, :, :] + (1.0 - m)[:, :, None] * _ABSENT
    s = jnp.sort(vals, axis=1)                         # (Kr, K, B) ascending
    K = shared.shape[0]
    n = jnp.sum(m, axis=1)[:, None, None]              # (Kr, 1, 1) live count
    ranks = jnp.arange(K, dtype=jnp.float32)[None, :, None]
    if mode == "median":
        lo = jnp.floor((n - 1.0) / 2.0)
        hi = jnp.floor(n / 2.0)
        w = 0.5 * ((ranks == lo).astype(jnp.float32) +
                   (ranks == hi).astype(jnp.float32))
        return jnp.sum(s * w, axis=1)
    t = jnp.asarray(float(trim), jnp.float32)
    t_eff = jnp.where(n - 2.0 * t >= 1.0, t, 0.0)      # deterministic fallback
    w = ((ranks >= t_eff) & (ranks < n - t_eff)).astype(jnp.float32)
    return jnp.sum(s * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1.0)


def robust_bernoulli_target(shared, part_mask, mode: str, trim: int = 1):
    """(K, B) shared Bernoulli probs -> (K, B) per-client robust targets
    (each client aggregates over the OTHER participants, as in Eq. 2)."""
    K = shared.shape[0]
    eye = jnp.eye(K, dtype=jnp.float32)
    pm = jnp.ones((K,), jnp.float32) if part_mask is None \
        else jnp.asarray(part_mask, jnp.float32)
    recv = pm[None, :] * (1.0 - eye)
    tgt = robust_weighted_target(shared, recv, mode, trim)
    return jnp.clip(tgt, 1e-6, 1.0 - 1e-6)


def bernoulli_kl_to_target(live_probs, target_probs):
    """Elementwise Bernoulli KL(live || target): (K, B) x (K, B) -> (K, B).
    The robust strategies descend this with the target held fixed."""
    pi = jnp.clip(live_probs.astype(jnp.float32), 1e-6, 1 - 1e-6)
    pj = jnp.clip(jax.lax.stop_gradient(
        target_probs.astype(jnp.float32)), 1e-6, 1 - 1e-6)
    return pi * jnp.log(pi / pj) + (1 - pi) * jnp.log((1 - pi) / (1 - pj))


def robust_categorical_target(received_logits, mode: str, trim: int = 1):
    """(J, B, V) received logits -> (B, V) robust consensus distribution.

    Static J (the hetero engine's per-client view): coordinate-wise
    trimmed mean or median over the J received softmax distributions,
    renormalised back onto the simplex.  J - 2*trim < 1 falls back to the
    untrimmed mean deterministically.
    """
    if mode not in ("trimmed", "median"):
        raise ValueError(f"robust mode must be 'trimmed' or 'median', "
                         f"got {mode!r}")
    probs = jax.nn.softmax(
        received_logits.astype(jnp.float32), axis=-1)   # (J,B,V)
    J = probs.shape[0]
    if mode == "median":
        tgt = jnp.median(probs, axis=0)
    else:
        t = trim if J - 2 * trim >= 1 else 0
        s = jnp.sort(probs, axis=0)
        tgt = jnp.mean(s[t:J - t or None], axis=0)
    tgt = jnp.clip(tgt, 1e-9, 1.0)
    return tgt / jnp.sum(tgt, axis=-1, keepdims=True)


def kl_to_robust_received(live_logits, received_logits, mode: str,
                          trim: int = 1, temperature: float = 1.0):
    """Robust Eq. 2 for ONE client: KL(P_live || robust-consensus of the
    received predictions).  live (B, V) x received (J, B, V) -> (B,).
    The consensus target is data (stop_gradient), like ``kl_to_received``.
    """
    rec = jax.lax.stop_gradient(
        received_logits.astype(jnp.float32) / temperature)
    tgt = jax.lax.stop_gradient(robust_categorical_target(rec, mode, trim))
    lp_live = jax.nn.log_softmax(
        live_logits.astype(jnp.float32) / temperature, axis=-1)
    p_live = jnp.exp(lp_live)
    return jnp.sum(p_live * (lp_live - jnp.log(tgt)), axis=-1)


# ---------------------------------------------------------------------------
# Bernoulli case (VisionNet sigmoid head — the paper's actual case study)

def bernoulli_mutual_terms_vs(live_probs, fixed_probs, pair_w):
    """Rectangular Bernoulli Eq. 2: (Kl, B) live x (Kg, B) fixed -> (Kl, B)
    with explicit (Kl, Kg) pair weights — the device-local shard of
    ``bernoulli_mutual_terms`` (rows = local clients, columns = the
    all-gathered fleet's shared predictions)."""
    pi = jnp.clip(live_probs.astype(jnp.float32), 1e-6, 1 - 1e-6)[:, None, :]
    pj = jnp.clip(fixed_probs.astype(jnp.float32), 1e-6, 1 - 1e-6)[None, :, :]
    kl = pi * jnp.log(pi / pj) + (1 - pi) * jnp.log((1 - pi) / (1 - pj))
    return jnp.sum(kl * pair_w[:, :, None], axis=1)         # (Kl,B)


def bernoulli_mutual_terms(live_probs, fixed_probs, part_mask=None):
    """Eq. 2 with the j-side fixed, Bernoulli case: (K,B) x (K,B) -> (K,B).

    out[i, b] = 1/(K-1) sum_{j != i} KL(Bern(live_i) || Bern(fixed_j)).
    Callers wanting the federated gradient semantics stop_gradient the
    fixed side (received predictions are data, not parameters).
    ``part_mask`` (K,) 0/1 drops non-participants from both sides of the
    average (partial participation: M <= K clients per round).
    """
    K = live_probs.shape[0]
    return bernoulli_mutual_terms_vs(live_probs, fixed_probs,
                                     _pair_mask(K, part_mask))


def bernoulli_mutual_loss(all_probs, stop_grad_others: bool = True,
                          fixed_probs=None, part_mask=None):
    """all_probs: (K, B) sigmoid outputs -> (K,) per-client Eq.-2 means.

    ``fixed_probs`` optionally supplies the received (j-side) predictions —
    e.g. dropout-free shared probabilities while ``all_probs`` is the live
    training-mode forward.  Defaults to ``all_probs`` itself.
    """
    fixed = all_probs if fixed_probs is None else fixed_probs
    if stop_grad_others:
        fixed = jax.lax.stop_gradient(fixed)
    return jnp.mean(bernoulli_mutual_terms(all_probs, fixed,
                                           part_mask=part_mask), axis=-1)


def bernoulli_mutual_eval(all_probs):
    return ref.bernoulli_mutual_kl(all_probs)
