"""Mesh-scale federated mutual learning — the paper's technique as a
first-class distributed-training feature.

Clients are a leading K axis on every param/opt leaf, sharded over the
``client`` logical axis (physically: the ``pod`` mesh axis in multi-pod
mode).  The per-client step is vmapped; cross-client interaction happens
ONLY in the Eq.-2 term, where the public-batch logits (K, B_pub*S, V) are
all-gathered over the client axis — bytes independent of model size, which
is the paper's bandwidth claim made literal on the mesh.

Provided steps (each individually jit/lower-able for the dry-run):
  - local_train_step:  vmapped per-client CE training on private shards
  - mutual_step:       Eq. 1 on the rotating public batch (DML sharing+update)
  - dml_train_step:    local + mutual fused (one program)
  - fedavg_sync:       all-reduce(params)/K over the client axis (baseline #1)
  - async_sync:        metric-weighted partial sync (baseline #2)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs.base import ModelConfig
from repro.core import stacking
from repro.core.async_fl import layer_schedule
from repro.core.mutual import (_pair_mask, mutual_kl_loss,
                               sparse_mutual_kl_loss, topk_predictions)
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, global_norm)
from repro.sharding import constrain

Params = Any


# ---------------------------------------------------------------------------
# init

def stacked_init(key, cfg: ModelConfig, n_clients: int) -> Params:
    return stacking.stacked_init(key, lambda k: tfm.init_model(k, cfg),
                                 n_clients)


def stacked_adamw_init(stacked_params: Params) -> Dict:
    """AdamW state over the stacked params; the scalar step is shared across
    clients (one LR schedule for the whole fleet)."""
    return adamw_init(stacked_params)


def stacked_logical_axes(cfg: ModelConfig) -> Params:
    ax = tfm.logical_axes(cfg)
    return jax.tree.map(
        lambda t: ("client",) + t, ax,
        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict))


# ---------------------------------------------------------------------------
# steps

def _cvmap(spmd_axis_name=None):
    """vmap over the client axis; ``spmd_axis_name`` pins the vmapped dim's
    sharding for every constraint inside (without it, SPMD may replicate
    per-client activations across pods — measured 1 GiB/layer of pod-axis
    K/V all-gathers in the mutual step)."""
    def wrap(fn):
        if spmd_axis_name:
            return jax.vmap(fn, spmd_axis_name=spmd_axis_name)
        return jax.vmap(fn)
    return wrap


def make_local_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                          remat: bool = True, unroll: bool = False,
                          spmd_client_axis=None, impl=None):
    """Vmapped private-shard CE step.

    batch: tokens (K, B, S_tok) [+ prefix (K, B, P, pd)].

    ``part_mask`` (K,) 0/1: absentees' losses are zeroed BEFORE the grad,
    so their private data contributes nothing — not even through the
    shared global-norm gradient clip — and their params/opt ride through
    unchanged (the same pre-grad weighting the fused DML step uses).
    """
    def step(stacked_params, opt_state, tokens, prefix=None,
             part_mask=None):
        def total_loss(sp):
            with jax.named_scope("private_loss"):
                losses, metrics = _cvmap(spmd_axis_name=spmd_client_axis)(
                    lambda p, t, pe: tfm.loss_fn(p, cfg, t, pe, remat=remat,
                                                 unroll=unroll, impl=impl)
                )(sp, tokens, prefix)
            pm = 1.0 if part_mask is None else jnp.asarray(part_mask,
                                                           jnp.float32)
            return jnp.sum(losses * pm), metrics
        (_, metrics), grads = jax.value_and_grad(total_loss, has_aux=True)(
            stacked_params)
        new_params, new_opt, om = _optimizer_update(
            stacked_params, opt_state, grads, opt_cfg, part_mask)
        return new_params, new_opt, {**metrics, **om}
    return step


def _mutual_term(flat, temperature, sparse_k, part_mask=None, impl=None):
    """Eq. 2 term: dense (full logits gathered) or sparse top-k sharing.

    ``impl`` routes both variants through the fused streaming kernels
    (``ops.mutual_kl_pair`` / ``ops.sparse_mutual_kl``) on kernel impls.
    Its operations, and their backward, carry the ``eq2`` scope.
    """
    with jax.named_scope("eq2"):
        if sparse_k:
            assert part_mask is None, \
                "sparse top-k sharing + partial participation not supported yet"
            idx, logp_top = topk_predictions(
                jax.lax.stop_gradient(flat), sparse_k, temperature)
            return sparse_mutual_kl_loss(flat, idx, logp_top, temperature,
                                         impl=impl)
        return mutual_kl_loss(flat, temperature, part_mask=part_mask,
                              impl=impl)


def make_mutual_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                     kl_weight: float = 1.0, temperature: float = 1.0,
                     remat: bool = True, ce_weight: float = 1.0,
                     unroll: bool = False, sparse_k: int = 0,
                     spmd_client_axis=None, impl=None):
    """Eq. 1 on the public batch: CE(public) + kl_weight * KLD_avg.

    public tokens: (B_pub, S_tok) — same data for every client (that is the
    point); per-client logits differ because params differ.

    ``part_mask`` (K,) 0/1 enables partial participation: absentees are
    masked out of the Eq.-2 average and their params/opt pass through
    unchanged (the AdamW schedule step is shared fleet-wide and still
    advances).
    """
    def step(stacked_params, opt_state, public_tokens, public_prefix=None,
             part_mask=None):
        def total_loss(sp):
            losses, flat = _public_forward(
                sp, cfg, public_tokens, public_prefix, remat, unroll, impl,
                spmd_client_axis)
            kl = _mutual_term(flat, temperature, sparse_k, part_mask,
                              impl=impl)  # (K,)
            pm = 1.0 if part_mask is None else jnp.asarray(part_mask,
                                                           jnp.float32)
            total = (ce_weight * jnp.sum(losses * pm)
                     + kl_weight * jnp.sum(kl))
            return total, {"public_ce": losses, "kld_avg": kl}
        (_, metrics), grads = jax.value_and_grad(total_loss, has_aux=True)(
            stacked_params)
        new_params, new_opt, om = _optimizer_update(
            stacked_params, opt_state, grads, opt_cfg, part_mask)
        return new_params, new_opt, {**metrics, **om}
    return step


def _public_forward(stacked_params, cfg, tokens, prefix, remat, unroll,
                    impl, spmd_client_axis):
    """Every client's CE on the shared public batch and its public logits
    flattened to (K, B_pub * S, V), under the ``public_logits`` scope."""
    with jax.named_scope("public_logits"):
        losses, fwd = _cvmap(spmd_axis_name=spmd_client_axis)(
            lambda p: _public_ce_and_logits(p, cfg, tokens, prefix, remat,
                                            unroll, impl))(stacked_params)
        K, B, S, V = fwd.shape
        flat = constrain(fwd.reshape(K, B * S, V), "client", None, "vocab")
    return losses, flat


def _public_ce_and_logits(params, cfg, tokens, prefix, remat, unroll=False,
                          impl=None):
    logits, _ = tfm.forward(params, cfg, tokens, prefix, remat=remat,
                            unroll=unroll, impl=impl)
    P = cfg.prefix_tokens or 0
    if P:
        pred, labels = logits[:, P - 1: -1], tokens
    else:
        pred, labels = logits[:, :-1], tokens[:, 1:]
    logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    # mutual KL acts on the token-position logits (prefix stripped)
    return ce, logits[:, P:] if P else logits


def _optimizer_update(params, opt_state, grads, opt_cfg, part_mask):
    """AdamW with its clip, under the ``optimizer`` scope; with
    ``part_mask``, absent clients keep their state."""
    with jax.named_scope("optimizer"):
        new_params, new_opt, om = adamw_update(params, grads, opt_state,
                                               opt_cfg)
        if part_mask is not None:
            new_params, new_opt = _mask_participation(
                params, opt_state, new_params, new_opt, part_mask)
    return new_params, new_opt, om


def _mask_participation(old_params, old_opt, new_params, new_opt, part_mask):
    """Absent clients keep params and AdamW moments; the (shared, scalar)
    schedule step keeps advancing."""
    params = stacking.client_lerp(old_params, new_params, part_mask)
    opt = {"mu": stacking.client_lerp(old_opt["mu"], new_opt["mu"], part_mask),
           "nu": stacking.client_lerp(old_opt["nu"], new_opt["nu"], part_mask),
           "step": new_opt["step"]}
    return params, opt


def make_dml_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                        kl_weight: float = 1.0, temperature: float = 1.0,
                        remat: bool = True, unroll: bool = False,
                        sparse_k: int = 0, spmd_client_axis=None,
                        impl=None):
    """One fused DML round-step: private CE + Eq. 1 on the public batch.

    ``part_mask`` (K,) 0/1 enables partial participation (see
    ``make_mutual_step``).  ``impl`` is the kernel implementation the
    population resolved at construction — threaded into BOTH the mixer
    forward (``tfm.loss_fn``; the attention/SSD kernels carry custom VJPs,
    so the same impl runs forward and backward) and the Eq.-2 term, never
    read from ambient state inside the jitted step."""
    def step(stacked_params, opt_state, tokens, public_tokens,
             prefix=None, public_prefix=None, part_mask=None):
        def total_loss(sp):
            with jax.named_scope("private_loss"):
                priv, _ = _cvmap(spmd_axis_name=spmd_client_axis)(
                    lambda p, t, pe: tfm.loss_fn(p, cfg, t, pe, remat=remat,
                                                 unroll=unroll, impl=impl)
                )(sp, tokens, prefix)
            ce_pub, flat = _public_forward(
                sp, cfg, public_tokens, public_prefix, remat, unroll, impl,
                spmd_client_axis)
            kl = _mutual_term(flat, temperature, sparse_k, part_mask,
                              impl=impl)
            w = 1.0 if part_mask is None else jnp.asarray(part_mask,
                                                          jnp.float32)
            total = (jnp.sum(priv * w) + jnp.sum(ce_pub * w)
                     + kl_weight * jnp.sum(kl))
            return total, {"private_loss": priv, "public_ce": ce_pub,
                           "kld_avg": kl}
        (_, metrics), grads = jax.value_and_grad(total_loss, has_aux=True)(
            stacked_params)
        new_params, new_opt, om = _optimizer_update(
            stacked_params, opt_state, grads, opt_cfg, part_mask)
        return new_params, new_opt, {**metrics, **om}
    return step


def make_sharded_dml_step(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh,
                          n_clients: int, kl_weight: float = 1.0,
                          temperature: float = 1.0, remat: bool = True,
                          unroll: bool = False, impl: Optional[str] = None):
    """``make_dml_train_step`` device-sharded over a ``clients`` mesh axis.

    Each device owns a contiguous block of whole clients: device d holds
    clients d*K_loc .. (d+1)*K_loc - 1 with K_loc = ceil(K / n_devices)
    (``sharded_client_layout``).  Where n_devices does not divide K, the
    fleet is padded with wrapped copies of real clients whose updates are
    masked out and dropped.  The stacked state keeps that blocked layout
    as it is stored (``client_sharding``), so params and optimizer
    moments never leave their device; private-shard CE runs
    collective-free, and the ONLY cross-device traffic is one all-gather
    of the public-batch logits (K_loc, B_pub*S, V) feeding the Eq.-2 term
    — the paper's communication frontier as real collective traffic
    (``comm_bytes``'s ``dml_round`` simulates exactly these bytes).

    Two deliberate deltas vs the unsharded step:
      - grad clipping is per client (``clip_norm`` applies to each client's
        own gradient) — the unsharded step's fleet-wide global norm would
        couple clients and need a second collective;
      - the Eq.-2 term goes through ``ops.mutual_kl_pair`` (``impl`` as in
        ``kernels.ops``), i.e. the fused streaming kernel + custom-VJP
        blocked backward on kernel impls.

    Prefix-conditioned archs (``cfg.prefix_tokens``) are not supported.
    Returns ``step(stacked_params, opt_state, tokens, public_tokens,
    part_mask=None)``; jit the result.
    """
    if cfg.prefix_tokens:
        raise ValueError("sharded DML step: prefix-conditioned archs are "
                         "not supported yet")
    n_dev = mesh.shape[stacking.CLIENT_AXIS]
    k_loc, k_pad = sharded_client_layout(n_clients, n_dev)
    spec = stacking.client_spec()
    opt_noclip = dataclasses.replace(opt_cfg, clip_norm=None)
    state_sharding = client_sharding(mesh, n_clients)

    def body(params, opt, tokens, public_tokens, pm_full):
        gids = (jax.lax.axis_index(stacking.CLIENT_AXIS) * k_loc
                + jnp.arange(k_loc))
        pm_loc = jnp.take(pm_full, gids)
        pair_w = jnp.take(_pair_mask(k_pad, pm_full), gids, axis=0)

        def total_loss(sp):
            with jax.named_scope("private_loss"):
                priv, _ = jax.vmap(
                    lambda p, t: tfm.loss_fn(p, cfg, t, remat=remat,
                                             unroll=unroll,
                                             impl=impl))(sp, tokens)
            with jax.named_scope("public_logits"):
                ce_pub, fwd = jax.vmap(
                    lambda p: _public_ce_and_logits(p, cfg, public_tokens,
                                                    None, remat, unroll,
                                                    impl))(sp)
                K_l, B, S, V = fwd.shape
                flat = fwd.reshape(K_l, B * S, V)
            with jax.named_scope("eq2"):
                # blocked layout: the tiled gather is already in client
                # order
                gathered = jax.lax.all_gather(
                    jax.lax.stop_gradient(flat), stacking.CLIENT_AXIS,
                    axis=0, tiled=True)
                kl = jnp.mean(ops.mutual_kl_pair(
                    flat, gathered, pair_w, temperature=temperature,
                    impl=impl), axis=-1)                      # (K_loc,)
            total = (jnp.sum(priv * pm_loc) + jnp.sum(ce_pub * pm_loc)
                     + kl_weight * jnp.sum(kl))
            return total, {"private_loss": priv, "public_ce": ce_pub,
                           "kld_avg": kl}

        (_, metrics), grads = jax.value_and_grad(total_loss, has_aux=True)(
            params)
        with jax.named_scope("optimizer"):
            if opt_cfg.clip_norm is not None:
                grads, gnorm = jax.vmap(
                    lambda g: clip_by_global_norm(g, opt_cfg.clip_norm))(
                        grads)
            else:
                gnorm = jax.vmap(global_norm)(grads)
        new_params, new_opt, om = _optimizer_update(params, opt, grads,
                                                    opt_noclip, pm_loc)
        return new_params, new_opt, {**metrics, "grad_norm": gnorm,
                                     "lr": om["lr"]}

    opt_spec = {"mu": spec, "nu": spec, "step": P()}
    met_spec = {"private_loss": spec, "public_ce": spec, "kld_avg": spec,
                "grad_norm": spec, "lr": P()}
    run = sharding.shard_map(
        body, mesh,
        in_specs=(spec, opt_spec, spec, P(), P()),
        out_specs=(spec, opt_spec, met_spec))

    wrap = np.arange(k_pad) % n_clients

    def pad(tree):
        if k_pad == n_clients:
            return tree
        return jax.tree.map(lambda t: jnp.take(t, wrap, axis=0), tree)

    def unpad(tree):
        return jax.tree.map(lambda t: jax.lax.with_sharding_constraint(
            t[:n_clients], state_sharding), tree)

    def step(stacked_params, opt_state, tokens, public_tokens,
             part_mask=None):
        pm = jnp.ones((n_clients,), jnp.float32) if part_mask is None \
            else jnp.asarray(part_mask, jnp.float32)
        pm_pad = jnp.zeros((k_pad,), jnp.float32).at[:n_clients].set(pm)
        new_p, new_o, met = run(
            pad(stacked_params),
            {"mu": pad(opt_state["mu"]), "nu": pad(opt_state["nu"]),
             "step": opt_state["step"]},
            pad(tokens), public_tokens, pm_pad)
        met = {k: (unpad(v) if k != "lr" else v) for k, v in met.items()}
        return unpad(new_p), \
            {"mu": unpad(new_o["mu"]), "nu": unpad(new_o["nu"]),
             "step": new_o["step"]}, met

    return step


def sharded_client_layout(n_clients: int, n_devices: int):
    """(K_loc, K_pad) of ``make_sharded_dml_step``'s blocked layout:
    K_loc = ceil(K / n_devices) clients per device, K_pad = n_devices *
    K_loc slots in all (the last K_pad - K are masked wrapped copies)."""
    k_loc = -(-n_clients // n_devices)
    return k_loc, n_devices * k_loc


def client_sharding(mesh, n_clients: int):
    """Where ``make_sharded_dml_step`` keeps a K-stacked state leaf: one
    block of clients per device when n_devices divides K, replicated
    otherwise (an uneven K cannot be split into equal blocks)."""
    n_dev = mesh.shape[stacking.CLIENT_AXIS]
    spec = stacking.client_spec() if n_clients % n_dev == 0 else P()
    return jax.sharding.NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# weight-sharing baselines on the client axis

def fedavg_sync(stacked_params: Params, part_mask=None) -> Params:
    """All-reduce(params)/K over the client axis (vanilla FL round).

    With ``part_mask`` (K,) 0/1, only participants are averaged and only
    participants receive the aggregate back (absentees are offline)."""
    if part_mask is None:
        def avg(p):
            m = jnp.mean(p.astype(jnp.float32), axis=0, keepdims=True)
            return jnp.broadcast_to(m, p.shape).astype(p.dtype)
        return jax.tree.map(avg, stacked_params)
    from repro.core.fedavg import weighted_average_weights
    avg = weighted_average_weights(stacked_params, part_mask)
    return stacking.client_lerp(stacked_params, avg, part_mask)


def transformer_shallow_mask(cfg: ModelConfig, stacked_params: Params):
    """Float lerp-mask: embed/projector + first half of the periods are
    'shallow' (synced every round); the rest is 'deep'."""
    half = cfg.n_periods // 2

    def mask_like(path, p):
        names = [str(getattr(q, "key", getattr(q, "name", q))) for q in path]
        if "periods" in names:
            per = jnp.arange(cfg.n_periods, dtype=jnp.float32) < half
            return per.reshape((1, cfg.n_periods) + (1,) * (p.ndim - 2))
        if "embed" in names or "projector" in names:
            return jnp.ones((1,) * p.ndim, jnp.float32)
        return jnp.zeros((1,) * p.ndim, jnp.float32)

    return jax.tree_util.tree_map_with_path(mask_like, stacked_params)


def async_sync(stacked_params: Params, scores, shallow_mask,
               round_idx: int, delta: int = 3, min_round: int = 5) -> Params:
    """Metric-weighted partial sync (async baseline) on the client axis."""
    layer = layer_schedule(round_idx, delta, min_round)
    w = jnp.asarray(scores, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-9)

    def sync(p, m):
        pf = p.astype(jnp.float32)
        wb = w.reshape((-1,) + (1,) * (p.ndim - 1))
        avg = jnp.broadcast_to(jnp.sum(pf * wb, axis=0, keepdims=True), p.shape)
        lerp = m if layer == "shallow" else 1.0 - m
        return (pf * (1 - lerp) + avg * lerp).astype(p.dtype)

    return jax.tree.map(sync, stacked_params, shallow_mask)


# ---------------------------------------------------------------------------
# communication accounting (analytic; HLO-parsed numbers live in benchmarks)

def comm_bytes(cfg: ModelConfig, n_clients: int, public_tokens: int,
               bytes_per_el: int = 2) -> Dict[str, int]:
    n = cfg.param_count()
    return {
        "fedavg_round": 2 * n_clients * n * bytes_per_el,
        "dml_round": 2 * n_clients * public_tokens * cfg.vocab_size * bytes_per_el,
        "ratio": (n / max(public_tokens * cfg.vocab_size, 1)),
    }
