"""LLM-scale stacked client population — the ``core.distributed`` step
factories behind the ``Federation`` session layer.

K same-arch clients live as a leading axis on every param/opt leaf
(``core.stacking``); one round is ONE fused jitted program:

  - dml / sparse-dml: ``distributed.make_dml_train_step`` — private CE +
    Eq. 1 on the round's public batch in a single update (``fused_dml``:
    the strategy's local phase and combine are one program here).  With a
    ``clients`` mesh, ``make_sharded_dml_step`` runs the same semantics
    device-sharded with ONE all-gather of public logits per round.
  - fedavg / async: ``make_local_train_step`` for the local phase, then
    ``fedavg_sync`` / ``async_sync`` on the stacked axis.

Private data is per-client synthetic bigram streams (one domain per
client — non-IID); the public batch is fresh every round ("dynamically
changing test dataset", paper §III.A).

The host builds round r+1's token batches while the chip runs round r:
after a round's program is enqueued and before its metrics are read back,
the batches the round used are built for the next round and held; the
next round takes them ready-made (``batches_ahead``) or, when none is
held for it, builds them then (``batches_on_demand``).  The rows depend
only on the round, the seed and the shapes, so where a batch is built
changes when the host works, never what the round trains on.

Under ``jax.profiler.trace`` a round writes the host spans ``batch
build``, ``dispatch`` and ``metrics sync``, each with the stat ``round``;
``compiled_programs`` counts the programs the round steps compiled
(docs/API.md, "Profiling a federation").
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed as D
from repro.core import stacking
from repro.core.async_fl import layer_schedule
from repro.core.populations.base import Population, broadcast_mask_counts
from repro.data.synthetic import make_token_stream
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.optim import AdamWConfig


class LMClients(Population):
    """K stacked same-arch LM clients on synthetic domain streams."""

    engine_name = "lm"
    supported = frozenset({"dml", "sparse-dml", "fedavg", "async"})
    fused_dml = True
    log_participants_always = True

    def __init__(self, cfg, n_clients: int = 2, rounds: int = 20,
                 batch: int = 4, seq: int = 64, lr: float = 1e-3,
                 seed: int = 0, mesh=None, kernel_impl: str = "auto"):
        self.cfg = cfg
        self.n_clients = n_clients
        self.rounds = rounds
        self.batch = batch
        self.seq = seq
        self.seed = seed
        self.mesh = mesh
        # kernel impl policy is resolved ONCE here ("auto" -> pallas on TPU,
        # ref elsewhere; see ops.resolve_impl) and threaded through every
        # step factory as a plain argument — the jitted hot path never
        # reads the ambient ops.get_impl() state
        self.impl = ops.resolve_impl(kernel_impl)
        self.opt_cfg = AdamWConfig(lr=lr, warmup=5, total_steps=rounds)
        key = jax.random.PRNGKey(seed)

        def init(k):
            params = D.stacked_init(k, cfg, n_clients)
            return params, D.stacked_adamw_init(params)

        if mesh is None:
            self.client_params, self.client_opts = init(key)
        else:
            # built where the sharded step keeps it: no device ever holds
            # the whole fleet's params and optimizer moments
            sh = D.client_sharding(mesh, n_clients)
            rep = NamedSharding(mesh, P())
            self.client_params, self.client_opts = jax.jit(
                init, out_shardings=(sh, {"mu": sh, "nu": sh, "step": rep})
            )(key)
        self._steps = {}
        self._jitted = []      # every jax.jit this population made
        self._last_metrics = {}
        # (round, "private" | "public") -> batch built during an earlier
        # round's program; at most one round's batches are held
        self._held = {}
        self._used = {}        # kind -> the last round that looked it up
        self.batches_ahead = 0
        self.batches_on_demand = 0

    def validate_strategy(self, strategy) -> None:
        super().validate_strategy(strategy)
        if getattr(strategy, "mutual_epochs", 1) != 1:
            raise ValueError(
                "the LM population fuses the whole round into one update "
                "program; mutual_epochs must be 1")
        if self.mesh is not None and strategy.name != "dml":
            raise ValueError(
                "mesh-sharded LM rounds support the dense dml strategy "
                f"only (make_sharded_dml_step), got {strategy.name!r}")

    # -- data -------------------------------------------------------------
    def _private_batch(self, r: int):
        """(K, B, S) tokens — each client has its own bigram domain."""
        return self._batch(r, "private")

    def _public_batch(self, r: int):
        """(B_pub, S) fresh public tokens from an unseen domain."""
        return self._batch(r, "public")

    def _batch(self, r: int, which: str):
        """Round r's ``which`` batch: the one built ahead, while an
        earlier round's program ran, if it is held; else built now."""
        self._used[which] = r
        batch = self._held.pop((r, which), None)
        if batch is not None:
            self.batches_ahead += 1
            return batch
        self.batches_on_demand += 1
        return self._build(r, which, ahead=False)

    def _build(self, r: int, which: str, ahead: bool):
        """Make round r's ``which`` batch from its seeds, inside the
        ``batch build`` span (stat ``ahead``: built during an earlier
        round's program, or on demand)."""
        b_pub = max(1, self.batch // 2)
        tokens = (self.n_clients * self.batch if which == "private"
                  else b_pub) * self.seq
        with jax.profiler.TraceAnnotation(
                "batch build", round=r, which=which, tokens=tokens,
                ahead=int(ahead)):
            if which == "public":
                return jnp.asarray(make_token_stream(
                    b_pub, self.seq + 1, self.cfg.vocab_size,
                    seed=1000 * (10_000 + r) + self.seed,
                    domain=self.n_clients)[:, :self.seq])
            return jnp.stack([
                jnp.asarray(make_token_stream(
                    self.batch, self.seq + 1, self.cfg.vocab_size,
                    seed=1000 * r + self.seed, domain=d)[:, :self.seq])
                for d in range(self.n_clients)])

    def _build_ahead(self, r: int) -> None:
        """Build round r+1's batches of the kinds round r used, for the
        next round to take; called once round r's program is enqueued, so
        the host builds while the chip runs it."""
        if r + 1 >= self.rounds:
            return
        self._held = {(r + 1, w): self._build(r + 1, w, ahead=True)
                      for w, used in self._used.items() if used == r}

    def _prefix(self, r: int, batch: int):
        """(B, P, pd) conditioning embeddings for modality-frontend archs
        (``cfg.prefix_tokens`` > 0); None otherwise."""
        if not self.cfg.prefix_tokens:
            return None
        rng = np.random.default_rng(r)
        return jnp.asarray(rng.normal(
            0, 1, (batch, self.cfg.prefix_tokens, self.cfg.prefix_dim)
        ).astype(np.float32))

    def _private_prefix(self, r: int):
        p = self._prefix(r, self.batch)
        if p is None:
            return None
        return jnp.broadcast_to(p[None], (self.n_clients,) + p.shape)

    # -- cached jitted steps ----------------------------------------------
    def _jit(self, fn):
        step = jax.jit(fn)
        self._jitted.append(step)
        return step

    @property
    def compiled_programs(self) -> int:
        """Programs compiled so far for this population's jitted steps:
        one per step and argument signature (shapes, dtypes, placement).
        It stays put over the rounds of a federation unless a round needs
        a new program."""
        # counted on the population's own list: ``_steps`` values may be
        # wrapped from outside
        return sum(step._cache_size() for step in self._jitted)

    def _dml_step(self, kl_weight: float, sparse_k: int):
        key = ("dml", kl_weight, sparse_k, self.mesh is not None, self.impl)
        if key not in self._steps:
            if self.mesh is not None:
                self._steps[key] = self._jit(D.make_sharded_dml_step(
                    self.cfg, self.opt_cfg, self.mesh, self.n_clients,
                    kl_weight=kl_weight, impl=self.impl))
            else:
                self._steps[key] = self._jit(D.make_dml_train_step(
                    self.cfg, self.opt_cfg, kl_weight=kl_weight,
                    sparse_k=sparse_k, impl=self.impl))
        return self._steps[key]

    def _local_step(self):
        key = ("local", self.impl)
        if key not in self._steps:
            self._steps[key] = self._jit(D.make_local_train_step(
                self.cfg, self.opt_cfg, impl=self.impl))
        return self._steps[key]

    def _dispatch(self, r: int, step, *args, **kwargs):
        """Call a round step inside the ``dispatch`` span: it returns once
        the program is enqueued (or compiled, on a new shape)."""
        with jax.profiler.TraceAnnotation("dispatch", round=r) as span:
            out = step(*args, **kwargs)
            if jax.profiler.TraceAnnotation.is_enabled():
                span.set_metadata(programs=self.compiled_programs)
        return out

    # -- strategy capabilities --------------------------------------------
    def local_phase(self, r: int, part: List[int], pm) -> List[float]:
        part_mask = jnp.asarray(pm) if len(part) < self.n_clients else None
        tokens = self._private_batch(r)
        prefix = self._private_prefix(r)
        self.client_params, self.client_opts, m = self._dispatch(
            r, self._local_step(), self.client_params, self.client_opts,
            tokens, prefix, part_mask)
        self._last_metrics = m
        self._build_ahead(r)
        # the first read of the round's results waits for the program
        with jax.profiler.TraceAnnotation("metrics sync", round=r):
            return [float(x) * w for x, w in zip(np.asarray(m["ce"]), pm)]

    def public_payload(self, r: int):
        return self._public_batch(r)

    def weights_payload(self, r: int):
        return None                      # no fold schedule to discipline

    def mutual_phase(self, r, part, pm, payload, kl_weight, mutual_epochs,
                     sparse_k: int = 0) -> dict:
        pub = payload.data
        if len(part) < 2:
            # nothing to share with: participants train locally only —
            # the same skip every other population applies when M < 2
            losses = self.local_phase(r, part, pm)
            return {"ran": False, "positions": 0, "client_loss": losses,
                    "kl_loss": [0.0] * self.n_clients}
        if sparse_k and len(part) < self.n_clients:
            raise ValueError("sparse top-k sharing + partial participation "
                             "is not supported by the fused LM step")
        part_mask = jnp.asarray(pm) if len(part) < self.n_clients else None
        tokens = self._private_batch(r)
        step = self._dml_step(kl_weight, sparse_k)
        prefixes = {} if self.mesh is not None else {
            "prefix": self._private_prefix(r),
            "public_prefix": self._prefix(10_000 + r, int(pub.shape[0]))}
        self.client_params, self.client_opts, m = self._dispatch(
            r, step, self.client_params, self.client_opts, tokens, pub,
            part_mask=part_mask, **prefixes)
        self._last_metrics = m
        self._build_ahead(r)
        # the first read of the round's results waits for the program
        with jax.profiler.TraceAnnotation("metrics sync", round=r):
            return {"ran": len(part) >= 2,
                    "positions": int(pub.shape[0]) * int(pub.shape[1]),
                    "client_loss": [float(x) for x in
                                    np.asarray(m["private_loss"])],
                    "public_ce": [float(x) for x in
                                  np.asarray(m["public_ce"])],
                    "kl_loss": [float(x) for x in np.asarray(m["kld_avg"])]}

    def fedavg_combine(self, part: List[int], pm) -> None:
        full = len(part) == self.n_clients
        self.client_params = D.fedavg_sync(
            self.client_params, None if full else jnp.asarray(pm))

    def async_combine(self, r, part, pm, delta, min_round, pub) -> str:
        layer = layer_schedule(r, delta, min_round)
        ce = np.asarray(self._last_metrics["ce"], np.float32)
        # weighting metric: inverse local loss, masked so absentees
        # contribute no weight and receive nothing back
        scores = (1.0 / (1.0 + np.maximum(ce, 0.0))) * pm
        synced = D.async_sync(self.client_params, jnp.asarray(scores),
                              self._shallow_mask(), r, delta, min_round)
        if len(part) < self.n_clients:
            synced = stacking.client_lerp(self.client_params, synced, pm)
        self.client_params = synced
        return layer

    def _shallow_mask(self):
        if not hasattr(self, "_shallow_mask_cache"):
            self._shallow_mask_cache = D.transformer_shallow_mask(
                self.cfg, self.client_params)
        return self._shallow_mask_cache

    def async_param_counts(self):
        return broadcast_mask_counts(self.client_params,
                                     self._shallow_mask(), self.n_clients)

    @property
    def bytes_per_position(self) -> int:
        return self.cfg.vocab_size * 4

    @property
    def params_per_client(self) -> int:
        total = sum(x.size for x in jax.tree.leaves(self.client_params))
        return int(total // self.n_clients)

    # -- eval / checkpoint -------------------------------------------------
    def evaluate(self, history, split=None):
        """Per-client CE on a fresh shared eval batch (domain K, never a
        training domain)."""
        if split is not None:
            raise ValueError(
                "the LM population evaluates on a fresh held-out synthetic "
                "batch; call evaluate() / evaluate(split=None)")
        toks = jnp.asarray(make_token_stream(
            self.batch, self.seq + 1, self.cfg.vocab_size,
            seed=777_000 + self.seed, domain=self.n_clients)[:, :self.seq])
        if "eval" not in self._steps:
            self._steps["eval"] = self._jit(jax.vmap(
                lambda p, t, pe: tfm.loss_fn(p, self.cfg, t, pe,
                                             impl=self.impl)[0],
                in_axes=(0, None, None)))
        losses = self._steps["eval"](self.client_params, toks,
                                     self._prefix(777_000, self.batch))
        history.client_eval_loss = [float(x) for x in np.asarray(losses)]
        return history

    def state_dict(self) -> dict:
        return {"client_params": self.client_params,
                "client_opts": self.client_opts}

    def meta_dict(self) -> dict:
        return {"engine": self.engine_name, "arch": self.cfg.name,
                "n_clients": self.n_clients, "n_rounds": self.rounds}

    def check_meta(self, meta: dict) -> None:
        if meta.get("arch") != self.cfg.name or \
                meta.get("n_clients") != self.n_clients:
            raise ValueError(
                f"checkpoint (arch={meta.get('arch')}, "
                f"K={meta.get('n_clients')}) != config "
                f"(arch={self.cfg.name}, K={self.n_clients})")

    def load_state_dict(self, state: dict, meta: dict) -> None:
        self.client_params = state["client_params"]
        self.client_opts = state["client_opts"]
        self._held = {}
