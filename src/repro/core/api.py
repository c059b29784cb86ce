"""``Federation`` — the one session object behind every federated run.

The paper's core claim is that *what crosses the wire* (predictions vs
weights, Eq. 1/2 vs FedAvg/async) is a swappable choice with
accuracy/bandwidth/privacy consequences.  This module makes the choice a
constructor argument instead of a trainer class:

    Federation(population, strategy, participation=0)

composes a sharing **strategy** (``core.strategies``: DML / SparseDML /
FedAvg / AsyncWeights — the protocol + comm formula) with a client
**population** (``core.populations``: stacked VisionNet, heterogeneous
model registry, LLM-scale stacked steps — the models + execution
backend, single-device vmap or a ``clients`` mesh).  The session owns
everything the three legacy engines used to duplicate:

  - ONE participation sampler (``data.federated.sample_participants``,
    stateless in the round index — resume-safe),
  - ONE round loop (local_phase -> round_payload -> combine) over the
    population's shared ``FoldScheduler`` discipline,
  - ONE ``History``/``RoundLog`` shape and comm-bytes ledger,
  - ONE checkpoint schema (``save_state``/``restore_state`` through
    ``repro.checkpoint`` — files written by the legacy
    ``FederatedTrainer``/``HeteroTrainer`` restore unchanged),
  - ONE ``evaluate(split=...)`` entry point (held-out dataset for the
    vision population, common eval fold for hetero/LM).

``core.federated.FederatedTrainer`` and ``core.hetero.HeteroTrainer``
are thin back-compat shims over this class and reproduce their
pre-refactor results bitwise (tests/test_api.py holds params, scores
and comm accounting to exact equality).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

from repro import checkpoint
from repro.data.federated import sample_participants


@dataclass
class RoundLog:
    """One round's ledger entry (superset of the legacy engines' logs:
    ``layer`` is async-only, ``public_ce`` prediction-sharing-only)."""
    round: int
    client_loss: List[float]
    kl_loss: List[float]
    comm_bytes: int
    layer: Optional[str] = None
    participants: Optional[List[int]] = None      # None -> full participation
    public_ce: Optional[List[float]] = None


@dataclass
class History:
    """Session history shared by every strategy x population pairing."""
    rounds: List[RoundLog] = field(default_factory=list)
    client_test_acc: List[float] = field(default_factory=list)   # vision eval
    global_test_acc: float = 0.0                                 # vision eval
    client_eval_loss: List[float] = field(default_factory=list)  # lm eval
    total_comm_bytes: int = 0


class Federation:
    """One federated learning session: strategy x population x rounds.

    ``participation``: sample M <= K clients per round (0 -> all K);
    non-participants train nothing, share nothing, receive nothing, and
    comm costs scale with M.  The sampler is stateless in the round
    index, so a restored session samples exactly the same subsets.
    """

    def __init__(self, population, strategy, participation: int = 0):
        population.validate_strategy(strategy)
        self.population = population
        self.strategy = strategy
        self.participation = participation
        self.history = History()
        self.round = 0                     # next round to run

    # -- derived ----------------------------------------------------------
    @property
    def n_clients(self) -> int:
        return self.population.n_clients

    @property
    def rounds(self) -> int:
        return self.population.rounds

    @property
    def dispatch_log(self):
        return getattr(self.population, "dispatch_log", [])

    def participants(self, r: int) -> List[int]:
        """The M clients sampled for round r (stateless in r — resume-safe).
        Full participation returns all K."""
        return sample_participants(self.n_clients, self.participation,
                                   self.population.seed, r)

    # -- rounds -----------------------------------------------------------
    def run(self, until: int = 0) -> History:
        """Run rounds up to ``until`` (0 -> population.rounds).  Picks up
        from the round counter, so save_state/restore_state mid-run and a
        second ``run()`` continue exactly where the checkpoint left off."""
        stop = until or self.rounds
        for r in range(self.round, min(stop, self.rounds)):
            # the profiler's span of the whole round; the population's
            # spans inside it carry the same ``round`` stat (docs/API.md)
            with jax.profiler.TraceAnnotation("federated round", round=r):
                self._run_round(r)
        return self.history

    def _run_round(self, r: int) -> None:
        pop, strat = self.population, self.strategy
        pop.begin_round(r)
        part = self.participants(r)
        pm = pop.part_mask(part)
        local_losses = strat.local_phase(pop, r, part, pm)
        payload = strat.round_payload(pop, r, part)
        out = strat.combine(pop, r, part, pm, payload) or {}
        comm = strat.comm_bytes(pop, part, payload, out)
        K = self.n_clients
        full = len(part) == K
        self.history.total_comm_bytes += comm
        self.history.rounds.append(RoundLog(
            r,
            out.get("client_loss", local_losses or [0.0] * K),
            out.get("kl_loss", [0.0] * K),
            comm,
            layer=out.get("layer"),
            participants=part if (not full or
                                  pop.log_participants_always) else None,
            public_ce=out.get("public_ce")))
        self.round = r + 1

    # -- eval ----------------------------------------------------------------
    def evaluate(self, split=None) -> History:
        """Population-appropriate final evaluation.

        vision: ``split=(test_images, test_labels)`` — per-client accuracy
        on the unseen dataset (paper Table II) + the global model's.
        hetero / lm: ``split=None`` — per-client loss on the common
        held-out fold every client optimised in Eq. 1.
        """
        return self.population.evaluate(self.history, split)

    # -- checkpoint/resume -------------------------------------------------
    def save_state(self, path: str) -> None:
        """Full session state through ``repro.checkpoint`` — the population
        state (params/opt/PRNG/fold cursor) plus the session's round
        counter, comm ledger and history.  Schema-identical to the legacy
        trainers' ``save_state`` files."""
        meta = {
            **self.population.meta_dict(),
            "method": self.strategy.name,
            "round": self.round,
            "total_comm_bytes": self.history.total_comm_bytes,
            "rounds": [dataclasses.asdict(rl) for rl in self.history.rounds],
        }
        # stateful strategies (e.g. DPDML's accountant + noise key) ride in
        # the JSON meta so resume replays the identical noise/budget stream
        if hasattr(self.strategy, "state_dict"):
            meta["strategy_state"] = self.strategy.state_dict()
        checkpoint.save(path, self.population.state_dict(), meta)

    def export_for_serving(self, path: str) -> None:
        """Write the slim serving artifact: client params only (no
        optimiser moments, PRNG state or fold cursors — typically ~1/3
        the bytes of ``save_state``) plus the meta the serving engine
        needs to rebuild the config (``engine``/``arch``/``n_clients``).
        ``ServeEngine.from_checkpoint`` / ``launch.serve --ckpt`` read
        both this artifact and full ``save_state`` files."""
        state = self.population.state_dict()
        if "client_params" not in state:
            raise ValueError(
                f"population {self.population.engine_name!r} does not "
                "expose a stacked 'client_params' pytree; only the LM "
                "population is servable (hetero checkpoints one pytree "
                "per arch)")
        meta = {k: v for k, v in self.population.meta_dict().items()
                if k in ("engine", "arch", "n_clients")}
        meta["round"] = self.round
        checkpoint.save(path, {"client_params": state["client_params"]},
                        meta)

    def restore_state(self, path: str) -> None:
        """Load a ``save_state`` checkpoint — including files written by
        the pre-API ``FederatedTrainer``/``HeteroTrainer`` — into this
        session (must be constructed with the same config and data pool)."""
        state, meta = checkpoint.restore(path)
        method = meta.get("method", self.strategy.name)
        if method != self.strategy.name:
            raise ValueError(
                f"checkpoint strategy {method!r} != session strategy "
                f"{self.strategy.name!r}")
        self.population.check_meta(meta)
        if "strategy_state" in meta and hasattr(self.strategy,
                                                "load_state_dict"):
            self.strategy.load_state_dict(meta["strategy_state"])
        self.population.load_state_dict(state, meta)
        self.round = int(meta["round"])
        self.history = History(
            rounds=[RoundLog(**_round_kwargs(d))
                    for d in meta.get("rounds", [])],
            total_comm_bytes=int(meta.get("total_comm_bytes", 0)))


def _round_kwargs(d: Dict[str, Any]) -> Dict[str, Any]:
    """Accept round dicts from any schema generation (legacy hetero logs
    have no ``layer``; legacy federated logs no ``public_ce``; unknown
    future keys are dropped rather than crashing the restore)."""
    fields = {f.name for f in dataclasses.fields(RoundLog)}
    return {k: v for k, v in d.items() if k in fields}
