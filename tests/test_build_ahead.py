"""The LM population builds round r+1's token batches while round r's
program runs.  Every round's step still receives that round's rows,
bitwise; the trained state is bitwise that of a population that builds
every batch on demand; ``batches_ahead`` / ``batches_on_demand`` count
the hits and misses; a restore at another round misses once, then hits;
and no build lies inside a ``dispatch`` span.

A K=2 ``LMClients`` at ``reduced()`` sizes, each round run by its own
``Federation.run(until=r + 1)`` call, as the chip benchmark drives it."""
import dataclasses
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import DML, FedAvg, Federation, LMClients, SparseDML
from repro.configs import get_reduced
from repro.core.populations import lm
from repro.data.synthetic import make_token_stream

K, B, S, SEED = 2, 2, 16, 3
ROUNDS = 5
STRATEGIES = {"dml": DML, "sparse-dml": lambda: SparseDML(k=8),
              "fedavg": FedAvg}
PUBLIC = {"dml": True, "sparse-dml": True, "fedavg": False}


class OnDemand(LMClients):
    """Drops the batches held for a round before it starts, so the round
    builds each one when it asks for it."""

    def begin_round(self, r):
        super().begin_round(r)
        self._held = {}


def _pop(cls=LMClients, rounds=ROUNDS):
    return cls(get_reduced("qwen3-4b"), n_clients=K, rounds=rounds,
               batch=B, seq=S, seed=SEED)


def _seeds(r):
    """(seed, domain) of round r's private streams, then its public one."""
    return ([(1000 * r + SEED, d) for d in range(K)],
            (1000 * (10_000 + r) + SEED, K))


def _rows(r):
    """Round r's private (K, B, S) and public (B // 2, S) rows, straight
    from ``make_token_stream``."""
    vocab = get_reduced("qwen3-4b").vocab_size
    private, (seed, domain) = _seeds(r)
    return (np.stack([make_token_stream(B, S + 1, vocab, seed=s,
                                        domain=d)[:, :S]
                      for s, d in private]),
            make_token_stream(B // 2, S + 1, vocab, seed=seed,
                              domain=domain)[:, :S])


def _record_steps(pop, fed):
    """Make the strategy's round step, then wrap every step in
    ``pop._steps`` as the benchmark harness does, keeping the tokens (and
    public rows) each call receives."""
    strat = fed.strategy
    if PUBLIC[strat.name]:
        pop._dml_step(strat.kl_weight, strat.sparse_k)
    else:
        pop._local_step()
    seen = []

    def wrap(step, fused):
        def wrapped(params, opts, tokens, *rest, **kw):
            seen.append((np.asarray(tokens),
                         np.asarray(rest[0]) if fused else None))
            return step(params, opts, tokens, *rest, **kw)
        return wrapped

    for key in list(pop._steps):
        pop._steps[key] = wrap(pop._steps[key], key[0] == "dml")
    return seen


@dataclasses.dataclass
class Run:
    pop: LMClients
    fed: Federation
    seen: list      # (tokens, public rows or None) each step received
    built: list     # (seed, domain) of each stream made


def _run(name, cls):
    pop = _pop(cls)
    fed = Federation(pop, STRATEGIES[name]())
    seen = _record_steps(pop, fed)
    built = []
    make = lm.make_token_stream

    def counted(*args, seed, domain, **kw):
        built.append((seed, domain))
        return make(*args, seed=seed, domain=domain, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lm, "make_token_stream", counted)
        for r in range(ROUNDS):
            fed.run(until=r + 1)
    return Run(pop, fed, seen, built)


@pytest.fixture(scope="module")
def runs():
    done = {}

    def get(name, cls=LMClients):
        if (name, cls) not in done:
            done[name, cls] = _run(name, cls)
        return done[name, cls]
    return get


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_each_round_steps_on_its_own_rows(runs, name):
    seen = runs(name).seen
    assert len(seen) == ROUNDS
    for r, (tokens, pub) in enumerate(seen):
        private, public = _rows(r)
        np.testing.assert_array_equal(tokens, private)
        if PUBLIC[name]:
            np.testing.assert_array_equal(pub, public)


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_trains_as_when_built_on_demand(runs, name):
    ahead, on_demand = runs(name), runs(name, OnDemand)
    _assert_tree_equal(ahead.pop.client_params, on_demand.pop.client_params)
    _assert_tree_equal(ahead.pop.client_opts, on_demand.pop.client_opts)
    assert [(rl.client_loss, rl.kl_loss) for rl in ahead.fed.history.rounds] \
        == [(rl.client_loss, rl.kl_loss)
            for rl in on_demand.fed.history.rounds]
    kinds = 2 if PUBLIC[name] else 1
    assert (on_demand.pop.batches_ahead,
            on_demand.pop.batches_on_demand) == (0, kinds * ROUNDS)


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_counts_batches_ahead_and_on_demand(runs, name):
    run = runs(name)
    kinds = 2 if PUBLIC[name] else 1
    assert (run.pop.batches_ahead, run.pop.batches_on_demand) == \
        (kinds * (ROUNDS - 1), kinds)
    # each round's streams are made once, and none for a round past the
    # last: nothing is left held
    want = []
    for r in range(ROUNDS):
        private, public = _seeds(r)
        want += private + ([public] if PUBLIC[name] else [])
    assert sorted(run.built) == sorted(want)
    assert run.pop._held == {}


def test_restore_at_another_round_misses_once(runs, tmp_path):
    pop = _pop()
    fed = Federation(pop, DML())
    for r in range(2):
        fed.run(until=r + 1)
    path = str(tmp_path / "state")
    fed.save_state(path)
    fed.run(until=4)
    assert set(pop._held) == {(4, "private"), (4, "public")}
    fed.restore_state(path)
    assert fed.round == 2 and pop._held == {}
    ahead, on_demand = pop.batches_ahead, pop.batches_on_demand
    fed.run(until=3)
    assert (pop.batches_ahead - ahead, pop.batches_on_demand - on_demand) \
        == (0, 2)
    for r in range(3, ROUNDS):
        fed.run(until=r + 1)
    assert (pop.batches_ahead - ahead, pop.batches_on_demand - on_demand) \
        == (2 * (ROUNDS - 3), 2)
    full = runs("dml")
    _assert_tree_equal(full.pop.client_params, pop.client_params)
    _assert_tree_equal(full.pop.client_opts, pop.client_opts)
    assert [rl.client_loss for rl in full.fed.history.rounds] == \
        [rl.client_loss for rl in fed.history.rounds]


def test_no_build_inside_a_dispatch(tmp_path):
    fed = Federation(_pop(rounds=3), DML())
    with jax.profiler.trace(str(tmp_path)):
        for r in range(3):
            fed.run(until=r + 1)
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    spans = {"batch build": [], "dispatch": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    assert len(spans["batch build"]) == 6 and len(spans["dispatch"]) == 3
    assert not any(d0 < b1 and b0 < d1
                   for b0, b1 in spans["batch build"]
                   for d0, d1 in spans["dispatch"])
