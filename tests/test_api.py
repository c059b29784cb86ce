"""Unified Federation API: the strategy x population composition, bitwise
parity with the legacy trainers, checkpoint schema compatibility, sparse
top-k sharing end-to-end, and the stable public import surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DML, AsyncWeights, FedAvg, Federation, HeteroClients,
                       LMClients, SparseDML, VisionClients, get_strategy,
                       make_lm_pool)
from repro.configs import get_reduced
from repro.configs.visionnet import reduced
from repro.core.federated import FederatedConfig, FederatedTrainer
from repro.core.hetero import HeteroConfig, HeteroTrainer
from repro.data.synthetic import make_paper_datasets

ARCHS2 = ("qwen3-4b", "mamba2-780m")


# ---------------------------------------------------------------------------
# fixtures

@pytest.fixture(scope="module")
def vision_data():
    vn = reduced()
    return vn, make_paper_datasets(image_size=vn.image_size,
                                   n_train=300, n_test=80)


@pytest.fixture(scope="module")
def lm_pool():
    return make_lm_pool(160, 24, 512, seed=0)


def _hetero_pop(lm_pool, archs=ARCHS2, **kw):
    data, labels = lm_pool
    base = dict(rounds=2, local_epochs=1, batch_size=2, public_batch=2,
                seed=0)
    base.update(kw)
    return HeteroClients(archs, data, labels, **base)


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# public surface

def test_top_level_import_contract():
    """`repro` is a real package exporting the stable API surface."""
    import repro
    assert isinstance(repro.__version__, str) and repro.__version__
    assert "Federation" in repro.__all__
    assert repro.Federation is Federation
    assert repro.DML is DML and repro.SparseDML is SparseDML
    assert repro.FedAvg is FedAvg and repro.AsyncWeights is AsyncWeights
    assert repro.VisionClients is VisionClients
    assert {n for n in repro.__all__ if not n.startswith("_")} <= \
        set(dir(repro))
    with pytest.raises(AttributeError):
        repro.no_such_symbol


def test_strategy_registry_resolves_and_filters_knobs():
    s = get_strategy("sparse-dml", k=32, kl_weight=2.0, delta=9)  # delta
    assert isinstance(s, SparseDML)                               # ignored
    assert s.sparse_k == 32 and s.kl_weight == 2.0
    a = get_strategy("async", delta=7, k=99)
    assert isinstance(a, AsyncWeights) and a.delta == 7
    with pytest.raises(ValueError, match="unknown strategy"):
        get_strategy("gossip")


# ---------------------------------------------------------------------------
# parity: Federation == legacy shims == pre-refactor engines

@pytest.mark.parametrize("method,participation", [
    ("dml", 0), ("dml", 2), ("fedavg", 0), ("async", 2)])
def test_federation_bitwise_matches_legacy_trainer(vision_data, method,
                                                   participation):
    """A directly-composed Federation(VisionClients, strategy) reproduces
    the FederatedConfig-driven legacy trainer bitwise — params, opt,
    global model, comm ledger, history, dispatch structure."""
    vn, ((tr_x, tr_y), (te_x, te_y)) = vision_data
    fc = FederatedConfig(method=method, n_clients=3, rounds=2,
                         local_epochs=1, batch_size=16, min_round=0,
                         delta=2, participation=participation, seed=3)
    legacy = FederatedTrainer(vn, fc, tr_x, tr_y)
    legacy.run()
    legacy.evaluate(te_x, te_y)

    strategy = {"dml": lambda: DML(kl_weight=fc.kl_weight,
                                   mutual_epochs=fc.mutual_epochs),
                "fedavg": FedAvg,
                "async": lambda: AsyncWeights(delta=fc.delta,
                                              min_round=fc.min_round)
                }[method]()
    fed = Federation(
        VisionClients(vn, tr_x, tr_y, n_clients=3, rounds=2,
                      local_epochs=1, batch_size=16, seed=3),
        strategy, participation=participation)
    fed.run()
    fed.evaluate(split=(te_x, te_y))

    _assert_tree_equal(legacy.client_params, fed.population.client_params)
    _assert_tree_equal(legacy.client_opts, fed.population.client_opts)
    _assert_tree_equal(legacy.global_params, fed.population.global_params)
    assert legacy.history.total_comm_bytes == fed.history.total_comm_bytes
    for ra, rb in zip(legacy.history.rounds, fed.history.rounds):
        assert ra.client_loss == rb.client_loss
        assert ra.kl_loss == rb.kl_loss
        assert ra.comm_bytes == rb.comm_bytes
        assert ra.participants == rb.participants
        assert ra.layer == rb.layer
    assert legacy.history.client_test_acc == fed.history.client_test_acc
    assert legacy.history.global_test_acc == fed.history.global_test_acc
    assert [p for _, p in legacy.dispatch_log] == \
        [p for _, p in fed.dispatch_log]


def test_federation_matches_hetero_trainer(lm_pool):
    data, labels = lm_pool
    cfg = HeteroConfig(archs=ARCHS2, rounds=2, local_epochs=1,
                       batch_size=2, public_batch=2, participation=0,
                       seed=4)
    legacy = HeteroTrainer(cfg, data, labels)
    legacy.run()
    legacy.evaluate()
    fed = Federation(_hetero_pop(lm_pool, seed=4), DML())
    fed.run()
    fed.evaluate()
    for pa, pb in zip(legacy.client_params, fed.population.client_params):
        _assert_tree_equal(pa, pb)
    for oa, ob in zip(legacy.client_opts, fed.population.client_opts):
        _assert_tree_equal(oa, ob)
    assert legacy.history.total_comm_bytes == fed.history.total_comm_bytes
    for ra, rb in zip(legacy.history.rounds, fed.history.rounds):
        assert ra.client_loss == rb.client_loss
        assert ra.public_ce == rb.public_ce
        assert ra.kl_loss == rb.kl_loss
        assert ra.participants == rb.participants
    assert legacy.history.client_eval_loss == fed.history.client_eval_loss


# ---------------------------------------------------------------------------
# checkpoint schema: legacy save_state files <-> Federation, both ways

def test_legacy_checkpoint_restores_into_federation(vision_data, tmp_path):
    vn, ((tr_x, tr_y), _) = vision_data
    fc = FederatedConfig(method="dml", n_clients=2, rounds=2,
                         local_epochs=1, batch_size=16, seed=5)
    full = FederatedTrainer(vn, fc, tr_x, tr_y)
    full.run()
    half = FederatedTrainer(vn, fc, tr_x, tr_y)
    half.run(until=1)
    path = str(tmp_path / "legacy_fed")
    half.save_state(path)

    # schema sanity: the legacy meta keys the shim always wrote
    import json
    meta = json.load(open(path + ".json"))["meta"]
    assert meta["engine"] == "federated" and meta["method"] == "dml"
    assert {"n_clients", "round", "plan_seed", "scheduler"} <= set(meta)

    fed = Federation(VisionClients(vn, tr_x, tr_y, n_clients=2, rounds=2,
                                   local_epochs=1, batch_size=16, seed=5),
                     DML())
    fed.restore_state(path)
    assert fed.round == 1
    fed.run()
    _assert_tree_equal(full.client_params, fed.population.client_params)
    _assert_tree_equal(full.client_opts, fed.population.client_opts)
    assert full.history.total_comm_bytes == fed.history.total_comm_bytes
    assert [r.comm_bytes for r in full.history.rounds] == \
        [r.comm_bytes for r in fed.history.rounds]


def test_federation_checkpoint_restores_into_legacy_shim(lm_pool, tmp_path):
    """The reverse direction: a Federation-written state resumes through
    the HeteroTrainer shim bitwise."""
    data, labels = lm_pool
    cfg = HeteroConfig(archs=ARCHS2, rounds=2, local_epochs=1,
                       batch_size=2, public_batch=2, seed=7)
    full = Federation(_hetero_pop(lm_pool, seed=7), DML())
    full.run()
    half = Federation(_hetero_pop(lm_pool, seed=7), DML())
    half.run(until=1)
    path = str(tmp_path / "fed_state")
    half.save_state(path)
    legacy = HeteroTrainer(cfg, data, labels)
    legacy.restore_state(path)
    assert legacy._round == 1
    legacy.run()
    for pa, pb in zip(full.population.client_params, legacy.client_params):
        _assert_tree_equal(pa, pb)
    assert full.history.total_comm_bytes == legacy.history.total_comm_bytes


def test_restore_rejects_strategy_mismatch(vision_data, tmp_path):
    vn, ((tr_x, tr_y), _) = vision_data
    pop = lambda: VisionClients(vn, tr_x, tr_y, n_clients=2, rounds=1,
                                local_epochs=1, batch_size=16)
    fed = Federation(pop(), DML())
    path = str(tmp_path / "st")
    fed.save_state(path)
    other = Federation(pop(), FedAvg())
    with pytest.raises(ValueError, match="checkpoint"):
        other.restore_state(path)


# ---------------------------------------------------------------------------
# sparse top-k sharing, end to end

def test_sparse_kl_to_received_matches_stacked_form():
    """Per-client sparse Eq. 2 vs received top-k sets == row i of the
    stacked ``sparse_mutual_kl_loss`` (same tail model)."""
    from repro.core.mutual import (sparse_kl_to_received,
                                   sparse_mutual_kl_loss, topk_predictions)
    rng = np.random.default_rng(2)
    K, B, V, k = 4, 5, 32, 6
    stack = jnp.asarray(rng.normal(0, 1, (K, B, V)).astype(np.float32))
    idx, logp = topk_predictions(stack, k)
    full = np.asarray(sparse_mutual_kl_loss(stack, idx, logp))  # (K,)
    for i in range(K):
        others_idx = jnp.asarray(np.delete(np.asarray(idx), i, axis=0))
        others_logp = jnp.asarray(np.delete(np.asarray(logp), i, axis=0))
        mine = np.asarray(sparse_kl_to_received(stack[i], others_idx,
                                                others_logp))   # (B,)
        np.testing.assert_allclose(mine.mean(), full[i], atol=1e-5)


def test_hetero_sparse_dml_cuts_comm(lm_pool):
    """Acceptance: SparseDML runs on a mixed-family fleet with strictly
    lower comm than dense DML — by exactly V / (2k)."""
    from repro.core.mutual import sparse_share_bytes
    k = 8
    dense = Federation(_hetero_pop(lm_pool), DML())
    hd = dense.run()
    sparse = Federation(_hetero_pop(lm_pool), SparseDML(k=k))
    hs = sparse.run()
    assert 0 < hs.total_comm_bytes < hd.total_comm_bytes
    # dense: E * 2M * N_pub * V * 4; sparse: E * 2M * N_pub * k * 8
    V = dense.population.n_classes
    assert hd.total_comm_bytes * (k * 8) == hs.total_comm_bytes * (V * 4)
    n_pub = 2 * 24                              # public_batch * seq positions
    assert hs.rounds[0].comm_bytes == sparse_share_bytes(2, n_pub, k)
    assert all(np.isfinite(x) for r in hs.rounds for x in r.kl_loss)
    assert max(hs.rounds[0].kl_loss) > 0
    # the sparse run genuinely trained different params than dense
    la = jax.tree.leaves(dense.population.client_params[0])[0]
    lb = jax.tree.leaves(sparse.population.client_params[0])[0]
    assert not np.array_equal(np.asarray(la), np.asarray(lb))


def test_vision_population_rejects_sparse(vision_data):
    vn, ((tr_x, tr_y), _) = vision_data
    pop = VisionClients(vn, tr_x, tr_y, n_clients=2, rounds=1,
                        local_epochs=1, batch_size=16)
    with pytest.raises(ValueError, match="sparse"):
        Federation(pop, SparseDML(k=4))


def test_sparse_dml_from_cli(lm_pool, capsys):
    """Acceptance: `--strategy sparse-dml` runs from launch/train.py and
    reports strictly lower comm bytes than dense DML."""
    from repro.launch import train

    def total(strategy):
        args = ["--method", "hetero", "--archs", "qwen3-4b,qwen3-4b",
                "--rounds", "1", "--batch", "2", "--seq", "16",
                "--strategy", strategy, "--sparse-k", "8"]
        assert train.main(args) == 0
        out = capsys.readouterr().out
        line = [l for l in out.splitlines()
                if l.startswith("total_comm_bytes=")][-1]
        return int(line.split("=")[1])
    dense, sparse = total("dml"), total("sparse-dml")
    assert 0 < sparse < dense


# ---------------------------------------------------------------------------
# strategy x population compatibility matrix

def test_weight_strategies_rejected_on_mixed_archs(lm_pool):
    for strat in (FedAvg(), AsyncWeights()):
        with pytest.raises(ValueError, match="undefined"):
            Federation(_hetero_pop(lm_pool), strat)


def test_fedavg_on_identical_arch_hetero_fleet_syncs(lm_pool):
    fed = Federation(_hetero_pop(lm_pool, archs=("qwen3-4b", "qwen3-4b")),
                     FedAvg())
    h = fed.run()
    p0, p1 = fed.population.client_params
    for x, y in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=1e-6)
    # weight comm scales with the param count, not the public set
    assert h.total_comm_bytes == \
        2 * 2 * fed.population.params_per_client * 4 * 2   # rounds x up/down


def test_unsupported_strategy_name_rejected(lm_pool):
    class Gossip:
        name = "gossip"
    with pytest.raises(ValueError, match="does not support"):
        Federation(_hetero_pop(lm_pool), Gossip())


# ---------------------------------------------------------------------------
# evaluate(split=...) symmetry

def test_evaluate_split_contract(vision_data, lm_pool):
    vn, ((tr_x, tr_y), (te_x, te_y)) = vision_data
    vfed = Federation(VisionClients(vn, tr_x, tr_y, n_clients=2, rounds=1,
                                    local_epochs=1, batch_size=16), DML())
    vfed.run()
    with pytest.raises(ValueError, match="split"):
        vfed.evaluate()
    h = vfed.evaluate(split=(te_x, te_y))
    assert len(h.client_test_acc) == 2 and 0 <= h.global_test_acc <= 1

    hfed = Federation(_hetero_pop(lm_pool, rounds=1), DML())
    hfed.run()
    with pytest.raises(ValueError, match="held-out"):
        hfed.evaluate(split=(te_x, te_y))
    h = hfed.evaluate()
    assert len(h.client_eval_loss) == 2
    assert all(np.isfinite(x) for x in h.client_eval_loss)

    lfed = Federation(LMClients(get_reduced("qwen3-4b"), n_clients=2,
                                rounds=1, batch=2, seq=16), DML())
    lfed.run()
    with pytest.raises(ValueError, match="held-out"):
        lfed.evaluate(split=(te_x, te_y))


# ---------------------------------------------------------------------------
# the LM population (fused distributed steps behind the session layer)

@pytest.fixture(scope="module")
def lm_cfg():
    return get_reduced("qwen3-4b")


def test_lm_population_strategy_matrix(lm_cfg):
    def pop():
        return LMClients(lm_cfg, n_clients=3, rounds=2, batch=2, seq=16,
                         seed=0)
    dml = Federation(pop(), DML())
    hd = dml.run()
    dml.evaluate()
    assert all(np.isfinite(x) for x in hd.client_eval_loss)
    assert hd.total_comm_bytes > 0
    assert hd.rounds[0].participants == [0, 1, 2]

    sparse = Federation(pop(), SparseDML(k=16))
    hs = sparse.run()
    assert 0 < hs.total_comm_bytes < hd.total_comm_bytes

    fa = Federation(pop(), FedAvg())
    hf = fa.run()
    leaf = jax.tree.leaves(fa.population.client_params)[0]
    np.testing.assert_allclose(np.asarray(leaf[0], np.float32),
                               np.asarray(leaf[1], np.float32), atol=1e-6)
    assert hf.total_comm_bytes > hd.total_comm_bytes   # weights >> logits

    asy = Federation(pop(), AsyncWeights(delta=2, min_round=0))
    ha = asy.run()
    assert [r.layer for r in ha.rounds] == ["shallow", "deep"]
    assert 0 < ha.rounds[0].comm_bytes < ha.rounds[1].comm_bytes


def test_lm_population_partial_participation(lm_cfg):
    def run(m):
        fed = Federation(LMClients(lm_cfg, n_clients=3, rounds=1, batch=2,
                                   seq=16, seed=0), DML(), participation=m)
        before = jax.tree.map(lambda x: np.asarray(x).copy(),
                              fed.population.client_params)
        h = fed.run()
        return fed, before, h
    fed, before, h = run(2)
    part = h.rounds[0].participants
    assert len(part) == 2
    (absent,) = [c for c in range(3) if c not in part]
    for x, y in zip(jax.tree.leaves(before),
                    jax.tree.leaves(fed.population.client_params)):
        np.testing.assert_array_equal(x[absent], np.asarray(y)[absent])
    _, _, hf = run(0)
    assert h.total_comm_bytes * 3 == hf.total_comm_bytes * 2


def test_lm_local_phase_isolates_absentees(lm_cfg):
    """Weight strategies with M < K: participants' updates must not depend
    on the absent client's private data in ANY way — including through the
    shared global-norm gradient clip (losses are masked BEFORE the grad)."""
    from repro.data.federated import sample_participants
    part = sample_participants(3, 2, 0, 0)
    (absent,) = [c for c in range(3) if c not in part]

    class Tampered(LMClients):
        def _private_batch(self, r):
            t = super()._private_batch(r)
            return t.at[absent].set((t[absent] + 7) % self.cfg.vocab_size)

    outs = []
    for cls in (LMClients, Tampered):
        fed = Federation(cls(lm_cfg, n_clients=3, rounds=1, batch=2,
                             seq=16, seed=0), FedAvg(), participation=2)
        fed.run()
        outs.append(fed.population.client_params)
    for x, y in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        for c in part:
            np.testing.assert_array_equal(np.asarray(x)[c],
                                          np.asarray(y)[c])


def test_lm_single_participant_skips_sharing(lm_cfg):
    """M < 2: the fused population must behave like the others — local
    training only, no public-fold descent, zero comm."""
    fed = Federation(LMClients(lm_cfg, n_clients=3, rounds=1, batch=2,
                               seq=16, seed=0), DML(), participation=1)
    before = jax.tree.map(lambda x: np.asarray(x).copy(),
                          fed.population.client_params)
    h = fed.run()
    assert h.total_comm_bytes == 0
    (lone,) = h.rounds[0].participants
    assert h.rounds[0].kl_loss == [0.0] * 3
    leaf_b = jax.tree.leaves(before)
    leaf_a = jax.tree.leaves(fed.population.client_params)
    for x, y in zip(leaf_b, leaf_a):
        for c in range(3):
            if c == lone:
                continue
            np.testing.assert_array_equal(x[c], np.asarray(y)[c])
    assert any(not np.array_equal(x[lone], np.asarray(y)[lone])
               for x, y in zip(leaf_b, leaf_a))


def test_lm_population_prefix_arch(lm_cfg):
    """Modality-frontend archs (prefix_tokens > 0) train through the LM
    population — the legacy train.py DML loop supported them, so the
    session path must too."""
    cfg = get_reduced("musicgen-medium")
    assert cfg.prefix_tokens > 0
    fed = Federation(LMClients(cfg, n_clients=2, rounds=1, batch=2, seq=16,
                               seed=0), DML())
    h = fed.run()
    assert all(np.isfinite(x) for x in h.rounds[0].client_loss)
    fed.evaluate()
    assert all(np.isfinite(x) for x in h.client_eval_loss)


def test_lm_population_mesh_rejects_non_dense(lm_cfg):
    # a one-device clients mesh: the population places its fleet on the
    # mesh at construction, so the mesh has to be real
    from repro.launch.mesh import make_client_mesh
    pop = LMClients(lm_cfg, n_clients=2, rounds=1, batch=2, seq=16,
                    mesh=make_client_mesh(1))
    with pytest.raises(ValueError, match="dense dml"):
        Federation(pop, SparseDML(k=8))


def test_participants_sampler_shared_across_engines(lm_pool):
    """One sampler: the session's subsets are data.federated's, so every
    strategy/population pairing with the same (seed, round) agrees."""
    from repro.data.federated import sample_participants
    fed = Federation(_hetero_pop(lm_pool, seed=9), DML(), participation=1)
    for r in range(3):
        assert fed.participants(r) == sample_participants(2, 1, 9, r)
