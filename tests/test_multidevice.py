"""Device-sharded federated rounds: sharded == unsharded BITWISE.

Runs on fake CPU host devices (tests/conftest.py sets
``--xla_force_host_platform_device_count=8`` when this module/marker is
selected).  The acceptance bar is exact float equality: a round executed
with whole clients sharded over a ``clients`` mesh — one all-gather of
public-fold predictions as the only collective — must reproduce the
single-device engine's params, opt state, scores, and comm accounting
bit for bit, for all three frameworks and under partial participation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.visionnet import reduced
from repro.core import stacking
from repro.core.federated import FederatedConfig, FederatedTrainer
from repro.data.synthetic import make_paper_datasets

pytestmark = pytest.mark.multidevice


def _need(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, have {len(jax.devices())}")


def _mesh(n):
    from repro.launch.mesh import make_client_mesh
    _need(n)
    return make_client_mesh(n)


def _data(n_train=600, n_test=80):
    vn = reduced()
    return vn, make_paper_datasets(image_size=vn.image_size,
                                   n_train=n_train, n_test=n_test)


def _run(vn, data, mesh, method, K=4, rounds=2, participation=0, seed=3):
    (tr_x, tr_y), (te_x, te_y) = data
    fc = FederatedConfig(method=method, n_clients=K, rounds=rounds,
                         local_epochs=1, batch_size=16, min_round=0,
                         delta=2, participation=participation, seed=seed)
    t = FederatedTrainer(vn, fc, tr_x, tr_y, mesh=mesh)
    t.run()
    t.evaluate(te_x, te_y)
    return t


def _assert_bitwise(a, b):
    """Full engine-state equality: params, opts, global model, history."""
    for x, y in zip(jax.tree.leaves(a.client_params),
                    jax.tree.leaves(b.client_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(a.client_opts),
                    jax.tree.leaves(b.client_opts)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(a.global_params),
                    jax.tree.leaves(b.global_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [r.comm_bytes for r in a.history.rounds] == \
        [r.comm_bytes for r in b.history.rounds]
    assert a.history.total_comm_bytes == b.history.total_comm_bytes
    for ra, rb in zip(a.history.rounds, b.history.rounds):
        assert ra.client_loss == rb.client_loss
        assert ra.kl_loss == rb.kl_loss
        assert ra.participants == rb.participants
    assert a.history.client_test_acc == b.history.client_test_acc
    assert a.history.global_test_acc == b.history.global_test_acc


@pytest.mark.parametrize("method", ["dml", "fedavg", "async"])
def test_sharded_round_bitwise_parity(method):
    """Acceptance: a 4-client round on a clients=4 mesh is bit-identical
    to the single-device engine — params, scores, comm dict."""
    mesh = _mesh(4)
    vn, data = _data()
    a = _run(vn, data, None, method)
    b = _run(vn, data, mesh, method)
    _assert_bitwise(a, b)


@pytest.mark.parametrize("method", ["dml", "fedavg", "async"])
def test_sharded_partial_participation_parity(method):
    """M < K: masking, comm scaling and absentee freezing survive the
    mesh bitwise for all 3 methods."""
    mesh = _mesh(4)
    vn, data = _data()
    a = _run(vn, data, None, method, participation=2)
    b = _run(vn, data, mesh, method, participation=2)
    assert b.history.rounds[0].participants is not None
    _assert_bitwise(a, b)


@pytest.mark.parametrize("K,n_dev", [(5, 4), (3, 8), (6, 2)])
def test_sharded_spill_round_robin(K, n_dev):
    """K != n_devices spills clients round-robin (stacking.client_layout)
    and still matches the unsharded engine bitwise."""
    mesh = _mesh(n_dev)
    vn, data = _data()
    a = _run(vn, data, None, "dml", K=K, rounds=1)
    b = _run(vn, data, mesh, "dml", K=K, rounds=1)
    _assert_bitwise(a, b)


def test_sharded_state_is_actually_distributed():
    """The client axis really lives on the mesh after a DML round (it is
    not gathered between rounds), and the layout helpers invert."""
    mesh = _mesh(4)
    vn, ((tr_x, tr_y), _) = _data()
    fc = FederatedConfig(method="dml", n_clients=4, rounds=1,
                         local_epochs=1, batch_size=16, seed=3)
    t = FederatedTrainer(vn, fc, tr_x, tr_y, mesh=mesh)
    t.run()
    leaf = jax.tree.leaves(t.client_params)[0]
    assert len(leaf.sharding.device_set) == 4, leaf.sharding

    k_loc, k_pad = stacking.client_layout(4, 4)
    assert k_loc % stacking.CLIENT_CHUNK == 0
    send = stacking.rr_send_indices(4, 4)
    inv = stacking.rr_inverse_indices(4, 4)
    np.testing.assert_array_equal(send[inv[:4]], np.arange(4))


def test_sharded_llm_dml_step_matches_unsharded():
    """core.distributed.make_sharded_dml_step: one public-logit all-gather,
    per-client updates allclose to the unsharded fused step, absent
    clients bitwise-frozen."""
    from repro.configs import get_reduced
    from repro.core import distributed as dml
    from repro.data.synthetic import make_token_stream
    from repro.optim import AdamWConfig
    mesh = _mesh(4)
    cfg = get_reduced("qwen3-4b")
    K = 4
    # clip_norm=None: the sharded step clips per client, the unsharded
    # step per fleet — only the unclipped semantics are comparable
    opt_cfg = AdamWConfig(lr=1e-3, warmup=2, total_steps=10,
                          clip_norm=None)
    params = dml.stacked_init(jax.random.PRNGKey(0), cfg, K)
    opt = dml.stacked_adamw_init(params)
    toks = jnp.stack([jnp.asarray(make_token_stream(
        2, 33, cfg.vocab_size, seed=d)[:, :32]) for d in range(K)])
    pub = jnp.asarray(make_token_stream(2, 33, cfg.vocab_size,
                                        seed=99)[:, :32])

    ref_step = jax.jit(dml.make_dml_train_step(cfg, opt_cfg, kl_weight=1.0))
    sh_step = dml.make_sharded_dml_step(cfg, opt_cfg, mesh, K,
                                        kl_weight=1.0)
    p1, _, m1 = ref_step(params, opt, toks, pub)
    p2, o2, m2 = sh_step(params, opt, toks, pub)
    # atol = lr: AdamW's step-1 update is sign-normalised, so a near-zero
    # gradient element whose width-4 and width-2 roundings straddle zero
    # legitimately moves a full lr in opposite directions
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-3, rtol=0)
    np.testing.assert_allclose(np.asarray(m1["kld_avg"]),
                               np.asarray(m2["kld_avg"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1["private_loss"]),
                               np.asarray(m2["private_loss"]), atol=1e-5)
    assert int(o2["step"]) == 1

    # M < K: the absent client's params ride through bitwise
    pm = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    p3, _, _ = sh_step(params, opt, toks, pub, part_mask=pm)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p3)):
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(b)[1])


@pytest.mark.parametrize("K", [4, 8, 5])
def test_lm_population_mesh_placement_and_parity(K):
    """LMClients(mesh=...) builds the fleet where the sharded step keeps
    it (one block of K/4 clients per device; replicated for an uneven K),
    keeps it there across rounds, and its round-0 metrics match the
    unsharded population's."""
    from repro.api import DML, Federation, LMClients
    from repro.configs import get_reduced
    from repro.core import distributed as dml
    mesh = _mesh(4)
    cfg = get_reduced("qwen3-4b")

    def run(m):
        fed = Federation(LMClients(cfg, n_clients=K, rounds=2, batch=2,
                                   seq=16, seed=1, mesh=m), DML())
        return fed, jax.tree.leaves(fed.population.client_params)[0]

    def held(leaf):
        return sorted(s.data.shape[0] for s in leaf.addressable_shards)

    (a, _), (b, leaf) = run(None), run(mesh)
    k_loc, _ = dml.sharded_client_layout(K, 4)
    want = [k_loc] * 4 if K % 4 == 0 else [K] * 4
    assert held(leaf) == want
    a.run()
    b.run()
    assert held(jax.tree.leaves(b.population.client_params)[0]) == want
    ra, rb = a.history.rounds[0], b.history.rounds[0]
    np.testing.assert_allclose(ra.client_loss, rb.client_loss, atol=1e-5)
    np.testing.assert_allclose(ra.kl_loss, rb.kl_loss, atol=1e-5)
    np.testing.assert_allclose(ra.public_ce, rb.public_ce, atol=1e-5)


def test_federation_mesh_bitwise_parity():
    """The unified API composes the execution backend too: a directly-built
    Federation(VisionClients(mesh=...), DML()) matches the single-device
    session bitwise (the legacy-shim mesh tests above cover fedavg/async)."""
    from repro.api import DML, Federation, VisionClients
    mesh = _mesh(4)
    vn, ((tr_x, tr_y), (te_x, te_y)) = _data()

    def run(m):
        fed = Federation(VisionClients(vn, tr_x, tr_y, n_clients=4,
                                       rounds=2, local_epochs=1,
                                       batch_size=16, seed=3, mesh=m),
                         DML())
        fed.run()
        fed.evaluate(split=(te_x, te_y))
        return fed

    a, b = run(None), run(mesh)
    for x, y in zip(jax.tree.leaves(a.population.client_params),
                    jax.tree.leaves(b.population.client_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.history.total_comm_bytes == b.history.total_comm_bytes
    assert a.history.client_test_acc == b.history.client_test_acc


def test_client_mesh_requires_clients_axis():
    _need(2)
    from repro.sharding import make_mesh
    vn, ((tr_x, tr_y), _) = _data(240, 40)
    bad = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    fc = FederatedConfig(method="dml", n_clients=2, rounds=1,
                         local_epochs=1, batch_size=16)
    with pytest.raises(ValueError, match="clients"):
        FederatedTrainer(vn, fc, tr_x, tr_y, mesh=bad)
