"""Fused top-k-gather + sparse-KL kernel vs the XLA oracle.

Covers: forward parity across padded shapes / temperatures / k == V,
custom-VJP gradients vs jax.grad of the ref graph, top-k tie-breaking
determinism, the ops impl switch, and a SparseDML end-to-end Federation
round that is bitwise-identical to the pre-kernel path at impl="ref".
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.core.mutual import (sparse_kl_to_received, sparse_mutual_kl_loss,
                               topk_predictions)
from repro.kernels import ops, ref
from repro.kernels.sparse_kl import sparse_kl_topk


def _logits(K, B, V, seed=0, scale=3.0):
    return jax.random.normal(jax.random.PRNGKey(seed), (K, B, V)) * scale


def _topk(logits, k, temperature=1.0):
    """Received payload: top-k (idx, logp) of each sender's softmax."""
    logp = jax.nn.log_softmax(
        logits.astype(jnp.float32) / temperature, axis=-1)
    vals, idx = jax.lax.top_k(logp, k)
    return idx, vals


def _uniform_w(Kl, J):
    return jnp.full((Kl, J), 1.0 / max(J, 1), jnp.float32)


# ---------------------------------------------------------------------------
# forward parity

@pytest.mark.parametrize("Kl,J,B,V,k,bb,bv", [
    (2, 2, 8, 64, 8, 8, 32),
    (3, 2, 16, 100, 16, 8, 32),    # padded V (100 % 32 != 0)
    (4, 3, 7, 257, 16, 4, 64),     # padded B and V
    (2, 2, 4, 90, 90, 4, 32),      # k == V: no uniform tail
    (1, 3, 6, 128, 8, 4, 128),     # Kl=1 (the hetero per-client form)
])
def test_forward_matches_oracle(Kl, J, B, V, k, bb, bv):
    live = _logits(Kl, B, V, seed=1)
    idx, lp = _topk(_logits(J, B, V, seed=2), k)
    w = _uniform_w(Kl, J)
    want = np.asarray(ref.sparse_kl_pair(live, idx, lp, w))
    got = np.asarray(sparse_kl_topk(live, idx, lp, w, block_b=bb,
                                    block_v=bv, interpret=True))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("temp", [0.5, 1.0, 2.0, 4.0])
def test_temperature(temp):
    live = _logits(3, 8, 128, seed=3)
    idx, lp = _topk(_logits(2, 8, 128, seed=4), 16, temperature=temp)
    w = _uniform_w(3, 2)
    want = np.asarray(ref.sparse_kl_pair(live, idx, lp, w, temperature=temp))
    got = np.asarray(sparse_kl_topk(live, idx, lp, w, temperature=temp,
                                    block_v=32, interpret=True))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_duplicate_indices_multiplicity():
    """Repeated entries in a received index set must be counted once per
    occurrence (gather semantics), exactly like the oracle's gather."""
    Kl, J, B, V, k = 2, 2, 5, 64, 8
    live = _logits(Kl, B, V, seed=5)
    idx, lp = _topk(_logits(J, B, V, seed=6), k)
    idx = idx.at[..., 1].set(idx[..., 0])          # duplicate the argmax
    w = _uniform_w(Kl, J)
    want = np.asarray(ref.sparse_kl_pair(live, idx, lp, w))
    got = np.asarray(sparse_kl_topk(live, idx, lp, w, block_b=4,
                                    block_v=32, interpret=True))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@given(Kl=st.integers(1, 4), J=st.integers(1, 3), B=st.integers(1, 6),
       V=st.integers(4, 90), frac=st.floats(0.1, 1.0),
       seed=st.integers(0, 1000))
def test_property_forward(Kl, J, B, V, frac, seed):
    k = max(1, int(V * frac))
    live = _logits(Kl, B, V, seed=seed, scale=4.0)
    idx, lp = _topk(_logits(J, B, V, seed=seed + 1, scale=4.0), k)
    w = _uniform_w(Kl, J)
    want = np.asarray(ref.sparse_kl_pair(live, idx, lp, w))
    got = np.asarray(sparse_kl_topk(live, idx, lp, w, block_b=4,
                                    block_v=32, interpret=True))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# custom-VJP backward vs AD of the oracle

@pytest.mark.parametrize("Kl,J,B,V,k,bv", [
    (2, 2, 4, 64, 8, 64),
    (3, 2, 6, 100, 16, 32),        # padded V in the streaming backward
    (4, 3, 3, 257, 16, 64),        # padded B and V
    (2, 2, 4, 90, 90, 32),         # k == V
])
def test_vjp_matches_ad_of_oracle(Kl, J, B, V, k, bv):
    live = _logits(Kl, B, V, seed=21)
    idx, lp = _topk(_logits(J, B, V, seed=22), k)
    w = _uniform_w(Kl, J)
    cot = jnp.cos(jnp.arange(Kl * B, dtype=jnp.float32)).reshape(Kl, B)
    g_ref = jax.grad(lambda x: jnp.sum(
        ref.sparse_kl_pair(x, idx, lp, w) * cot))(live)
    g_ker = jax.grad(lambda x: jnp.sum(
        sparse_kl_topk(x, idx, lp, w, block_v=bv,
                       interpret=True) * cot))(live)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("temp", [0.5, 2.5])
def test_vjp_temperature(temp):
    live = _logits(3, 5, 96, seed=23)
    idx, lp = _topk(_logits(2, 5, 96, seed=24), 12, temperature=temp)
    w = _uniform_w(3, 2)
    g_ref = jax.grad(lambda x: jnp.sum(
        ref.sparse_kl_pair(x, idx, lp, w, temperature=temp)))(live)
    g_ker = jax.grad(lambda x: jnp.sum(sparse_kl_topk(
        x, idx, lp, w, temperature=temp, block_v=32,
        interpret=True)))(live)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               atol=2e-5, rtol=1e-4)


@given(Kl=st.integers(1, 3), J=st.integers(1, 3), B=st.integers(1, 5),
       V=st.integers(4, 90), seed=st.integers(0, 1000))
def test_property_vjp(Kl, J, B, V, seed):
    k = max(1, V // 3)
    live = _logits(Kl, B, V, seed=seed, scale=4.0)
    idx, lp = _topk(_logits(J, B, V, seed=seed + 7, scale=4.0), k)
    w = _uniform_w(Kl, J)
    g_ref = jax.grad(lambda x: jnp.sum(
        ref.sparse_kl_pair(x, idx, lp, w)))(live)
    g_ker = jax.grad(lambda x: jnp.sum(sparse_kl_topk(
        x, idx, lp, w, block_b=4, block_v=32, interpret=True)))(live)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               atol=3e-5, rtol=5e-4)


# ---------------------------------------------------------------------------
# top-k tie-breaking determinism (what goes on the wire must not depend on
# who computes it)

def test_topk_tie_breaking_deterministic():
    """Ties break toward the LOWEST vocab index, and two evaluations of
    the share payload are bitwise-identical."""
    B, V, k = 4, 32, 6
    logits = jnp.zeros((2, B, V))                 # all tied
    idx, lp = topk_predictions(logits, k)
    np.testing.assert_array_equal(
        np.asarray(idx), np.broadcast_to(np.arange(k), (2, B, k)))
    idx2, lp2 = topk_predictions(logits, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lp2))
    # partially tied: the tied pair keeps index order
    t = jnp.zeros((1, 1, V)).at[0, 0, 10].set(1.0).at[0, 0, 20].set(1.0)
    idx3, _ = topk_predictions(t, 3)
    assert list(np.asarray(idx3[0, 0, :2])) == [10, 20]


# ---------------------------------------------------------------------------
# the ops impl switch + the core.mutual entry points

def test_no_model_grad_impl_downgrade():
    """Every model kernel now carries a custom VJP, so the grad-time
    downgrade hook must be gone: training steps thread the impl they were
    given, unchanged."""
    assert not hasattr(ops, "model_grad_impl")


def test_unknown_impl_raises_at_every_entry_point():
    """ops.* must validate impl against IMPLS and raise — 'xla_flush' must
    never silently run the oracle (regression: ops.ssd treated any unknown
    impl as pallas-eligible / ref)."""
    q = jnp.zeros((1, 4, 2, 8))
    x = jnp.zeros((1, 8, 2, 4))
    dt = jnp.ones((1, 8, 2))
    A = -jnp.ones((2,))
    Bm = jnp.zeros((1, 8, 1, 4))
    logits = jnp.zeros((2, 3, 16))
    w = jnp.ones((2, 2)) / 2
    idx = jnp.zeros((2, 3, 4), jnp.int32)
    lp = jnp.zeros((2, 3, 4))
    calls = [
        lambda: ops.attention(q, q, q, impl="xla_flush"),
        lambda: ops.ssd(x, dt, A, Bm, Bm, impl="xla_flush"),
        lambda: ops.mutual_kl(logits, impl="cuda"),
        lambda: ops.mutual_kl_pair(logits, logits, w, impl="cuda"),
        lambda: ops.sparse_mutual_kl(logits, idx, lp, w, impl="cuda"),
        lambda: ops.set_impl("nope"),
        lambda: ops.resolve_impl("nope"),
    ]
    for call in calls:
        with pytest.raises(ValueError, match="unknown kernel impl"):
            call()


@pytest.mark.parametrize("env,expect", [
    (None, "pallas"), ("pallas", "pallas"), ("ref", ValueError),
    ("interpret", ValueError), ("xla_flash", ValueError)])
def test_resolve_impl_env_cannot_replace_pallas_on_tpu(monkeypatch, env,
                                                       expect):
    """On a TPU backend ``auto`` is always the compiled kernels: an env
    override naming anything else is an error, never a silent fallback;
    an explicit impl argument still wins."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_IMPL", env)
    if expect is ValueError:
        with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
            ops.resolve_impl("auto")
    else:
        assert ops.resolve_impl("auto") == expect
    assert ops.resolve_impl("ref") == "ref"


def test_local_train_step_differentiable_under_interpret():
    """make_local_train_step(impl='interpret') differentiates straight
    through the attention/SSD Pallas kernels (their custom VJPs; formerly
    a downgrade to 'ref' — regression for the _pallas_call_jvp_rule
    AssertionError)."""
    from repro.configs import get_reduced
    from repro.core import distributed as D
    from repro.optim import AdamWConfig

    cfg = get_reduced("qwen3-4b")
    opt_cfg = AdamWConfig(lr=1e-3, warmup=2, total_steps=10)
    K, B, S = 2, 2, 16
    key = jax.random.PRNGKey(0)
    sp = D.stacked_init(key, cfg, K)
    opt = D.stacked_adamw_init(sp)
    tokens = jax.random.randint(key, (K, B, S), 0, cfg.vocab_size)
    step = jax.jit(D.make_local_train_step(cfg, opt_cfg, impl="interpret"))
    _, _, metrics = step(sp, opt, tokens)
    assert np.isfinite(np.asarray(metrics["ce"])).all()


def test_ops_impl_switch_routes_to_kernel():
    Kl, J, B, V, k = 2, 2, 6, 80, 8
    live = _logits(Kl, B, V, seed=31)
    idx, lp = _topk(_logits(J, B, V, seed=32), k)
    w = _uniform_w(Kl, J)
    a = ops.sparse_mutual_kl(live, idx, lp, w, impl="ref")
    b = ops.sparse_mutual_kl(live, idx, lp, w, impl="interpret")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.parametrize("entry", ["stacked", "received"])
def test_mutual_entry_points_interpret_vs_ref(entry):
    """core.mutual sparse losses: interpret impl == ref impl, values and
    gradients."""
    K, B, V, k = 3, 5, 96, 12
    stack = _logits(K, B, V, seed=41)
    idx, lp = _topk(stack, k)
    if entry == "stacked":
        f = lambda impl: lambda x: jnp.sum(
            sparse_mutual_kl_loss(x, idx, lp, impl=impl))
        x0 = stack
    else:
        f = lambda impl: lambda x: jnp.sum(
            sparse_kl_to_received(x, idx[1:], lp[1:], impl=impl))
        x0 = stack[0]
    np.testing.assert_allclose(np.asarray(f("interpret")(x0)),
                               np.asarray(f("ref")(x0)),
                               atol=3e-5, rtol=3e-5)
    ga = jax.grad(f("ref"))(x0)
    gb = jax.grad(f("interpret"))(x0)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ga), atol=2e-5,
                               rtol=1e-4)


def test_explicit_ref_identical_to_default_path():
    """impl='ref' takes the IDENTICAL branch as the pre-kernel default
    (impl=None -> get_impl()): bitwise, not just close.  Pin the ambient
    default to ref so the check holds under REPRO_KERNEL_IMPL overrides."""
    K, B, V, k = 3, 4, 64, 8
    stack = _logits(K, B, V, seed=51)
    idx, lp = _topk(stack, k)
    with ops.use_impl("ref"):
        default = sparse_mutual_kl_loss(stack, idx, lp)      # get_impl()->ref
        d2 = sparse_kl_to_received(stack[0], idx[1:], lp[1:])
    explicit = sparse_mutual_kl_loss(stack, idx, lp, impl="ref")
    np.testing.assert_array_equal(np.asarray(default), np.asarray(explicit))
    e2 = sparse_kl_to_received(stack[0], idx[1:], lp[1:], impl="ref")
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(e2))


# ---------------------------------------------------------------------------
# SparseDML end-to-end through the Federation session layer

def test_sparse_dml_federation_bitwise_at_ref():
    """A SparseDML federation with kernel_impl='ref' is bitwise-identical
    to kernel_impl='auto' on CPU (auto resolves to ref) — i.e. the impl
    plumbing added for the kernel did not perturb the pre-PR hot path."""
    from repro.api import Federation, HeteroClients, SparseDML, make_lm_pool
    if ops.resolve_impl("auto") != "ref":
        pytest.skip("auto does not resolve to ref here (TPU backend or "
                    "REPRO_KERNEL_IMPL override) — bitwise check is "
                    "ref-vs-auto on CPU only")
    data, labels = make_lm_pool(120, 24, 512, seed=0)
    mk = lambda impl: HeteroClients(
        ("qwen3-4b", "mamba2-780m"), data, labels, rounds=2,
        local_epochs=1, batch_size=2, public_batch=2, seed=0,
        kernel_impl=impl)
    pa = Federation(mk("ref"), SparseDML(k=8))
    ha = pa.run()
    pb = Federation(mk("auto"), SparseDML(k=8))
    hb = pb.run()
    assert jax.default_backend() == "cpu"
    la, lb = (jax.tree.leaves(pa.population.client_params),
              jax.tree.leaves(pb.population.client_params))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert ha.total_comm_bytes == hb.total_comm_bytes
    np.testing.assert_array_equal(ha.rounds[-1].kl_loss, hb.rounds[-1].kl_loss)
