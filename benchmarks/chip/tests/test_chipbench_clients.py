"""The reference's clients: where they live, how they clip, and the sparse
Eq. 2 they take, on the CPU at the registry's ``reduced()`` sizes.

  - with one device, or none named, the reference runs the same programs
    in the same order, so its numbers are bitwise the same;
  - on four host devices (a child process, so that the flag that makes
    them is set before JAX starts) client c's state lives on device
    c mod n, and the numbers are bitwise those of one device;
  - the clip scope is the traffic file's, the program must run the one it
    states, and the two scopes read apart by more than a cell's limit;
  - the sparse Eq. 2 rebuilds each received distribution from its top k
    with an even tail, and takes the KL against it."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench_cells as cells
from benchmarks.chip import correct, program
from benchmarks.chip.reference import dml as ref_dml

SEED = 2 ** 31 + 31
K2 = "qwen3-4b.dml.k2"
K4 = "qwen3-4b.dml.k4.4chip"
SPARSE = "qwen3-4b.sparse64.k2"


def _run(cell, **kw):
    c = cells.tiny(cell)
    return ref_dml.Federation(c.family, c.config, c.traffic, **kw).run(
        SEED, correct.STEPS)


def _assert_bitwise(a, b):
    for key in ("losses", "grad_norms", "change_norms"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)


@pytest.mark.parametrize("cell", [K2, SPARSE])
def test_one_device_is_bitwise_the_reference_without_devices(cell):
    import jax
    _assert_bitwise(_run(cell, devices=jax.devices()[:1]), _run(cell))


CHILD = r"""
import json, sys
sys.path[:0] = sys.argv[1:]
import jax
import numpy as np
import chipbench_cells as cells
from benchmarks.chip import correct
from benchmarks.chip.reference import dml as ref_dml
c = cells.tiny("%s")
out = {}
for n in (1, 2, 4):
    fed = ref_dml.Federation(c.family, c.config, c.traffic,
                             devices=jax.devices()[:n])
    where = [sorted({d.id for x in jax.tree.leaves(p) for d in x.devices()})
             for p in fed.init(5)]
    run = fed.run(%d, correct.STEPS)
    out[n] = {"where": where, **{k: np.asarray(run[k]).tolist() for k in
                                 ("losses", "grad_norms", "change_norms")}}
print(json.dumps(out))
"""


def test_each_client_lives_on_its_device():
    from pathlib import Path
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD % (K4, SEED), str(cells.ROOT),
         str(cells.ROOT / "src"), str(here)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for n in ("1", "2", "4"):
        assert out[n]["where"] == [[c % int(n)] for c in range(4)]
        for key in ("losses", "grad_norms", "change_norms"):
            assert out[n][key] == out["1"][key], (n, key)


def test_the_traffic_files_state_their_clip_scope():
    assert ref_dml.clip_scope(cells.tiny(K2).traffic["optimizer"]) == "fleet"
    assert ref_dml.clip_scope(cells.tiny(K4).traffic["optimizer"]) == \
        "per_client"
    with pytest.raises(ValueError, match="clip scope"):
        ref_dml.clip_scope({"clip_scope": "per_layer"})


def test_the_program_must_clip_as_the_traffic_file_states(monkeypatch):
    # the 4-chip cell's traffic clips per client; the one-chip round
    # clips over the fleet, and the sharded round per client
    cells.on_one_device(monkeypatch)
    c = cells.tiny(K4)
    with pytest.raises(ValueError, match="clip_scope"):
        program.build(c.config, c.traffic, 5, 1)
    pop, _ = program.build(c.config, c.traffic, 5, 4)
    assert program.optimizer_of(pop)["clip_scope"] == "per_client"
    k2 = cells.tiny(K2)
    with pytest.raises(ValueError, match="clip_scope"):
        program.build(k2.config, k2.traffic, 5, 4)


def test_per_client_and_fleet_clips_read_apart():
    c = cells.tiny(K4)
    fleet = dict(c.traffic, optimizer=dict(c.traffic["optimizer"],
                                           clip_scope="fleet"))
    per_client = _run(K4)
    other = ref_dml.Federation(c.family, c.config, fleet).run(
        SEED, correct.STEPS)
    ok, checks = correct.decide(correct.numbers(other, per_client),
                                c.limits or dict.fromkeys(correct.NUMBERS,
                                                          1e-2))
    assert not ok, checks
    # each client's clipped gradient has the clip's norm under its own
    # scope, and the fleet's under the fleet's
    norms = np.sqrt(np.sum(np.square(per_client["grad_norms"]), axis=1))
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    fleet_norm = math.sqrt(np.sum(np.square(other["grad_norms"])))
    assert fleet_norm == pytest.approx(1.0, rel=1e-5)


def test_the_strategy_file_names_its_top_k():
    c = cells.tiny(SPARSE)
    s = program.strategy(c.traffic)
    assert (s.name, s.sparse_k) == ("sparse-dml", 64)
    assert program.strategy(cells.tiny(K2).traffic).name == "dml"
    with pytest.raises(ValueError, match="unknown strategy"):
        ref_dml.Federation(c.family, c.config,
                           dict(c.traffic, strategy={"name": "fedavg"}))


def test_a_rebuilt_distribution_keeps_the_top_k_and_an_even_tail():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0, 2, (5, 40)), jnp.float32)
    idx, logp = ref_dml.top_k(logits, 6)
    lq = np.asarray(ref_dml.rebuilt(idx, logp, 40), np.float64)
    x = np.asarray(logits, np.float64)
    lp = x - np.log(np.exp(x).sum(-1, keepdims=True))
    top = np.argsort(-np.asarray(logits), axis=-1)[:, :6]
    for n in range(5):
        assert sorted(np.asarray(idx[n])) == sorted(top[n])
        np.testing.assert_allclose(lq[n, top[n]], lp[n, top[n]], rtol=1e-5)
        rest = np.setdiff1d(np.arange(40), top[n])
        tail = 1.0 - np.exp(lp[n, top[n]]).sum()
        np.testing.assert_allclose(lq[n, rest], math.log(tail / 34),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.exp(lq).sum(-1), 1.0, rtol=1e-5)


def test_the_sparse_kl_against_a_rebuilt_distribution_by_hand():
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    live = rng.normal(0, 1, (3, 20))
    sent = rng.normal(0, 1, (2, 3, 20))
    received = [ref_dml.top_k(jnp.asarray(s, jnp.float32), 4) for s in sent]
    idx = jnp.stack([r[0] for r in received])
    logp = jnp.stack([r[1] for r in received])
    got = float(ref_dml.kl_sparse(jnp.asarray(live, jnp.float32),
                                  (idx, logp),
                                  jnp.asarray([0.25, 0.75], jnp.float32)))
    p = np.exp(live) / np.exp(live).sum(-1, keepdims=True)
    want = 0.0
    for j, w in enumerate((0.25, 0.75)):
        q = np.exp(sent[j]) / np.exp(sent[j]).sum(-1, keepdims=True)
        for n in range(3):
            top = np.argsort(-q[n])[:4]
            r = np.full(20, (1.0 - q[n, top].sum()) / 16)
            r[top] = q[n, top]
            want += w * np.sum(p[n] * np.log(p[n] / r)) / 3
    assert got == pytest.approx(want, rel=1e-5)


def test_the_sparse_and_dense_reference_read_apart():
    dense = _run(K2)
    sparse = _run(SPARSE)
    # the same weights give the same cross-entropies before any update
    np.testing.assert_allclose(sparse["losses"][0, :, :2],
                               dense["losses"][0, :, :2], rtol=1e-6)
    assert not np.allclose(sparse["losses"][..., 2], dense["losses"][..., 2],
                           rtol=1e-3)
