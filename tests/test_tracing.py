"""The federated round under ``jax.profiler``: the host spans a round
writes and their stats, the population's count of compiled programs, the
named scopes of the round programs and the Pallas kernels' names.

A K=2 ``LMClients`` DML federation at ``reduced()`` sizes runs two rounds
under ``jax.profiler.trace``; the trace is read back with
``ProfileData``.  The kernels are lowered for the TPU without a chip
(``lowering_platforms``: nothing compiles, no TPU library is loaded) at
the widths ``tests/test_chip_compile.py`` compiles them."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.api import DML, Federation, LMClients
from repro.configs import get_reduced
from repro.core import distributed as D
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kl_mutual import kl_mutual, kl_mutual_pair
from repro.kernels.sparse_kl import sparse_kl_topk
from repro.kernels.ssd_scan import ssd_scan
from repro.optim import AdamWConfig

K, B, S = 2, 2, 16
TRACED = 2
SPANS = ("federated round", "batch build", "dispatch", "metrics sync")
SCOPES = ("private_loss", "public_logits", "eq2", "optimizer")


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """(population, session, host events of the first two rounds)."""
    out = tmp_path_factory.mktemp("trace")
    pop = LMClients(get_reduced("qwen3-4b"), n_clients=K, rounds=TRACED + 2,
                    batch=B, seq=S, seed=0)
    fed = Federation(pop, DML())
    with jax.profiler.trace(str(out)):
        fed.run(until=TRACED)
    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                               dict(e.stats) if e.name in SPANS else {})
                              for e in line.events)
    return pop, fed, events


def _spans(events, name, r):
    return [e for e in events if e[0] == name and e[3].get("round") == r]


@pytest.mark.parametrize("r", range(TRACED))
def test_each_round_writes_its_spans(session, r):
    _, _, events = session
    assert len(_spans(events, "federated round", r)) == 1
    builds = {e[3]["which"]: e[3]["tokens"]
              for e in _spans(events, "batch build", r)}
    assert builds == {"private": K * B * S, "public": (B // 2) * S}
    assert len(_spans(events, "batch build", r)) == 2
    [dispatch] = _spans(events, "dispatch", r)
    assert dispatch[3]["programs"] == 1
    assert len(_spans(events, "metrics sync", r)) == 1


@pytest.mark.parametrize("r", range(TRACED))
def test_spans_lie_inside_their_round(session, r):
    """A round's dispatch and metrics sync lie inside its own round.  Its
    batches are built on demand inside it in round 0, and from round 1
    on inside the round before, after that round's program was
    dispatched and before the host reads its metrics back."""
    _, _, events = session
    [(_, lo, hi, _)] = _spans(events, "federated round", r)
    inner = [e for name in SPANS[2:] for e in _spans(events, name, r)]
    assert len(inner) == 2
    assert all(lo <= start and end <= hi for _, start, end, _ in inner)
    builds = _spans(events, "batch build", r)
    assert len(builds) == 2
    if r == 0:
        assert all(lo <= start and end <= hi for _, start, end, _ in builds)
        assert [e[3]["ahead"] for e in builds] == [0, 0]
        return
    [(_, lo, hi, _)] = _spans(events, "federated round", r - 1)
    [(_, _, dispatched, _)] = _spans(events, "dispatch", r - 1)
    [(_, synced, _, _)] = _spans(events, "metrics sync", r - 1)
    assert all(lo <= dispatched <= start and end <= synced <= hi
               for _, start, end, _ in builds)
    assert [e[3]["ahead"] for e in builds] == [1, 1]


def test_no_host_event_named_round(session):
    # ``round`` is the name a caller's StepTraceAnnotation gives its steps
    _, _, events = session
    assert {e[0] for e in events} >= set(SPANS)
    assert "round" not in {e[0] for e in events}


def test_compiled_programs_counts_new_programs_only(session):
    pop, fed, _ = session
    assert fed.round == TRACED and pop.compiled_programs == 1
    fed.strategy.kl_weight = 0.5          # a new program, once
    fed.run(until=TRACED + 1)
    assert pop.compiled_programs == 2
    fed.run(until=TRACED + 2)
    assert pop.compiled_programs == 2


def _stacked_shapes(cfg):
    params = jax.eval_shape(lambda k: D.stacked_init(k, cfg, K),
                            jax.random.PRNGKey(0))
    return params, jax.eval_shape(D.stacked_adamw_init, params)


def _round_steps():
    """name -> (step, its arguments after params and optimizer state, the
    scopes it holds)."""
    cfg = get_reduced("qwen3-4b")
    opt = AdamWConfig(lr=1e-3, warmup=5, total_steps=4)
    tok = jax.ShapeDtypeStruct((K, B, S), jnp.int32)
    pub = jax.ShapeDtypeStruct((B // 2, S), jnp.int32)
    from repro.launch.mesh import make_client_mesh
    return {
        "dml": (D.make_dml_train_step(cfg, opt, impl="ref"), (tok, pub),
                SCOPES),
        "sharded_dml": (D.make_sharded_dml_step(
            cfg, opt, make_client_mesh(1), K, impl="ref"), (tok, pub),
            SCOPES),
        "local": (D.make_local_train_step(cfg, opt, impl="ref"), (tok,),
                  ("private_loss", "optimizer")),
        "mutual": (D.make_mutual_step(cfg, opt, impl="ref"), (pub,),
                   ("public_logits", "eq2", "optimizer")),
    }, cfg


@pytest.mark.parametrize("name", ["dml", "sharded_dml", "local", "mutual"])
def test_round_step_names_its_scopes(name):
    steps, cfg = _round_steps()
    step, args, scopes = steps[name]
    params, opt_state = _stacked_shapes(cfg)
    text = jax.jit(step).lower(params, opt_state, *args).as_text(
        debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in scopes:
        assert any(f"/{scope}/" in n or f"({scope})" in n for n in locs), \
            scope
    # the backward carries the forward's scopes by itself
    assert any("transpose(jvp(" in n and any(s in n for s in scopes)
               for n in locs)


def _shapes(*specs):
    return [jax.ShapeDtypeStruct(s, d) for s, d in specs]


BF, F32 = jnp.bfloat16, jnp.float32
V, POS = 18_992, 2048          # qwen3-4b vocabulary / 8; public positions
KERNELS = {
    "flash_attention_fwd": (flash_attention, _shapes(
        ((4, 32, 1024, 128), BF), ((4, 8, 1024, 128), BF),
        ((4, 8, 1024, 128), BF))),
    "kl_mutual_pair": (kl_mutual_pair, _shapes(
        ((2, POS, V), BF), ((2, POS, V), BF), ((2, 2), F32))),
    "kl_mutual": (kl_mutual, _shapes(((2, POS, V), BF))),
    "sparse_kl_topk": (sparse_kl_topk, _shapes(
        ((2, POS, V), BF), ((2, POS, 64), jnp.int32), ((2, POS, 64), F32),
        ((2, 2), F32))),
    "ssd_scan_fwd": (lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c,
                                                     chunk=256)[0],
                     _shapes(((4, 1024, 48, 64), BF), ((4, 1024, 48), F32),
                             ((48,), F32), ((4, 1024, 1, 128), BF),
                             ((4, 1024, 1, 128), BF))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_lowering_carries_its_name(name):
    fn, shapes = KERNELS[name]
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [name]
    # and the call's name stack, which a trace's ``tf_op`` carries
    assert f'/{name}/pallas_call"' in text
