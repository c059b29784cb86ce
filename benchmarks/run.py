"""Benchmark harness — one function per paper table/figure + kernel perf.

  bench_table2   paper Table II: client accuracies, 3 frameworks (reduced)
  bench_history  paper Fig. 3/4: per-round training-loss history
  bench_comm     communication bytes/round (the bandwidth claim), CNN + LLM
  bench_hetero   heterogeneous-client DML (transformer+SSM+MoE) incl.
                 partial participation comm scaling
  bench_api      the unified Federation session layer: per-round jit
                 dispatch counts unchanged vs the PR-1 engine (asserted)
                 + bitwise parity + sparse-vs-dense comm ratios
  bench_sharded  device-sharded DML rounds: wall-clock + dispatches vs
                 device count (fake CPU host devices), bitwise-checked
  bench_kernels  kernel wrappers (us_per_call + FLOP/byte model + roofline
                 attribution) and the dense-vs-sparse mutual step vs k
                 (the fused top-k sparse-KL kernel's perf claim)
  bench_privacy  privacy & robustness battery: comm/accuracy/epsilon/
                 MIA-advantage per strategy, the accountant's analytic
                 epsilon curve, and honest accuracy under a colluding
                 client for plain vs trimmed/median DML
  bench_decode   serving engine: steady-state decode tokens/s + p50/p99
                 per-token latency vs batch x model-count x arch, with
                 the O(1)-dispatch, legacy-token-parity and bitwise
                 ensemble-average gates as structural rows

Output: CSV-ish lines on stdout (``name,col,col,...``) AND a
machine-readable ``BENCH_<table>.json`` per bench next to them (--out-dir,
default cwd) — the perf-trajectory input for future PRs.  Committed
baselines live in benchmarks/results/ and are gated by
``benchmarks.check_regression`` in CI.
Run: PYTHONPATH=src python -m benchmarks.run [--fast]
     PYTHONPATH=src python -m benchmarks.run --table sharded
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the sharded table needs several XLA host devices, and the flag must be
# set BEFORE jax initialises — hence this pre-import peek at argv (both
# "--table sharded" and "--table=sharded" forms)
if any("sharded" in a for a in sys.argv) and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = " ".join(x for x in (
        os.environ.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count="
        + os.environ.get("BENCH_HOST_DEVICES", "8")) if x)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.visionnet import reduced as vn_reduced
from repro.core import distributed as D
from repro.core.federated import FederatedConfig, FederatedTrainer
from repro.data.synthetic import make_paper_datasets
from repro.kernels import ref

FAST = False
OUT_DIR = "."

# section -> list of row dicts; cleared before each bench fn and dumped to
# BENCH_<bench>.json right after it, so stdout CSV and JSON never diverge
_ROWS: dict = {}


def row(section: str, **cols) -> None:
    """Record one result row: CSV-ish on stdout + collected for the JSON."""
    _ROWS.setdefault(section, []).append(cols)
    print(",".join([section] + [str(v) for v in cols.values()]))


def _dump_json(bench: str, seconds: float) -> None:
    path = os.path.join(OUT_DIR, f"BENCH_{bench}.json")
    with open(path, "w") as f:
        json.dump({"bench": bench, "seconds": round(seconds, 1),
                   "fast": FAST, "sections": _ROWS}, f, indent=2)


def _fed_runs(rounds=6, n_train=2000, n_test=600, clients=5):
    vn = vn_reduced()
    (tr_x, tr_y), (te_x, te_y) = make_paper_datasets(
        image_size=vn.image_size, n_train=n_train, n_test=n_test)
    out = {}
    for method in ("fedavg", "async", "dml"):
        fc = FederatedConfig(method=method, n_clients=clients, rounds=rounds,
                             local_epochs=3, batch_size=16, lr=0.05,
                             delta=3, min_round=2)
        tr = FederatedTrainer(vn, fc, tr_x, tr_y)
        tr.run()
        out[method] = tr.evaluate(te_x, te_y)
    return out


_RUNS_CACHE = {}


def _runs():
    if "r" not in _RUNS_CACHE:
        if FAST:
            _RUNS_CACHE["r"] = _fed_runs(rounds=2, n_train=400, n_test=200,
                                         clients=3)
        else:
            _RUNS_CACHE["r"] = _fed_runs()
    return _RUNS_CACHE["r"]


def bench_table2() -> None:
    """Paper Table II: per-client accuracy on the unseen dataset 2."""
    print("\n# table2: framework,client,accuracy_pct (paper Table II)")
    names = {"fedavg": "vanilla_fl", "async": "async_weight_fl",
             "dml": "mutual_learning_fl_ours"}
    for method, h in _runs().items():
        for c, acc in enumerate(h.client_test_acc):
            row("table2", framework=names[method], client=f"client{c}",
                accuracy_pct=round(100 * acc, 2))
        spread = 100 * (max(h.client_test_acc) - min(h.client_test_acc))
        row("table2", framework=names[method], client="spread_pct",
            accuracy_pct=round(spread, 2))


def bench_history() -> None:
    """Paper Fig. 3/4: round-by-round mean client loss (+ KL term for DML)."""
    print("\n# history: framework,round,mean_client_loss,mean_kl")
    for method, h in _runs().items():
        for r in h.rounds:
            row("history", framework=method, round=r.round,
                mean_client_loss=round(float(np.mean(r.client_loss)), 4),
                mean_kl=round(float(np.mean(r.kl_loss)), 5))


def bench_comm() -> None:
    """The bandwidth claim: measured CNN bytes + analytic LLM-scale table."""
    print("\n# comm: setting,method,bytes_per_federation")
    for method, h in _runs().items():
        row("comm", setting="visionnet", method=method,
            bytes_per_federation=h.total_comm_bytes)
    print("# comm_llm: arch,fedavg_bytes,dml_dense_bytes,dml_top64_bytes,"
          "dense_ratio,sparse_ratio (K=5 clients, 4096-token public set)")
    from repro.core.mutual import sparse_share_bytes
    for arch in ("qwen3-4b", "dbrx-132b", "jamba-1.5-large-398b",
                 "qwen1.5-110b"):
        cfg = get_config(arch)
        c = D.comm_bytes(cfg, n_clients=5, public_tokens=4096)
        sp = sparse_share_bytes(5, 4096, 64)
        row("comm_llm", arch=arch, fedavg_bytes=c["fedavg_round"],
            dml_dense_bytes=c["dml_round"], dml_top64_bytes=sp,
            dense_ratio=f"{c['fedavg_round'] / max(c['dml_round'], 1):.1f}x",
            sparse_ratio=f"{c['fedavg_round'] / sp:.0f}x")


def bench_noniid() -> None:
    """Paper §VI future work: Dirichlet non-IID client data.  Mutual
    learning's public-set consensus regularises the skewed clients."""
    print("\n# noniid: framework,alpha,client,accuracy_pct")
    vn = vn_reduced()
    n_tr, n_te, rounds = (400, 200, 2) if FAST else (2000, 600, 6)
    (tr_x, tr_y), (te_x, te_y) = make_paper_datasets(
        image_size=vn.image_size, n_train=n_tr, n_test=n_te)
    for alpha in (0.3,):
        for method in ("fedavg", "async", "dml"):
            fc = FederatedConfig(method=method, n_clients=5, rounds=rounds,
                                 local_epochs=3, batch_size=16, lr=0.05,
                                 delta=3, min_round=2, non_iid_alpha=alpha)
            t = FederatedTrainer(vn, fc, tr_x, tr_y)
            t.run()
            h = t.evaluate(te_x, te_y)
            for c, acc in enumerate(h.client_test_acc):
                row("noniid", framework=method, alpha=alpha,
                    client=f"client{c}", accuracy_pct=round(100 * acc, 2))


def bench_hard_task() -> None:
    """Beyond-paper observation: on a weak-signal task, weight AVERAGING
    destroys the fragile features individual clients learn, while
    prediction sharing preserves them — DML is the only framework that
    learns at signal=0.18 (see EXPERIMENTS.md §Repro)."""
    from repro.data.synthetic import make_image_dataset
    print("\n# hard_task: framework,client,accuracy_pct (signal=0.18)")
    vn = vn_reduced()
    n_tr, n_te, rounds = (400, 200, 2) if FAST else (2000, 600, 6)
    tr_x, tr_y = make_image_dataset(n_tr, vn.image_size, seed=0,
                                    brightness=0.0, noise=0.3, signal=0.18)
    te_x, te_y = make_image_dataset(n_te, vn.image_size, seed=999,
                                    brightness=0.1, noise=0.38, signal=0.18)
    for method in ("fedavg", "async", "dml"):
        fc = FederatedConfig(method=method, n_clients=5, rounds=rounds,
                             local_epochs=3, batch_size=16, lr=0.05,
                             delta=3, min_round=2)
        t = FederatedTrainer(vn, fc, tr_x, tr_y)
        t.run()
        h = t.evaluate(te_x, te_y)
        for c, acc in enumerate(h.client_test_acc):
            row("hard_task", framework=method, client=f"client{c}",
                accuracy_pct=round(100 * acc, 2))


def bench_hetero() -> None:
    """Heterogeneous-client DML (the §I motivation): a dense transformer,
    an attention-free SSM, and a fine-grained MoE federate by prediction
    sharing — weight averaging is undefined across their pytrees.  Also
    reports partial-participation (M < K) communication scaling."""
    from repro.core.hetero import HeteroConfig, HeteroTrainer, make_lm_pool
    archs = ("qwen3-4b", "mamba2-780m", "dbrx-132b")
    rounds = 2 if FAST else 4
    print("\n# hetero: participation,round,mean_local_loss,mean_kl,comm_bytes")
    base = HeteroConfig(archs=archs, rounds=rounds, local_epochs=1,
                        batch_size=4, public_batch=4, seed=0)
    pool, labels = make_lm_pool(
        ((1 + len(archs)) * rounds + 1) * 8, 32,
        512, seed=0)
    evals = {}
    for m in (0, 2):                       # full vs 2-of-3 participation
        hc = HeteroConfig(**{**base.__dict__, "participation": m})
        tr = HeteroTrainer(hc, pool, labels)
        h = tr.run()
        for rl in h.rounds:
            live = [rl.client_loss[c] for c in rl.participants]
            row("hetero", participation=m or len(archs), round=rl.round,
                mean_local_loss=round(float(np.mean(live)), 4),
                mean_kl=round(float(np.mean(
                    [rl.kl_loss[c] for c in rl.participants])), 5),
                comm_bytes=rl.comm_bytes)
        evals[m] = (tr.evaluate(), tr)
    print("# hetero_eval: participation,client,arch,family,eval_loss,"
          "total_comm_bytes")
    for m, (h, tr) in evals.items():
        for c, loss in enumerate(h.client_eval_loss):
            row("hetero_eval", participation=m or len(archs),
                client=f"client{c}", arch=archs[c],
                family=tr._models[archs[c]].family,
                eval_loss=round(loss, 4),
                total_comm_bytes=h.total_comm_bytes)


def bench_api() -> None:
    """The unified Federation API has NO abstraction overhead: for every
    strategy the session layer dispatches exactly the per-round jitted
    programs of the PR-1 engine (dml: local_scan + mutual_scan; fedavg:
    local_scan; async: 2x local_scan + accuracy_scan) and reproduces the
    legacy FederatedConfig-driven trainer bitwise.  Also reports the
    sparse-vs-dense comm ratio of the hetero population."""
    from repro.api import (DML, AsyncWeights, FedAvg, Federation,
                           HeteroClients, SparseDML, VisionClients,
                           make_lm_pool)
    # per-round dispatch counts of the PR-1 engine (asserted, not assumed)
    PR1_DISPATCHES = {"dml": {"local_scan": 1, "mutual_scan": 1},
                      "fedavg": {"local_scan": 1},
                      "async": {"local_scan": 2, "accuracy_scan": 1}}
    print("\n# api: strategy,dispatches_per_round,programs,"
          "bitwise_vs_legacy,comm_bytes_per_round")
    vn = vn_reduced()
    rounds = 2
    n_tr = 400 if FAST else 1200
    (tr_x, tr_y), _ = make_paper_datasets(image_size=vn.image_size,
                                          n_train=n_tr, n_test=40)
    strategies = {"dml": lambda: DML(), "fedavg": FedAvg,
                  "async": lambda: AsyncWeights(delta=2, min_round=0)}
    for name, make in strategies.items():
        fc = FederatedConfig(method=name, n_clients=3, rounds=rounds,
                             local_epochs=2, batch_size=16, delta=2,
                             min_round=0, seed=0)
        legacy = FederatedTrainer(vn, fc, tr_x, tr_y)
        legacy.run()
        fed = Federation(VisionClients(vn, tr_x, tr_y, n_clients=3,
                                       rounds=rounds, local_epochs=2,
                                       batch_size=16, seed=0), make())
        fed.run()
        bitwise = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(legacy.client_params),
                            jax.tree.leaves(fed.population.client_params)))
        assert bitwise, f"{name}: Federation diverged from legacy trainer"
        progs = [p for r, p in fed.dispatch_log if r == rounds - 1]
        counts = {p: progs.count(p) for p in sorted(set(progs))}
        assert counts == PR1_DISPATCHES[name], (
            f"{name}: dispatch counts {counts} != PR-1 engine "
            f"{PR1_DISPATCHES[name]} — the session layer added overhead")
        row("api", strategy=name, dispatches_per_round=len(progs),
            programs="+".join(f"{k}x{v}" for k, v in counts.items()),
            bitwise_vs_legacy=bitwise,
            comm_bytes_per_round=fed.history.rounds[-1].comm_bytes)
    # sparse top-k vs dense comm on the hetero population
    print("# api_sparse: strategy,k,comm_bytes_per_federation,vs_dense")
    pool, labels = make_lm_pool(160, 24, 512, seed=0)
    mk_pop = lambda: HeteroClients(("qwen3-4b", "mamba2-780m"), pool,
                                   labels, rounds=2, local_epochs=1,
                                   batch_size=2, public_batch=2, seed=0)
    dense = Federation(mk_pop(), DML())
    hd = dense.run()
    row("api_sparse", strategy="dml", k="-",
        comm_bytes_per_federation=hd.total_comm_bytes, vs_dense="1.0x")
    for k in (8, 64):
        sp = Federation(mk_pop(), SparseDML(k=k))
        hs = sp.run()
        assert hs.total_comm_bytes < hd.total_comm_bytes
        row("api_sparse", strategy="sparse-dml", k=k,
            comm_bytes_per_federation=hs.total_comm_bytes,
            vs_dense=f"{hd.total_comm_bytes / hs.total_comm_bytes:.1f}x")


def bench_sharded() -> None:
    """Device-sharded federated rounds (core.federated + shard_map over a
    ``clients`` mesh): steady-state round wall-clock and jitted dispatches
    per round vs device count, on fake CPU host devices.  device_count=1
    is the unsharded engine baseline; every sharded run's final state is
    checked bitwise against it (the engine's parity guarantee)."""
    from repro.core.federated import FederatedConfig, FederatedTrainer
    from repro.launch.mesh import make_client_mesh
    from repro.configs.visionnet import reduced as vn_reduced
    print("\n# sharded: device_count,clients,compile_round_s,"
          "steady_round_s,dispatches_per_round,comm_bytes_per_round,"
          "bitwise_vs_unsharded")
    n_avail = len(jax.devices())
    if n_avail < 2:
        print("# sharded: skipped — 1 visible device (run via "
              "`--table sharded`, which sets "
              "--xla_force_host_platform_device_count before jax init)")
        return
    K = 8
    rounds = 2 if FAST else 4
    n_tr = 600 if FAST else 1600
    vn = vn_reduced()
    (tr_x, tr_y), _ = make_paper_datasets(image_size=vn.image_size,
                                          n_train=n_tr, n_test=40)
    baseline = None
    for n_dev in (1, 2, 4, 8):
        if n_dev > n_avail:
            print(f"# sharded: skipping device_count={n_dev} "
                  f"(only {n_avail} devices; run with XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8)")
            continue
        mesh = None if n_dev == 1 else make_client_mesh(n_dev)
        fc = FederatedConfig(method="dml", n_clients=K, rounds=rounds,
                             local_epochs=1, batch_size=16, seed=0)
        tr = FederatedTrainer(vn, fc, tr_x, tr_y, mesh=mesh)
        t0 = time.perf_counter()
        tr.run(until=1)                     # compile + round 0
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        tr.run()                            # steady-state rounds
        steady = (time.perf_counter() - t0) / max(rounds - 1, 1)
        disp = len([1 for r, _ in tr.dispatch_log if r == rounds - 1])
        comm = tr.history.rounds[-1].comm_bytes
        if mesh is None:
            baseline = tr
            bitwise = "ref"
        else:
            bitwise = all(
                np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(jax.tree.leaves(baseline.client_params),
                                jax.tree.leaves(tr.client_params)))
            assert bitwise, f"sharded n_dev={n_dev} diverged from unsharded"
        row("sharded", device_count=n_dev, clients=K,
            compile_round_s=round(t_compile, 2),
            steady_round_s=round(steady, 3), dispatches_per_round=disp,
            comm_bytes_per_round=comm, bitwise_vs_unsharded=bitwise)


def _time_call(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def bench_kernels() -> None:
    """Kernel entry points + the dense-vs-sparse mutual step (the PR's
    perf claim).

    Wall-time of the compiled Pallas kernels is only meaningful on TPU;
    interpret mode is a correctness tool whose wall-clock tracks the
    kernel's BLOCK structure (work per vocab block), so the sparse table
    times both the XLA ref graph and the interpreted kernel.  Every row
    carries the analytic FLOP/byte model + the shared roofline attribution
    (``analysis.roofline.roofline_terms`` at V5E peaks): ``roofline_frac``
    = t_compute / max-term, ``bottleneck`` = the binding term.
    """
    from repro.analysis.roofline import roofline_terms
    from repro.core import mutual

    def _rl(flops, hbm, coll=0.0):
        t = roofline_terms(flops, hbm, coll)
        return {"roofline_frac": round(t["roofline_frac"], 3),
                "bottleneck": t["dominant"].replace("t_", "")}

    print("\n# kernels: name,us_per_call,derived_flops,derived_hbm_bytes,"
          "roofline_frac,bottleneck")
    key = jax.random.PRNGKey(0)
    # mutual KL (paper Eq. 2) at LLM-ish width
    K, B, V = 4, 64, 8192
    logits = jax.random.normal(key, (K, B, V))
    f = jax.jit(lambda x: ref.mutual_kl(x))
    us = _time_call(f, logits)
    flops = K * K * B * V * 4                 # softmax + pairwise terms
    hbm = 4 * (K * B * V + K * K * B * V)     # live + every received tensor
    row("kernels", name="kl_mutual_ref", us_per_call=round(us),
        derived_flops=flops, derived_hbm_bytes=hbm, **_rl(flops, hbm))
    # attention
    Bq, S, H, hd = 2, 512, 8, 64
    q = jax.random.normal(key, (Bq, S, H, hd))
    f = jax.jit(lambda q: ref.attention(q, q, q))
    us = _time_call(f, q)
    flops = 4 * Bq * H * S * S * hd
    hbm = 4 * 4 * Bq * S * H * hd             # q,k,v,out (flash-style IO)
    row("kernels", name="attention_ref", us_per_call=round(us),
        derived_flops=flops, derived_hbm_bytes=hbm, **_rl(flops, hbm))
    # SSD
    Bb, Sl, Hh, P, G, N = 2, 1024, 8, 64, 1, 128
    x = jax.random.normal(key, (Bb, Sl, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(key, (Bb, Sl, Hh)))
    A = -jnp.exp(jax.random.normal(key, (Hh,)))
    Bm = jax.random.normal(key, (Bb, Sl, G, N))
    f = jax.jit(lambda x, dt, Bm: ref.ssd(x, dt, A, Bm, Bm, chunk=256)[0])
    us = _time_call(f, x, dt, Bm)
    flops = Bb * Hh * (Sl * 256 * (N + P) + Sl * N * P * 3)
    hbm = 4 * (2 * Bb * Sl * Hh * P + 2 * Bb * Sl * G * N + Bb * Sl * Hh)
    row("kernels", name="ssd_ref", us_per_call=round(us),
        derived_flops=flops, derived_hbm_bytes=hbm, **_rl(flops, hbm))

    # -- dense vs sparse mutual step (value+grad) vs k --------------------
    # The tentpole claim: SparseDML's combine FLOPs/HBM traffic scale with
    # the shared top-k size, not the vocab.  step="dense" is the Eq.-2 step
    # SparseDML replaces (k column = V); step="sparse" rows are the top-k
    # step at k << V.  share_bytes is what goes on the wire per round.
    # NOTE on wall-clock: on CPU the XLA *ref* sparse backward scatter-adds
    # into (K,B,V) per peer — O(K^2 B V) traffic, same order as dense — so
    # only the k-series trend is meaningful there; the streaming custom-VJP
    # kernel path (timed via interpret; compiled on TPU) is the one whose
    # traffic actually scales with k (see the derived columns).
    print("# kernels_sparse: step,impl,k,us_per_call,share_bytes,"
          "derived_flops,derived_hbm_bytes,roofline_frac,bottleneck,"
          "vs_dense")
    K, B, V = 4, 128, 4096
    ks = (128, 32, 8)
    live = jax.random.normal(jax.random.PRNGKey(1), (K, B, V), jnp.float32)
    logp = jax.nn.log_softmax(live, axis=-1)
    reps = 3 if FAST else 10
    for impl in ("ref", "interpret"):
        if impl == "interpret" and FAST:
            continue                      # interpreter is slow; full runs only
        dense = jax.jit(jax.grad(
            lambda l: jnp.sum(mutual.mutual_kl_loss(l, impl=impl))))
        dense_us = _time_call(dense, live, reps=reps)
        flops = 3 * 4 * K * K * B * V          # fwd + bwd ~ 3x fwd
        hbm = 3 * 4 * (K * B * V + K * K * B * V)
        share = K * B * V * 4
        row("kernels_sparse", step="dense", impl=impl, k=V,
            us_per_call=round(dense_us), share_bytes=share,
            derived_flops=flops, derived_hbm_bytes=hbm,
            **_rl(flops, hbm, share), vs_dense="1.0x")
        for k in ks:
            vals, idx = jax.lax.top_k(logp, k)
            step = jax.jit(lambda l, i, v, _impl=impl: jax.grad(
                lambda ll: jnp.sum(mutual.sparse_mutual_kl_loss(
                    ll, i, v, impl=_impl)))(l))
            us = _time_call(step, live, idx, vals, reps=reps)
            # live softmax/entropy is O(V); every received-side term is O(k)
            flops = 3 * (4 * K * B * V + 6 * K * (K - 1) * B * k)
            hbm = 3 * 4 * (K * B * V + 2 * K * (K - 1) * B * k)
            share = 2 * K * B * k * 8
            row("kernels_sparse", step="sparse", impl=impl, k=k,
                us_per_call=round(us), share_bytes=share,
                derived_flops=flops, derived_hbm_bytes=hbm,
                **_rl(flops, hbm, share),
                vs_dense=f"{dense_us / max(us, 1e-9):.1f}x")

    # -- train step vs forward step, per impl -----------------------------
    # Since the flash-attention / SSD kernels carry custom VJPs, a training
    # step runs the SAME impl it runs forward (no grad-time xla_flash
    # downgrade), so the fwd+bwd rows below differentiate straight through
    # the kernels.  derived_flops is the 2ND-forward / 6ND-train parameter
    # model (deterministic, regression-gated); us_per_call is reported.
    print("# kernels_train: impl,step,us_per_call,derived_flops")
    from repro.configs import get_reduced
    from repro.models import transformer as tfm

    cfg = get_reduced("qwen3-4b")
    Bt, St = 2, 64
    tokens = jax.random.randint(jax.random.PRNGKey(2), (Bt, St), 0,
                                cfg.vocab_size)
    params = tfm.init_model(jax.random.PRNGKey(3), cfg)
    n_active = cfg.active_param_count()
    reps = 2 if FAST else 5
    for impl in ("ref", "interpret"):
        fwd = jax.jit(lambda p, t, _i=impl: tfm.loss_fn(p, cfg, t,
                                                        impl=_i)[0])
        train = jax.jit(jax.grad(lambda p, t, _i=impl: tfm.loss_fn(
            p, cfg, t, impl=_i)[0]))
        us_f = _time_call(fwd, params, tokens, reps=reps)
        us_t = _time_call(train, params, tokens, reps=reps)
        row("kernels_train", impl=impl, step="fwd",
            us_per_call=round(us_f), derived_flops=2 * n_active * Bt * St)
        row("kernels_train", impl=impl, step="fwd+bwd",
            us_per_call=round(us_t), derived_flops=6 * n_active * Bt * St)


def bench_privacy() -> None:
    """Privacy & robustness battery (ISSUE 7): what each sharing strategy
    costs on the wire, what it gives up to a membership-inference
    adversary, what (eps, delta) the DP variant certifies, and how the
    robust combiners hold up under a colluding client.

      privacy         strategy,comm_bytes,accuracy_pct,epsilon,
                      mia_advantage — comm is gated deterministically;
                      accuracy/advantage/epsilon are reported (volatile)
                      but their ORDERING is a structural invariant
                      (fedavg leaks most, dp-dml never more than dml)
      privacy_dp      the analytic accountant curve: epsilon vs sigma and
                      vs composed releases (deterministic math, gated;
                      epsilon strictly decreasing in sigma is structural)
      privacy_robust  honest-client accuracy, attack x strategy: plain
                      DML collapses under one colluder in four, the
                      trimmed/median combiners hold (structural)
    """
    from repro.api import Federation, VisionClients, get_strategy
    from repro.core import stacking
    from repro.privacy import gaussian_epsilon
    from repro.privacy.attacks import (collect_client_payloads, payload_mia,
                                       weight_upload_mia)
    vn = vn_reduced().replace(image_size=16)
    seed = 0

    # -- strategy table: comm / accuracy / epsilon / MIA advantage --------
    print("\n# privacy: strategy,comm_bytes,accuracy_pct,epsilon,"
          "mia_advantage")
    K, R, BS = 4, 3, 8
    LE, N, mia_steps = (12, 160, 200) if FAST else (20, 220, 300)
    rng = np.random.default_rng(seed)
    imgs = rng.normal(size=(N, 16, 16, 3)).astype(np.float32)
    labs = (imgs.mean(axis=(1, 2, 3)) > 0).astype(np.float32)
    rand_mask = rng.random(N) < 0.4
    labs[rand_mask] = (rng.random(int(rand_mask.sum())) > 0.5
                       ).astype(np.float32)
    test = rng.normal(size=(200, 16, 16, 3)).astype(np.float32)
    tlab = (test.mean(axis=(1, 2, 3)) > 0).astype(np.float32)

    def make_pop(rounds=R):
        return VisionClients(vn, imgs, labs, n_clients=K, rounds=rounds,
                             local_epochs=LE, batch_size=BS, lr=0.05,
                             seed=seed, record_payloads=True)

    def mem_non(pop, client):
        other = (client + 1) % K
        mem = np.unique(np.concatenate([f[client] for f in pop.fold_log]))
        non = np.setdiff1d(
            np.unique(np.concatenate([f[other] for f in pop.fold_log])), mem)
        return mem, non

    def payload_probe(pop):
        advs = []
        for c in range(K):
            mem, non = mem_non(pop, c)
            pi, pp = collect_client_payloads(pop.payload_log, imgs, c)
            advs.append(payload_mia(vn, pi, pp, imgs, labs, mem, non,
                                    jax.random.PRNGKey(1000 + c),
                                    steps=mia_steps))
        return float(np.mean(advs))

    # FedAvg upload tap: run the schedule, then one extra local phase IS
    # the weight upload the eavesdropper scores
    pop_fa = make_pop(rounds=R + 1)
    fed_fa = Federation(pop_fa, get_strategy("fedavg"))
    fed_fa.run(until=R)
    pop_fa.begin_round(R)
    part = list(range(K))
    pop_fa.local_phase(R, part, pop_fa.part_mask(part))
    advs = []
    for c in range(K):
        mem, non = mem_non(pop_fa, c)
        cp = stacking.client_slice(pop_fa.client_params, c)
        advs.append(weight_upload_mia(cp, vn, imgs, labs, mem, non))
    acc_fa = float(np.mean(
        fed_fa.evaluate(split=(test, tlab)).client_test_acc))
    row("privacy", strategy="fedavg",
        comm_bytes=fed_fa.history.total_comm_bytes,
        accuracy_pct=round(100 * acc_fa, 2), epsilon="inf",
        mia_advantage=round(float(np.mean(advs)), 3))

    specs = [("dml", {}), ("dp-dml", {"dp_noise_multiplier": 1.0}),
             ("trimmed-dml", {"trim": 1}), ("median-dml", {})]
    for name, knobs in specs:
        pop = make_pop()
        fed = Federation(pop, get_strategy(name, **knobs))
        fed.run()
        acc = float(np.mean(
            fed.evaluate(split=(test, tlab)).client_test_acc))
        eps = (round(fed.strategy.epsilon(), 3)
               if hasattr(fed.strategy, "epsilon") else "inf")
        row("privacy", strategy=name,
            comm_bytes=fed.history.total_comm_bytes,
            accuracy_pct=round(100 * acc, 2), epsilon=eps,
            mia_advantage=round(payload_probe(pop), 3))

    # -- the accountant's analytic curve ----------------------------------
    print("# privacy_dp: sigma,releases,delta,epsilon")
    for sigma in (0.5, 1.0, 2.0, 4.0):
        row("privacy_dp", sigma=sigma, releases=1, delta=1e-5,
            epsilon=round(gaussian_epsilon(sigma, 1e-5), 6))
    from repro.privacy import RDPAccountant
    for releases in (3, 12, 48):
        acc = RDPAccountant()
        acc.step(1.0, releases=releases)
        row("privacy_dp", sigma=1.0, releases=releases, delta=1e-5,
            epsilon=round(acc.epsilon(1e-5), 6))

    # -- Byzantine collusion vs the robust combiners ----------------------
    print("# privacy_robust: strategy,attack,honest_accuracy_pct")
    Rb, kl, me, le, off, lr = (3, 5.0, 3, 2, 0.3, 0.03) if FAST \
        else (4, 5.0, 3, 2, 0.3, 0.03)
    rngb = np.random.default_rng(seed)

    def make_xy(n):
        y = (rngb.random(n) > 0.5).astype(np.float32)
        x = rngb.normal(size=(n, 16, 16, 3)).astype(np.float32)
        x += (y * 2 - 1)[:, None, None, None] * off
        return x, y

    bimgs, blabs = make_xy(420)
    btest, btlab = make_xy(300)
    byz = {K - 1: "collude"}
    for name, attacked, knobs in [
            ("dml", False, {}), ("dml", True, {}),
            ("trimmed-dml", True, {"trim": 1}), ("median-dml", True, {})]:
        pop = VisionClients(vn, bimgs, blabs, n_clients=K, rounds=Rb,
                            local_epochs=le, batch_size=16, seed=seed,
                            lr=lr, byzantine=byz if attacked else None)
        fed = Federation(pop, get_strategy(name, kl_weight=kl,
                                           mutual_epochs=me, **knobs))
        fed.run()
        h = fed.evaluate(split=(btest, btlab))
        honest = float(np.mean([a for c, a in enumerate(h.client_test_acc)
                                if c != K - 1]))
        row("privacy_robust", strategy=name,
            attack="collude" if attacked else "none",
            honest_accuracy_pct=round(100 * honest, 2))


def bench_decode() -> None:
    """Serving decode (the serving-subsystem tentpole): steady-state
    tokens/s + per-token latency vs batch x model-count x arch, and the
    engine's structural guarantees as gated rows —

      decode          throughput/latency grid.  ``decode_dispatches`` is
                      the per-generate device-program count (gated
                      deterministically); compile/steady/p50/p99 are
                      wall-clock info.  p50/p99 time the SINGLE-step
                      decode program (the chunk=1 continuous-serving
                      dispatch); steady_tok_s times the fused full-length
                      scan.
      decode_dispatch dispatches per generate at two gen_lens — the O(1)
                      claim: equal counts regardless of gen_len
                      (structural).
      decode_parity   ok-flag rows (MUST_BE_TRUE): fused-scan tokens ==
                      legacy per-token Python loop; ensemble-average
                      logits bitwise == the standalone vmapped oracle.
    """
    from repro.configs import get_reduced
    from repro.launch.serve import greedy_generate
    from repro.models import transformer as tfm
    from repro.serve import ServeEngine

    GEN, MAX_SEQ, S0 = 16, 64, 8
    reps = 3 if FAST else 10
    lat_reps = 8 if FAST else 30
    grid = [("qwen3-4b", 1), ("mamba2-780m", 1), ("qwen3-4b", 3)]
    rng = np.random.default_rng(0)

    def make(arch, models):
        cfg = get_reduced(arch)
        if models == 1:
            return cfg, tfm.init_model(jax.random.PRNGKey(0), cfg), "single"
        params = jax.vmap(lambda k: tfm.init_model(k, cfg))(
            jax.random.split(jax.random.PRNGKey(0), models))
        return cfg, params, "average"

    print("\n# decode: arch,models,batch,gen_len,decode_dispatches,"
          "compile_s,steady_tok_s,p50_ms,p99_ms")
    for arch, models in grid:
        cfg, params, mode = make(arch, models)
        for batch in (1, 2, 4):
            prompts = rng.integers(0, cfg.vocab_size,
                                   (batch, S0)).astype(np.int32)
            eng = ServeEngine(cfg, params, mode=mode, slots=batch,
                              max_seq=MAX_SEQ)
            t0 = time.perf_counter()
            eng.generate(prompts, GEN)
            compile_s = time.perf_counter() - t0
            n0 = len(eng.dispatch_log)
            t0 = time.perf_counter()
            for _ in range(reps):
                eng.generate(prompts, GEN)
            steady = (time.perf_counter() - t0) / reps
            disp = (len(eng.dispatch_log) - n0) // reps
            # per-token latency distribution: the chunk=1 decode program
            lg, cache = eng._prefill_prog()(eng.params,
                                            jnp.asarray(prompts), None)
            cidx = jnp.zeros((batch,), jnp.int32)
            key = jax.random.PRNGKey(0)
            tok0, _ = eng._first_token_prog()(lg, cidx, key)
            sd = eng._decode_prog(1)
            out = sd(eng.params, tok0[:, None], cache, jnp.int32(S0), key,
                     cidx)
            jax.block_until_ready(out[0])              # compile
            lats = []
            tok, cache, pos, key = out[3], out[2], out[4], out[5]
            for _ in range(lat_reps):
                t1 = time.perf_counter()
                out = sd(eng.params, tok, cache, pos, key, cidx)
                jax.block_until_ready(out[0])
                lats.append((time.perf_counter() - t1) * 1e3)
                tok, cache, pos, key = out[3], out[2], out[4], out[5]
            row("decode", arch=arch, models=models, batch=batch,
                gen_len=GEN, decode_dispatches=disp,
                compile_s=round(compile_s, 2),
                steady_tok_s=round(batch * GEN / steady, 1),
                p50_ms=round(float(np.percentile(lats, 50)), 3),
                p99_ms=round(float(np.percentile(lats, 99)), 3))

    print("# decode_dispatch: arch,models,gen_len,dispatches")
    for arch, models in grid:
        cfg, params, mode = make(arch, models)
        prompts = rng.integers(0, cfg.vocab_size, (2, S0)).astype(np.int32)
        for gl in (4, 16):
            eng = ServeEngine(cfg, params, mode=mode, slots=2,
                              max_seq=MAX_SEQ)
            eng.generate(prompts, gl)
            row("decode_dispatch", arch=arch, models=models, gen_len=gl,
                dispatches=len(eng.dispatch_log))

    print("# decode_parity: arch,models,check,ok")
    for arch, models in grid[:2]:
        cfg, params, mode = make(arch, models)
        prompts = rng.integers(0, cfg.vocab_size, (2, S0)).astype(np.int32)
        eng = ServeEngine(cfg, params, mode=mode, slots=2, max_seq=MAX_SEQ)
        legacy = np.asarray(greedy_generate(cfg, params,
                                            jnp.asarray(prompts), GEN))
        ok = bool(np.array_equal(eng.generate(prompts, GEN), legacy))
        row("decode_parity", arch=arch, models=models,
            check="tokens_match_legacy", ok=ok)
    # ensemble-average bitwise vs the independently-jitted vmapped oracle
    arch, models = grid[2]
    cfg, params, _ = make(arch, models)
    prompts = rng.integers(0, cfg.vocab_size, (2, S0)).astype(np.int32)
    eng = ServeEngine(cfg, params, mode="average", slots=2, max_seq=MAX_SEQ)
    G = 5
    toks, lg = eng.generate(prompts, G, return_logits=True)
    pre = jax.jit(lambda ps, t: jax.vmap(
        lambda p: tfm.prefill(p, cfg, t, None, max_seq=MAX_SEQ))(ps))
    step = jax.jit(lambda ps, tok, c, pos: (
        lambda lc: (jnp.mean(lc[0], axis=0), lc[1]))(
            jax.vmap(lambda p, cc: tfm.decode_step(p, cfg, tok, cc, pos))(
                ps, c)))
    l0, cache = pre(params, jnp.asarray(prompts))
    tok = jnp.argmax(jnp.mean(l0, 0), -1)[:, None].astype(jnp.int32)
    ok = True
    for t in range(G):
        ok &= bool(np.array_equal(np.asarray(tok[:, 0]), toks[:, t]))
        lo, cache = step(params, tok, cache, jnp.int32(S0 + t))
        ok &= bool(np.array_equal(np.asarray(lo), lg[:, t]))
        tok = jnp.argmax(lo, -1)[:, None].astype(jnp.int32)
    row("decode_parity", arch=arch, models=models,
        check="bitwise_ensemble_avg_vs_oracle", ok=ok)


BENCHES = {
    "table2": bench_table2,
    "history": bench_history,
    "comm": bench_comm,
    "hard_task": bench_hard_task,
    "noniid": bench_noniid,
    "hetero": bench_hetero,
    "api": bench_api,
    "sharded": bench_sharded,
    "kernels": bench_kernels,
    "privacy": bench_privacy,
    "decode": bench_decode,
}


def main() -> None:
    global FAST, OUT_DIR
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", choices=sorted(BENCHES), default=None,
                    help="run a single bench section")
    ap.add_argument("--table", dest="only", choices=sorted(BENCHES),
                    help="alias for --only")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<table>.json files")
    args, _ = ap.parse_known_args()
    FAST = args.fast
    OUT_DIR = args.out_dir
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.time()
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        t1 = time.time()
        _ROWS.clear()
        fn()
        dt = time.time() - t1
        _dump_json(name, dt)
        print(f"# section_seconds,{name},{dt:.1f}")
    print(f"\n# total_bench_seconds,{time.time() - t0:.0f}")


if __name__ == "__main__":
    from repro.launch import use_compile_cache
    use_compile_cache()
    main()
