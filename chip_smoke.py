"""Chip smoke: the federated DML round on a TPU at published widths.

Drives the paper's main path, ``Federation(LMClients(cfg, ...), strategy)``,
for a few rounds and checks what comes out:

  (a) the device is a TPU (anything else exits 1 before any phase runs);
  (b) every population resolved the compiled Pallas kernels and its
      compiled round program holds ``tpu_custom_call``;
  (c) dense DML, qwen3-4b at published widths cut to 2 of 36 layers and a
      vocabulary of 18,992 (one eighth of 151,936), K=2, batch 4, seq
      1024, 3 rounds;
  (d) SparseDML(k=64) on the same cut, 2 rounds;
  (e) dense DML, mamba2-780m at published widths cut to 8 of 48 layers,
      full 50,280 vocabulary, 2 rounds (exercises the SSD kernel).

Every loss must be finite, and round 0's private_loss, public_ce and
kld_avg must match the same step built with ``impl="ref"`` on the same
initial params and batches.  ``--chips 4`` runs only the sharded path:
K=4 clients on a 4-chip ``clients`` mesh, checked against an unsharded
forward of the same initial params on one chip.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips

Timings and memory printed on the way are smoke timings (one process,
few rounds), not benchmark numbers.  The last stdout line is
``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it is printed.
"""
import argparse
import gc
import json
import math
import os
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import DML, Federation, LMClients, SparseDML  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_client_mesh  # noqa: E402

SEED = 0
BATCH, SEQ = 4, 1024
METRICS = ("private_loss", "public_ce", "kld_avg")
# Kernel vs reference tolerance.  Params and activations are bf16 (8-bit
# mantissa: one rounding is up to 2^-9 relative).  The Pallas kernels and
# the plain-JAX references accumulate in fp32 but round their bf16
# outputs in a different order, and those differences compound over the
# layers; each metric is then a mean over >= 2048 positions.  1e-2
# relative is a few bf16 roundings of headroom on losses near ln(V);
# 1e-3 absolute covers kld_avg, which sits near zero at random init.
RTOL, ATOL = 1e-2, 1e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"FAILED: {what}", file=sys.stderr, flush=True)
        raise SystemExit(1)


def qwen3_cut():
    """qwen3-4b at published widths: 2 of 36 layers (the period is one
    layer) and one eighth of the vocabulary, one chip's share under
    8-way vocab parallelism."""
    return get_config("qwen3-4b").replace(n_layers=2, vocab_size=18_992)


def mamba2_cut():
    """mamba2-780m at published widths: 8 of 48 layers, full vocab."""
    return get_config("mamba2-780m").replace(n_layers=8)


def gib(n: int) -> str:
    return f"{n / 2 ** 30:.2f} GiB"


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def kernel_checks(tag: str, pop, compiled) -> None:
    """(b): the population runs the compiled kernels, not a fallback."""
    check(pop.impl == "pallas", f"{tag}: population resolved impl "
          f"{pop.impl!r}, not 'pallas'")
    check("tpu_custom_call" in compiled.as_text(),
          f"{tag}: no tpu_custom_call in the compiled round program")
    print(f"[b] {tag}: impl={pop.impl}, round program holds "
          "tpu_custom_call", flush=True)


def metrics_only(step):
    """Round-0 metrics of a DML step without its optimizer state: the
    update is discarded, so XLA drops the backward and the moments."""
    return jax.jit(lambda p, toks, pub: {
        k: v for k, v in step(p, D.stacked_adamw_init(p), toks, pub)[2]
        .items() if k in METRICS})


def round0_metrics(history) -> dict:
    r0 = history.rounds[0]
    return {"private_loss": r0.client_loss, "public_ce": r0.public_ce,
            "kld_avg": r0.kl_loss}


def compare(tag: str, got: dict, want: dict) -> None:
    for k in METRICS:
        a = np.asarray(got[k], np.float64)
        b = np.asarray(want[k], np.float64)
        err = np.abs(a - b)
        ok = bool(np.all(err <= ATOL + RTOL * np.abs(b)))
        print(f"[{tag}] round-0 {k}: run={a.tolist()} reference="
              f"{b.tolist()} max|diff|={float(err.max()):.3e} "
              f"(rtol {RTOL}, atol {ATOL})", flush=True)
        check(ok, f"{tag}: round-0 {k} differs from the reference")


def check_finite(tag: str, history) -> None:
    for rl in history.rounds:
        vals = list(rl.client_loss) + list(rl.kl_loss) + \
            list(rl.public_ce or [])
        check(all(math.isfinite(v) for v in vals),
              f"{tag}: non-finite loss in round {rl.round}: {vals}")
    print(f"[{tag}] all losses finite over {len(history.rounds)} rounds",
          flush=True)


def run_rounds(tag: str, fed, rounds: int) -> None:
    times = []
    for r in range(rounds):
        t0 = time.perf_counter()
        fed.run(until=r + 1)     # the history floats wait for the device
        times.append(time.perf_counter() - t0)
        rl = fed.history.rounds[-1]
        print(f"[{tag}] round {r}: private_loss={rl.client_loss} "
              f"kld_avg={rl.kl_loss} public_ce={rl.public_ce} "
              f"({times[-1]:.3f} s)", flush=True)
    print(f"[{tag}] smoke timing: first round {times[0]:.3f} s (program "
          f"load included), warm rounds {[round(t, 4) for t in times[1:]]}"
          " s", flush=True)


def lm_phase(tag: str, cfg, strategy, rounds: int, n_clients: int = 2):
    """One single-chip Federation session with its checks (b) + (tag)."""
    pop = LMClients(cfg, n_clients=n_clients, rounds=rounds, batch=BATCH,
                    seq=SEQ, seed=SEED)
    print(f"[{tag}] {cfg.name}: d_model={cfg.d_model} "
          f"layers={cfg.n_layers} vocab={cfg.vocab_size} K={n_clients} "
          f"batch={BATCH} seq={SEQ} strategy={strategy.name} "
          f"params/client={pop.params_per_client:,}", flush=True)
    args = (pop._private_batch(0), pop.public_payload(0))
    step = pop._dml_step(strategy.kl_weight, strategy.sparse_k)
    t0 = time.perf_counter()
    compiled = step.lower(pop.client_params, pop.client_opts,
                          *args).compile()
    print(f"[{tag}] round program compile {time.perf_counter() - t0:.1f} s",
          flush=True)
    kernel_checks(tag, pop, compiled)
    ref = metrics_only(D.make_dml_train_step(
        cfg, pop.opt_cfg, kl_weight=strategy.kl_weight,
        sparse_k=strategy.sparse_k, impl="ref"))(pop.client_params, *args)
    ref = {k: np.asarray(v) for k, v in ref.items()}
    del args, compiled
    fed = Federation(pop, strategy)
    run_rounds(tag, fed, rounds)
    check_finite(tag, fed.history)
    compare(tag, round0_metrics(fed.history), ref)
    print(f"[{tag}] peak device memory so far "
          f"{gib(peak_bytes(jax.devices()[0]))}", flush=True)


def one_chip() -> None:
    lm_phase("c", qwen3_cut(), DML(), rounds=3)
    gc.collect()
    lm_phase("d", qwen3_cut(), SparseDML(k=64), rounds=2)
    gc.collect()
    lm_phase("e", mamba2_cut(), DML(), rounds=2)


def four_chips() -> None:
    """K=4 qwen3 clients, one per chip, against an unsharded forward of
    the same initial params on chip 0."""
    tag, K, rounds = "4chip", 4, 2
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = qwen3_cut()
    mesh = make_client_mesh(4)
    pop = LMClients(cfg, n_clients=K, rounds=rounds, batch=BATCH, seq=SEQ,
                    seed=SEED, mesh=mesh)
    k_loc, k_pad = D.sharded_client_layout(K, 4)
    leaf = jax.tree.leaves(pop.client_params)[0]
    held = sorted(s.data.shape[0] for s in leaf.addressable_shards)
    print(f"[{tag}] k_loc={k_loc} k_pad={k_pad}; clients held per device "
          f"at construction: {held}", flush=True)
    check(held == [k_loc] * 4, f"{tag}: fleet not placed one block per "
          f"device: {held}")
    toks, pub = pop._private_batch(0), pop.public_payload(0)
    step = pop._dml_step(1.0, 0)
    t0 = time.perf_counter()
    compiled = step.lower(pop.client_params, pop.client_opts, toks,
                          pub).compile()
    # the TPU compiler splits one all-gather over the fusions it overlaps
    # with, and each piece keeps the collective's channel id
    pieces = re.findall(r"all-gather(?:-start)?\(.*channel_id=(\d+)",
                        compiled.as_text())
    print(f"[{tag}] sharded round program compile "
          f"{time.perf_counter() - t0:.1f} s; all-gathers in it: "
          f"{len(set(pieces))}, in {len(pieces)} pieces", flush=True)
    kernel_checks(tag, pop, compiled)
    del compiled
    one = devs[0]
    ref = metrics_only(D.make_dml_train_step(
        cfg, pop.opt_cfg, kl_weight=1.0, impl=pop.impl))(
            jax.device_put(pop.client_params, one),
            jax.device_put(toks, one), jax.device_put(pub, one))
    ref = {k: np.asarray(v) for k, v in ref.items()}
    del toks, pub
    fed = Federation(pop, DML())
    run_rounds(tag, fed, rounds)
    check_finite(tag, fed.history)
    compare(tag, round0_metrics(fed.history), ref)
    leaf = jax.tree.leaves(pop.client_params)[0]
    held = sorted(s.data.shape[0] for s in leaf.addressable_shards)
    check(held == [k_loc] * 4, f"{tag}: state left its blocks: {held}")
    print(f"[{tag}] per-device peak memory: "
          f"{[gib(peak_bytes(d)) for d in devs[:4]]}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args()
    env_impl = os.environ.get("REPRO_KERNEL_IMPL")
    check(env_impl in (None, "", "pallas"),
          f"REPRO_KERNEL_IMPL={env_impl!r} would replace the kernels")
    devs = jax.devices()
    d0 = devs[0]
    print(f"[a] jax {jax.__version__}; devices: {devs}", flush=True)
    print(f"[a] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d0.platform == "tpu", f"no TPU: JAX found {d0.platform!r}")
    print(f"[a] compile cache: {use_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    four_chips() if args.chips == 4 else one_chip()
    print(f"smoke wall time {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
