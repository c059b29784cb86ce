"""Serving CLI: batched ensemble inference over trained Federations.

  # serve a trained population checkpoint, averaging all clients
  PYTHONPATH=src python -m repro.launch.serve --ckpt runs/fed.npz \
      --ensemble average --batch 2 --prompt-len 8 --gen 16

  # no checkpoint: random-init single model (kernel/arch smoke test)
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
      --batch 2 --prompt-len 32 --gen 16

  # continuous batching: more requests than slots, mixed budgets
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
      --requests 8 --slots 2

Timing separates WARMUP (first call — includes jit compilation) from
STEADY STATE (recompiled-nothing repeat), each synced with
``block_until_ready``; the steady-state number is the serving rate.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_reduced
from repro.data.synthetic import make_token_stream
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import transformer as tfm
from repro.serve import MODES, ServeEngine


def greedy_generate(cfg, params, prompts, gen_len: int, prefix=None):
    """Legacy per-token Python decode loop — kept as the token-parity
    reference the engine's fused multi-step scan is tested against.
    prompts: (B, S0) int32.  Returns (B, gen_len) generated ids."""
    B, S0 = prompts.shape
    max_seq = S0 + gen_len + (cfg.prefix_tokens or 0)
    prefill = jax.jit(make_prefill_step(cfg, max_seq=max_seq))
    decode = jax.jit(make_decode_step(cfg))
    args = (params, prompts) if prefix is None else (params, prompts, prefix)
    logits, cache = prefill(*args)
    out = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    pos = S0 + (cfg.prefix_tokens or 0)
    for t in range(gen_len):
        out.append(tok[:, 0])
        logits, cache = decode(params, tok, cache, jnp.int32(pos + t))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    return jnp.stack(out, axis=1)


def _random_prefix(cfg, batch: int, seed: int):
    if not cfg.prefix_tokens:
        return None
    rng = np.random.default_rng(seed)
    return rng.normal(0, 1, (batch, cfg.prefix_tokens, cfg.prefix_dim)
                      ).astype(np.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=None,
                    help="Federation save_state / export_for_serving file; "
                         "omit to serve a random-init --arch model")
    ap.add_argument("--ensemble", choices=MODES, default="average",
                    help="how to serve the K clients of --ckpt")
    ap.add_argument("--client", type=int, default=0,
                    help="client index for --ensemble single")
    ap.add_argument("--arch", choices=ARCH_IDS, default="mamba2-780m",
                    help="arch for random-init serving (no --ckpt)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="cache arena length (0 = fit batch args exactly)")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--requests", type=int, default=0,
                    help=">0: continuous-batching mode with this many "
                         "mixed-length requests instead of one fixed batch")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    max_seq = args.max_seq or ((args.prompt_len + args.gen) * 2)
    kw = dict(max_seq=max_seq, slots=max(args.slots, args.batch),
              chunk=args.chunk, temperature=args.temperature,
              top_k=args.top_k, seed=args.seed)
    if args.ckpt:
        eng = ServeEngine.from_checkpoint(
            args.ckpt, mode=args.ensemble, client=args.client, **kw)
        print(f"ckpt={args.ckpt} arch={eng.cfg.name} "
              f"clients={eng.n_checkpoint_clients} mode={eng.mode}")
    else:
        cfg = get_reduced(args.arch)
        params = tfm.init_model(jax.random.PRNGKey(args.seed), cfg)
        eng = ServeEngine(cfg, params, mode="single", **kw)
        print(f"arch={args.arch} random-init mode=single")
    cfg = eng.cfg

    if args.requests:                      # continuous-batching mode
        rng = np.random.default_rng(args.seed)
        budget = max_seq - (cfg.prefix_tokens or 0)
        for i in range(args.requests):
            s0 = int(rng.integers(2, max(3, min(args.prompt_len,
                                                budget - args.gen) + 1)))
            prompt = rng.integers(0, cfg.vocab_size, (s0,)).astype(np.int32)
            pfx = _random_prefix(cfg, 1, args.seed + i)
            eng.submit(prompt, max_new=min(args.gen, budget - s0),
                       prefix=None if pfx is None else pfx[0])
        t0 = time.perf_counter()
        done = eng.run()
        jax.block_until_ready(eng._arena)
        dt = time.perf_counter() - t0
        n_tok = sum(len(v) for v in done.values())
        print(f"served {len(done)} requests over {eng.slots} slots: "
              f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s, "
              f"compile included); dispatches={eng.dispatch_counts()}")
        rid = min(done)
        print(f"sample rid={rid}:", done[rid][:16].tolist())
        return 0

    prompts = np.asarray(make_token_stream(
        args.batch, args.prompt_len, cfg.vocab_size, seed=args.seed))
    prefix = _random_prefix(cfg, args.batch, args.seed)
    n_tok = args.batch * args.gen

    t0 = time.perf_counter()               # warmup: traces + compiles
    gen = eng.generate(prompts, args.gen, prefix=prefix)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()               # steady state: cached programs
    gen = eng.generate(prompts, args.gen, prefix=prefix)
    steady = time.perf_counter() - t0
    print(f"generated {gen.shape}: warmup {warm:.2f}s "
          f"({n_tok / warm:.1f} tok/s incl. compile), steady {steady:.3f}s "
          f"({n_tok / steady:.1f} tok/s); dispatches/call="
          f"{len(eng.dispatch_log) // 2}")
    print("sample:", gen[0][:16].tolist())
    return 0


if __name__ == "__main__":
    from repro.launch import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
