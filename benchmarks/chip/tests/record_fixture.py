"""Record the small traces that ``test_chipbench_trace.py`` and
``test_chipbench_scopes.py`` read.

    python3 benchmarks/chip/tests/record_fixture.py <out.xplane.pb>   # TPU
    python3 benchmarks/chip/tests/record_fixture.py --scoped <out.xplane.pb>

Three ``round`` steps, as the harness marks them, each running two
programs, with a 5 ms host sleep after each step, under the profiler's
default options.

Plain (``tests/data/rounds.xplane.pb``): a Pallas flash-attention forward
(bf16 (1, 2, 256, 128), summed) and a bf16 512 x 512 matmul (summed).

Scoped (``tests/data/scoped_rounds.xplane.pb``): the flash-attention
forward under ``jax.named_scope("private_loss")`` with its sum outside
any scope, then the gradient of the summed squares of a bf16 512 x 512
matmul taken under ``jax.named_scope("eq2")``, so that its forward runs
as ``jvp(eq2)`` and its backward as ``transpose(jvp(eq2))``.  The host
writes, as the program does, spans that carry the stat ``round``: two
``batch build`` spans a round with the numeric stat ``rows``, 64 x (r +
1) and 8, and a ``dispatch`` span around each program's call; and one
span without stats, which the trace's reader leaves out.

The committed traces were recorded so on one TPU v5 lite.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[3] / "src")]


def _programs(scoped: bool):
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    q = jnp.ones((1, 2, 256, 128), jnp.bfloat16)
    x = jnp.ones((512, 512), jnp.bfloat16)
    if not scoped:
        return [(jax.jit(lambda q: flash_attention(q, q, q).sum()), q),
                (jax.jit(lambda x: (x @ x).sum()), x)]

    def attn(q):
        with jax.named_scope("private_loss"):
            o = flash_attention(q, q, q)
        return o.sum()

    def squares(x):
        with jax.named_scope("eq2"):
            y = x @ x
            return (y.astype(jnp.float32) ** 2).sum()
    return [(jax.jit(attn), q), (jax.jit(jax.grad(squares)), x)]


def main(argv) -> int:
    import jax
    scoped = "--scoped" in argv
    out = [a for a in argv if a != "--scoped"][0]
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    programs = _programs(scoped)
    for fn, arg in programs:
        fn(arg).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for r in range(3):
        with jax.profiler.StepTraceAnnotation("round", step_num=r):
            if scoped:
                for rows in (64 * (r + 1), 8):
                    with jax.profiler.TraceAnnotation("batch build",
                                                      round=r, rows=rows):
                        time.sleep(0.001)
                with jax.profiler.TraceAnnotation("batch build"):
                    pass
            for fn, arg in programs:
                if scoped:
                    with jax.profiler.TraceAnnotation("dispatch", round=r):
                        y = fn(arg)
                else:
                    y = fn(arg)
                y.block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
