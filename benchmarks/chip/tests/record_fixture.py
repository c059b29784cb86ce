"""Record the small trace that ``test_chipbench_trace.py`` reads.

    python3 benchmarks/chip/tests/record_fixture.py <out.xplane.pb>   # TPU

Three ``round`` steps, as the harness marks them, each running a Pallas
flash-attention forward (bf16 (1, 2, 256, 128), summed) and a bf16
512 x 512 matmul (summed) as two programs, with a 5 ms host sleep after
each step, under the profiler's default options.  The committed
``tests/data/rounds.xplane.pb`` was recorded so on one TPU v5 lite.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[3] / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    q = jnp.ones((1, 2, 256, 128), jnp.bfloat16)
    attn = jax.jit(lambda q: flash_attention(q, q, q).sum())
    mm = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512), jnp.bfloat16)
    attn(q).block_until_ready()
    mm(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for r in range(3):
        with jax.profiler.StepTraceAnnotation("round", step_num=r):
            attn(q).block_until_ready()
            mm(x).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
