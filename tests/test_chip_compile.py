"""The main path's kernels compile for a TPU v5e at published widths.

Interpret-mode tests check what the kernels compute; they cannot see what
the chip's compiler refuses: a block that breaks the (8, 128) tiling, or
a working set above scoped VMEM.  These tests compile each kernel's
forward and backward for a described ``v5e:2x2`` topology (no chip is
needed; nothing runs) at the widths the chip smoke uses:

  - flash attention at qwen3-4b widths: B 4, 32 query / 8 KV heads,
    head_dim 128, S 1024;
  - ``kl_mutual_pair`` over 2048 public positions and the 18,992-token
    vocabulary slice, at Kl=2 with Kg=2 (one chip, K 2) and Kg=8 (one
    device of a four-chip mesh with two clients per device);
  - ``sparse_kl_topk`` at k 64 on the same slice;
  - ``ssd_scan`` at mamba2-780m widths: 48 heads of 64, d_state 128,
    chunk 256, B 4, S 1024.

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off while these tests run: entries
compiled for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.kl_mutual import kl_mutual_pair
from repro.kernels.sparse_kl import sparse_kl_topk
from repro.kernels.ssd_scan import ssd_scan

V = 18_992          # qwen3-4b vocabulary / 8
POS = 2048          # public positions per client: batch 2 x seq 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    old_cache = jax.config.jax_enable_compilation_cache
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _loss_and_grad(fn, argnums):
    """fwd + bwd in one program: the value and the grads of its sum."""
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=argnums)


def test_flash_attention_fwd_bwd(one_chip):
    bf = jnp.bfloat16
    _compile(_loss_and_grad(flash_attention, (0, 1, 2)),
             [((4, 32, 1024, 128), bf), ((4, 8, 1024, 128), bf),
              ((4, 8, 1024, 128), bf)], one_chip)


@pytest.mark.parametrize("kl,kg", [(2, 2), (2, 8)])
def test_kl_mutual_pair_fwd_bwd(one_chip, kl, kg):
    bf = jnp.bfloat16
    _compile(_loss_and_grad(kl_mutual_pair, 0),
             [((kl, POS, V), bf), ((kg, POS, V), bf),
              ((kl, kg), jnp.float32)], one_chip)


def test_sparse_kl_topk_fwd_bwd(one_chip):
    k = 64
    _compile(_loss_and_grad(sparse_kl_topk, 0),
             [((2, POS, V), jnp.bfloat16), ((2, POS, k), jnp.int32),
              ((2, POS, k), jnp.float32), ((2, 2), jnp.float32)], one_chip)


def test_ssd_scan_fwd_bwd(one_chip):
    bf, f32 = jnp.bfloat16, jnp.float32
    B, S, H, P, G, N = 4, 1024, 48, 64, 1, 128

    def y(x, dt, a, b, c):
        return ssd_scan(x, dt, a, b, c, chunk=256)[0]

    _compile(_loss_and_grad(y, (0, 1, 2, 3, 4)),
             [((B, S, H, P), bf), ((B, S, H), f32), ((H,), f32),
              ((B, S, G, N), bf), ((B, S, G, N), bf)], one_chip)
