"""Subprocess body for test_dryrun_small: 8 host devices, reduced configs,
a (2, 2, 2) pod mesh — exercises the exact dry-run machinery end-to-end
without the 512-device compile cost.  Run via test_dryrun_small.py only.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs import SHAPES, get_reduced
from repro.configs.base import ShapeConfig
from repro.launch import dryrun as DR


def small_mesh():
    return shd.make_mesh((2, 2, 2), ("pod", "data", "model"))


def main():
    arch = sys.argv[1]
    method = sys.argv[2] if len(sys.argv) > 2 else "standard"
    kind = sys.argv[3] if len(sys.argv) > 3 else "train"
    cfg = get_reduced(arch)
    shape = ShapeConfig("small", seq_len=64, global_batch=8, kind=kind)
    mesh = small_mesh()
    rules = ({"batch": ("data",), "attn_batch": ("data",)}
             if method in ("dml", "mutual", "fedavg_sync") else {})
    with shd.axis_rules(rules):
        step, args, shards = DR.build_case(cfg, shape, mesh, method)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=shards).lower(*args)
            compiled = lowered.compile()
    stats = DR.collective_stats(compiled.as_text(), pod_stride=4)
    cost = DR.cost_dict(compiled)
    assert cost.get("flops", 0) > 0 or method == "fedavg_sync"
    print(f"OK {arch} {method} {kind} collectives={int(stats['count'])} "
          f"pod_axis={stats['pod_axis']:.0f}")


if __name__ == "__main__":
    main()
