import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) on 512
placeholder host devices, and dump cost/memory/collective analysis to JSON
for the roofline report.

The two lines above MUST stay the first statements in this module — jax
locks the device count on first init (see the task brief).

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out experiments/dryrun
  python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k \
      --mesh multi --method dml          # clients = pods
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.core import distributed as dml
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (decode_window, make_decode_step,
                                make_prefill_step, make_train_step)
from repro.optim import AdamWConfig

DTYPE_BYTES = {
    "pred": 0.125, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8, "c64": 8,
}
COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n]*")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")
GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")


def _parse_groups(line: str):
    """Replica groups as a list of id-lists (both HLO formats), or None."""
    m = GROUPS_IOTA_RE.search(line)
    if m:
        import numpy as _np
        g, n = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        arr = _np.arange(int(_np.prod(dims))).reshape(dims)
        if m.group(4):
            arr = arr.transpose([int(d) for d in m.group(4).split(",")])
        return arr.reshape(g, n).tolist()
    m = GROUPS_LIST_RE.search(line)
    if m:
        out = []
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in
                   grp.replace("{", "").replace("}", "").split(",")
                   if x.strip()]
            if ids:
                out.append(ids)
        return out or None
    m = SOURCE_TARGET_RE.search(line)
    if m:
        ids = [int(x) for x in re.findall(r"\d+", m.group(1))]
        return [list(p) for p in zip(ids[::2], ids[1::2])]
    return None


def _pod_class(line: str, pod_stride: int) -> str:
    """'intra' (groups within one pod), 'pod_axis' (groups vary ONLY in pod
    index — the client-axis traffic), or 'mixed' (spanning both)."""
    groups = _parse_groups(line)
    if not groups:
        return "intra"
    crosses = any(i // pod_stride != g[0] // pod_stride
                  for g in groups for i in g)
    if not crosses:
        return "intra"
    pure = all(len({i % pod_stride for i in g}) == 1 for g in groups)
    return "pod_axis" if pure else "mixed"


def _type_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str, pod_stride: int = 256) -> Dict[str, float]:
    """Per-device bytes by collective kind, parsed from partitioned HLO.
    ``cross_pod`` separates traffic whose replica groups span pods — the
    client-axis (DCN-class) traffic the paper's bandwidth claim is about."""
    out: Dict[str, float] = {"all-gather": 0.0, "all-reduce": 0.0,
                             "reduce-scatter": 0.0, "all-to-all": 0.0,
                             "collective-permute": 0.0, "count": 0,
                             "cross_pod": 0.0, "pod_axis": 0.0}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        b = _type_bytes(type_str)
        out[kind] += b
        out["count"] += 1
        cls = _pod_class(m.group(0), pod_stride)
        if cls != "intra":
            out["cross_pod"] += b
        if cls == "pod_axis":
            out["pod_axis"] += b
    out["total"] = sum(v for k, v in out.items()
                       if k not in ("count", "total", "cross_pod", "pod_axis"))
    return out


def _shardings(tree_specs, tree_axes, mesh):
    def leaf(ax, sd):
        return jax.NamedSharding(
            mesh, shd.logical_to_spec(tuple(ax), mesh, sd.shape))
    return jax.tree.map(
        leaf, tree_axes, tree_specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def _mem_record(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "peak_bytes": (ma.argument_size_in_bytes + ma.temp_size_in_bytes +
                       ma.output_size_in_bytes),
    }


# ---------------------------------------------------------------------------
# case builders: return (fn, args, in_shardings)

def _case_train(cfg, shape, mesh, unroll=False, ce_impl="dense",
                remat=True, slot_remat=False):
    opt_cfg = AdamWConfig()
    step = make_train_step(cfg, opt_cfg, unroll=unroll, ce_impl=ce_impl,
                           remat=remat, slot_remat=slot_remat)
    p_specs, p_axes = S.model_state_specs(cfg)
    o_specs = S.opt_state_specs(p_specs)
    o_axes = S.opt_logical_axes(p_axes)
    b_specs, b_axes = S.batch_inputs(cfg, shape)
    args = [p_specs, o_specs, b_specs["tokens"]]
    shards = [_shardings(p_specs, p_axes, mesh),
              _shardings(o_specs, o_axes, mesh),
              _shardings(b_specs, b_axes, mesh)["tokens"]]
    if cfg.prefix_tokens:
        args.append(b_specs["prefix"])
        shards.append(_shardings(b_specs, b_axes, mesh)["prefix"])
    return step, tuple(args), tuple(shards)


def _case_prefill(cfg, shape, mesh, unroll=False):
    window = decode_window(cfg, shape)
    step = make_prefill_step(cfg, max_seq=shape.seq_len, window=window,
                             unroll=unroll)
    p_specs, p_axes = S.model_state_specs(cfg)
    b_specs, b_axes = S.batch_inputs(cfg, shape)
    args = [p_specs, b_specs["tokens"]]
    shards = [_shardings(p_specs, p_axes, mesh),
              _shardings(b_specs, b_axes, mesh)["tokens"]]
    if cfg.prefix_tokens:
        args.append(b_specs["prefix"])
        shards.append(_shardings(b_specs, b_axes, mesh)["prefix"])
    return step, tuple(args), tuple(shards)


def _case_decode(cfg, shape, mesh, unroll=False):
    window = decode_window(cfg, shape)
    step = make_decode_step(cfg, window=window, unroll=unroll)
    p_specs, p_axes = S.model_state_specs(cfg)
    c_specs, c_axes = S.cache_specs(cfg, shape)
    token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    args = (p_specs, token, c_specs, pos)
    shards = (_shardings(p_specs, p_axes, mesh),
              jax.NamedSharding(mesh, shd.logical_to_spec(
                  ("batch", None), mesh, token.shape)),
              _shardings(c_specs, c_axes, mesh),
              jax.NamedSharding(mesh, shd.logical_to_spec((), mesh)))
    return step, args, shards


def _case_dml(cfg, shape, mesh, n_clients=2, fused=True, unroll=False,
              sparse_k=0):
    """The paper's technique on the mesh: clients = pod axis."""
    opt_cfg = AdamWConfig()
    step = (dml.make_dml_train_step(cfg, opt_cfg, unroll=unroll,
                                    sparse_k=sparse_k,
                                    spmd_client_axis="pod") if fused
            else dml.make_mutual_step(cfg, opt_cfg, unroll=unroll,
                                      sparse_k=sparse_k,
                                      spmd_client_axis="pod"))
    p_one, p_axes_one = S.model_state_specs(cfg)
    p_specs = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct((n_clients,) + sd.shape, sd.dtype),
        p_one)
    p_axes = jax.tree.map(
        lambda t: ("client",) + t, p_axes_one,
        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict))
    o_specs = S.opt_state_specs(p_specs)
    o_axes = S.opt_logical_axes(p_axes)
    pub_b = max(1, shape.global_batch // (4 * n_clients))
    pub_specs, pub_axes = S.public_inputs(cfg, shape, pub_b)
    args = [p_specs, o_specs]
    shards = [_shardings(p_specs, p_axes, mesh),
              _shardings(o_specs, o_axes, mesh)]
    if fused:
        b_specs, b_axes = S.batch_inputs(cfg, shape, n_clients=n_clients)
        args.append(b_specs["tokens"])
        shards.append(_shardings(b_specs, b_axes, mesh)["tokens"])
    args.append(pub_specs["public_tokens"])
    shards.append(_shardings(pub_specs, pub_axes, mesh)["public_tokens"])
    if cfg.prefix_tokens:
        # signature order: (..., tokens, public_tokens, prefix, public_prefix)
        if fused:
            args.append(b_specs["prefix"])
            shards.append(_shardings(b_specs, b_axes, mesh)["prefix"])
        args.append(pub_specs["public_prefix"])
        shards.append(_shardings(pub_specs, pub_axes, mesh)["public_prefix"])
    return step, tuple(args), tuple(shards)


def _case_fedavg_sync(cfg, shape, mesh, n_clients=2, unroll=False):
    """Baseline collective: all-reduce(params) over the client/pod axis."""
    p_one, p_axes_one = S.model_state_specs(cfg)
    p_specs = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct((n_clients,) + sd.shape, sd.dtype),
        p_one)
    p_axes = jax.tree.map(
        lambda t: ("client",) + t, p_axes_one,
        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict))
    return dml.fedavg_sync, (p_specs,), (_shardings(p_specs, p_axes, mesh),)


def build_case(cfg, shape, mesh, method: str, unroll: bool = False,
               variant: str = "baseline"):
    ce_impl = "chunked" if "chunked_ce" in variant else "dense"
    remat = "noremat" not in variant
    slot_remat = "slotremat" in variant
    if method == "standard":
        if shape.kind == "train":
            return _case_train(cfg, shape, mesh, unroll, ce_impl=ce_impl,
                               remat=remat, slot_remat=slot_remat)
        if shape.kind == "prefill":
            return _case_prefill(cfg, shape, mesh, unroll)
        return _case_decode(cfg, shape, mesh, unroll)
    sparse_k = 64 if "sparse" in variant else 0
    if method == "dml":
        return _case_dml(cfg, shape, mesh, fused=True, unroll=unroll,
                         sparse_k=sparse_k)
    if method == "mutual":
        return _case_dml(cfg, shape, mesh, fused=False, unroll=unroll,
                         sparse_k=sparse_k)
    if method == "fedavg_sync":
        return _case_fedavg_sync(cfg, shape, mesh)
    raise ValueError(method)


# ---------------------------------------------------------------------------

def cost_dict(compiled) -> Dict[str, float]:
    """compiled.cost_analysis() across jax versions (list-of-dicts before 0.6)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def _costs(compiled) -> Dict[str, float]:
    cost = cost_dict(compiled)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": collective_stats(compiled.as_text())}


def _lower_compile(step, args, in_shardings, mesh):
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_shardings).lower(*args)
        return lowered.compile()


def depth_corrected_costs(cfg, shape, mesh, method,
                          variant: str = "baseline") -> Dict[str, Any]:
    """XLA's cost analysis counts a scan body ONCE regardless of trip count,
    so the scanned lowering under-counts per-layer work.  We therefore lower
    two small UNROLLED variants (1 and 2 periods) and extrapolate:

        X_total = X(1) + (n_periods - 1) * (X(2) - X(1))

    which is exact for depth-linear quantities (flops, bytes, collective
    traffic): X(1) carries the embed/head/optimizer constant term.
    """
    P = len(cfg.period)
    cost = {}
    for tag, depth in (("d1", P), ("d2", 2 * P)):
        cc = cfg.replace(n_layers=depth)
        step, args, shards = build_case(cc, shape, mesh, method, unroll=True,
                                        variant=variant)
        compiled = _lower_compile(step, args, shards, mesh)
        cost[tag] = _costs(compiled)
    n = cfg.n_periods
    out: Dict[str, Any] = {}
    for key in ("flops", "bytes"):
        d = max(cost["d2"][key] - cost["d1"][key], 0.0)
        out[key] = cost["d1"][key] + (n - 1) * d
    coll = {}
    for k in cost["d1"]["coll"]:
        d = max(cost["d2"]["coll"][k] - cost["d1"]["coll"][k], 0)
        coll[k] = cost["d1"]["coll"][k] + (n - 1) * d
    out["coll"] = coll
    return out


def model_flops_estimate(cfg, shape, method: str = "standard") -> float:
    """Useful model FLOPs for one step of (cfg, shape, method).

    The classic parameter-FLOP model: a forward pass costs 2·N·D (N =
    active params, D = tokens) and training costs 6·N·D — forward AND
    backward, since every kernel on the hot path (attention, SSD,
    mutual-KL) now carries a custom VJP and trains through the same impl
    it runs forward.  Decode shapes process one token per step; the DML /
    mutual methods add the public-batch mutual phase (trained, so 6·N·D)
    for k = 2 clients; fedavg_sync moves no tokens at all.
    """
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind != "decode" else 1)
    if method == "fedavg_sync":
        tokens = 0
    active = cfg.active_param_count()
    flops_per_tok = 6 * active if shape.kind == "train" else 2 * active
    model_flops = float(flops_per_tok) * tokens
    if method in ("dml", "mutual"):
        k = 2
        pub = max(1, shape.global_batch // (4 * k)) * shape.seq_len
        extra = 6.0 * active * pub * k        # mutual phase is trained
        model_flops = (model_flops if method == "dml" else 0.0) + extra
    return model_flops


def run_case(arch: str, shape_name: str, mesh_kind: str,
             method: str = "standard", verbose: bool = True,
             skip_depth_correction: bool = False,
             variant: str = "baseline") -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "method": method, "chips": n_chips, "status": "ok",
        "variant": variant,
    }
    # client modes: the pod axis belongs to the clients, not the batch
    rules = ({"batch": ("data",), "attn_batch": ("data",)}
             if method in ("dml", "mutual", "fedavg_sync") else {})
    if "attn_dp" in variant:
        # reshard attention over model axis too (heads-indivisible archs)
        rules["attn_batch"] = (rules.get("attn_batch", ("pod", "data"))
                               + ("model",))
    if "no_fsdp" in variant:
        rules["embed"] = None          # replicate params over the data axis
    if "seqpar" in variant:
        rules["res_seq"] = "model"     # sequence-parallel residual stream
    try:
        # 1) the REAL deliverable: the full scanned program must lower+compile
        from repro.kernels import ops as kops
        attn_impl = "xla_flash" if "flash" in variant else "ref"
        with shd.axis_rules(rules), kops.use_impl(attn_impl):
            step, args, in_shardings = build_case(cfg, shape, mesh, method,
                                                  variant=variant)
            compiled = _lower_compile(step, args, in_shardings, mesh)
        rec.update(_mem_record(compiled))
        rec["collectives_scanned"] = collective_stats(compiled.as_text())

        # 2) depth-corrected flops/bytes/collectives for the roofline
        with shd.axis_rules(rules), kops.use_impl(attn_impl):
            if method == "fedavg_sync" or skip_depth_correction:
                costs = _costs(compiled)
            else:
                costs = depth_corrected_costs(cfg, shape, mesh, method,
                                              variant)
        rec["flops_per_device"] = costs["flops"]
        rec["bytes_per_device"] = costs["bytes"]
        rec["collectives"] = costs["coll"]

        # 3) roofline terms (seconds) — the shared three-term model
        from repro.analysis.roofline import roofline_terms
        rl = roofline_terms(rec["flops_per_device"], rec["bytes_per_device"],
                            rec["collectives"]["total"])
        rec.update({k: rl[k] for k in ("t_compute", "t_memory",
                                       "t_collective", "dominant")})

        # 4) useful-FLOP ratio (2ND forward, 6ND fwd+bwd — see the helper)
        model_flops = model_flops_estimate(cfg, shape, method)
        rec["model_flops"] = model_flops
        total_hlo = rec["flops_per_device"] * n_chips
        rec["useful_flop_ratio"] = model_flops / total_hlo if total_hlo else 0.0
        rec["compile_s"] = time.time() - t0
    except Exception as e:  # noqa: BLE001 — a failed case is a bug to record
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
        rec["compile_s"] = time.time() - t0
    if verbose:
        if rec["status"] == "ok":
            print(f"[ok] {arch} {shape_name} {mesh_kind} {method} "
                  f"({rec['compile_s']:.0f}s) dominant={rec['dominant']} "
                  f"tc={rec['t_compute']:.4f} tm={rec['t_memory']:.4f} "
                  f"tx={rec['t_collective']:.4f} "
                  f"useful={rec['useful_flop_ratio']:.2f} "
                  f"peakGB={rec['peak_bytes']/2**30:.1f}", flush=True)
        else:
            print(f"[FAIL] {arch} {shape_name} {mesh_kind} {method} "
                  f"({rec['compile_s']:.0f}s) err={rec['error'][:160]}",
                  flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--method", default="standard",
                    choices=["standard", "dml", "mutual", "fedavg_sync"])
    ap.add_argument("--all", action="store_true",
                    help="baseline sweep: every arch x shape on --mesh")
    ap.add_argument("--variant", default="baseline",
                    help="optimisation variant: baseline | chunked_ce | "
                         "flash | chunked_ce+flash | noremat ...")
    ap.add_argument("--out", default=None, help="JSONL output path")
    args = ap.parse_args(argv)

    records = []
    if args.all:
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                records.append(run_case(arch, shape_name, args.mesh,
                                        args.method, variant=args.variant))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        records.append(run_case(args.arch, args.shape, args.mesh,
                                args.method, variant=args.variant))

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    bad = [r for r in records if r["status"] != "ok"]
    print(f"\n{len(records) - len(bad)}/{len(records)} cases lowered+compiled")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
