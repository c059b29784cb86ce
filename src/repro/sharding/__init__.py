"""Logical-axis sharding rules.

Models annotate activations/params with *logical* axis names
(``batch``, ``heads``, ``ff``, ``vocab``, ``expert``, ``client`` ...); this
module maps them to physical mesh axes and produces PartitionSpecs.  The map
is swappable (hillclimbing changes it without touching model code).

Physical mesh axes:
  - single-pod: ("data", "model")
  - multi-pod:  ("pod", "data", "model")  -- "pod" doubles as the FL client axis
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Default logical->physical rules.  ``fsdp`` shards params over the data axis
# (ZeRO-3 style); ``tensor`` is megatron tensor parallel.
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "batch": ("pod", "data"),     # standard mode: pure DP across pods
    "attn_batch": ("pod", "data"),  # attention activations; hillclimb remaps
    # FL client axis: a dedicated 1-D "clients" mesh (launch.mesh
    # .make_client_mesh — the shard_map'ed round engines) when present,
    # else the multi-pod "pod" axis; remapped in tests
    "client": ("clients", "pod"),
    "seq": None,
    "res_seq": None,     # residual-stream seq dim; "seqpar" variant -> model
    "kv_seq": "model",        # decode KV-cache sequence sharding when heads < tp
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "embed": "data",          # FSDP: param d_model dim over data
    "embed_act": None,        # activation d_model dim stays unsharded
    "ff": "model",
    "vocab": "model",
    "expert": "model",
    "expert_ff": None,
    "conv": None,
    "state": None,
    "layers": None,
}

_local = threading.local()


def get_rules() -> Dict[str, Optional[str]]:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: Dict[str, Optional[str]]):
    """Override logical->physical mapping (e.g. tests map client->data)."""
    old = get_rules()
    _local.rules = {**old, **rules}
    try:
        yield
    finally:
        _local.rules = old


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def logical_to_spec(logical: Tuple[Optional[str], ...], mesh=None,
                    shape: Optional[Tuple[int, ...]] = None) -> P:
    """Map logical axis names to a PartitionSpec valid on ``mesh``.

    Axes not in the rules / not on the mesh / not dividing the dim size are
    dropped (replicated).  Duplicate physical axes keep first occurrence.
    """
    rules = get_rules()
    sizes = _mesh_axis_sizes(mesh) if mesh is not None else None
    used = set()
    out = []
    for i, name in enumerate(logical):
        phys = rules.get(name) if name is not None else None
        if phys is None:
            out.append(None)
            continue
        cand = (phys,) if isinstance(phys, str) else tuple(phys)
        # keep axes that exist on the mesh and are not already used
        cand = tuple(a for a in cand
                     if (sizes is None or a in sizes) and a not in used)
        if sizes is not None and shape is not None:
            # greedy prefix whose product divides the dim size
            kept = []
            prod = 1
            for a in cand:
                if shape[i] % (prod * sizes[a]) == 0:
                    kept.append(a)
                    prod *= sizes[a]
            cand = tuple(kept)
        if not cand:
            out.append(None)
            continue
        used.update(cand)
        out.append(cand[0] if len(cand) == 1 else cand)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def current_mesh():
    """The ambient (abstract) mesh, or None when unsharded."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return None
    return mesh


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: the round
    engines' bodies mix per-device and replicated values by design."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with every axis of type Auto: what no constraint
    pins is sharded by the compiler's propagation."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(tuple(logical), mesh, x.shape)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def named_sharding(mesh, *logical, shape=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(tuple(logical), mesh, shape))


def spec_tree_like(logical_tree, mesh, shape_tree):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda log, sd: NamedSharding(mesh, logical_to_spec(log, mesh, sd.shape)),
        logical_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )
