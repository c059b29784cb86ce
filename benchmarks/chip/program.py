"""The system under test, as the harness drives it: a ``repro.api``
``Federation`` over ``LMClients``.  This is the only module of the
benchmark that imports the program.

What the harness takes from the program: the population and session
objects, the round call ``Federation.run``, the client state they hold
(weights and AdamW moments) and the round metrics in its history.  The
weights are the benchmark's: drawn from the seed by the reference family's
``init`` and written over the ones the population drew at construction.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import DML, Federation, LMClients, SparseDML
from repro.configs import get_config, get_reduced
from repro.launch import use_compile_cache  # noqa: F401  (re-exported)
from repro.launch.mesh import make_client_mesh
from repro.optim import cosine_schedule

from .reference.dml import clip_scope, client_key

# the AdamW schedule's length: far past any window, so that it only sets
# the cosine's horizon (``traffic["optimizer"]["total_steps"]``)
ROUNDS = 1_000_000


def model_config(config: dict):
    """The program's configuration object for a configuration file: the
    registry entry (its ``reduced`` preset where the file asks for it, as
    the CPU tests do) with the file's replaced keys."""
    prog = config["program"]
    get = get_reduced if prog.get("preset") == "reduced" else get_config
    return get(prog["registry"]).replace(**prog.get("replace", {}))


def strategy(traffic: dict):
    s = traffic["strategy"]
    if s["name"] == "dml":
        return DML(kl_weight=s.get("kl_weight", 1.0))
    if s["name"] == "sparse-dml":
        return SparseDML(k=s["k"], kl_weight=s.get("kl_weight", 1.0))
    raise ValueError(f"unknown strategy {s['name']!r}")


def optimizer_of(pop) -> dict:
    """The population's AdamW settings under the traffic file's names.
    Its clip scope is that of the round programs the benchmark runs: on a
    mesh ``make_sharded_dml_step`` clips each client's gradient to its own
    norm, without one the fused round clips the whole fleet's to one."""
    o = pop.opt_cfg
    if o.schedule != "cosine":
        raise ValueError(f"the population's schedule is {o.schedule!r}")
    final = inspect.signature(cosine_schedule).parameters["final_frac"]
    return {"name": "adamw", "lr": o.lr, "warmup": o.warmup,
            "total_steps": o.total_steps, "final_frac": final.default,
            "b1": o.b1, "b2": o.b2, "eps": o.eps,
            "weight_decay": o.weight_decay, "clip_norm": o.clip_norm,
            "clip_scope": "per_client" if pop.mesh is not None else "fleet"}


def build(config: dict, traffic: dict, seed: int, chips: int):
    """The population and its session, as the window drives them.  The
    population must run the optimizer the traffic file states, which the
    reference reads from it."""
    mesh = make_client_mesh(chips) if chips > 1 else None
    pop = LMClients(model_config(config), n_clients=traffic["clients"],
                    rounds=ROUNDS, batch=traffic["batch"], seq=traffic["seq"],
                    lr=traffic["optimizer"]["lr"], seed=seed, mesh=mesh)
    runs = optimizer_of(pop)
    states = dict(traffic["optimizer"],
                  clip_scope=clip_scope(traffic["optimizer"]))
    if runs != states:
        raise ValueError(f"the population runs the optimizer {runs}, the "
                         f"traffic file states {states}")
    return pop, Federation(pop, strategy(traffic))


def weight_maker(family, config: dict, traffic: dict, like):
    """A jitted seed -> the whole fleet's weights in the program's layout,
    dtypes and placement (those of ``like``)."""
    K = traffic["clients"]
    cfg = config
    shardings = jax.tree.map(lambda x: x.sharding, like)
    dtypes = jax.tree.map(lambda x: x.dtype, like)

    def make(keys):
        tree = jax.vmap(lambda k: family.to_program(family.init(k, cfg)))(keys)
        return jax.tree.map(lambda x, d: x.astype(d), tree, dtypes)

    jitted = jax.jit(make, out_shardings=shardings)

    def for_seed(seed: int):
        keys = jnp.stack([client_key(seed, c) for c in range(K)])
        return jitted(keys)
    return for_seed


def install_weights(pop, make_weights, seed: int) -> None:
    """Replace the population's weights with the benchmark's, freeing the
    old ones first; their tree, shapes and dtypes must agree."""
    old = pop.client_params
    want = jax.tree.map(lambda x: (x.shape, x.dtype), old)
    pop.client_params = None
    del old
    new = make_weights(seed)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), new)
    if got != want:
        raise ValueError(f"benchmark weights {got} do not match the "
                         f"program's {want}")
    pop.client_params = new


def host_norms(family, tree, minus=None) -> np.ndarray:
    """(K, n_leaves) float32 norms of each client's leaves, in the
    reference layout's order, of a fleet's tree (minus another) copied to
    the host: nothing of it takes device memory."""
    tree = jax.device_get(tree)
    K = jax.tree.leaves(tree)[0].shape[0]
    rows = []
    for c in range(K):
        def leaves(t):
            return jax.tree.leaves(family.from_program(
                jax.tree.map(lambda x: x[c], t)))
        now = leaves(tree)
        base = leaves(minus) if minus is not None else [None] * len(now)
        row = []
        for a, b in zip(now, base):
            x = np.asarray(a, np.float32).ravel()
            if b is not None:
                x = x - np.asarray(b, np.float32).ravel()
            row.append(np.sqrt(np.dot(x, x)))
        rows.append(row)
    return np.asarray(rows, np.float32)


def round_metrics(history, r: int) -> np.ndarray:
    """(K, 3): private CE, public CE and KLD_avg of round r, as logged."""
    rl = history.rounds[r]
    return np.stack([np.asarray(rl.client_loss, np.float64),
                     np.asarray(rl.public_ce, np.float64),
                     np.asarray(rl.kl_loss, np.float64)], axis=1)
