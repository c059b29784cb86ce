"""jit-friendly kernel entry points with a runtime impl switch.

impl values:
  - "ref":       pure-jnp oracle (XLA-native; used by the dry-run so roofline
                 numbers reflect the compiler's own schedule)
  - "interpret": Pallas kernel body interpreted on CPU (correctness tests)
  - "pallas":    compiled Pallas TPU kernel (the production target)

Default comes from REPRO_KERNEL_IMPL or "ref"; tests/tools may override
per-scope with ``use_impl("interpret")``.

Production call sites do NOT rely on this ambient state: populations resolve
an impl once at construction (``resolve_impl``) and thread it through the
step factories as a plain argument.  ``use_impl`` exists for tests and the
dry-run only — the old thread-local version leaked inside jitted traces
(``lax.map`` chunking dispatches the body on worker threads that never saw
the override and silently fell back to the env default), so the override is
now a module-global set/restored by the context manager.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kl_mutual import kl_mutual as _kl_mutual_pallas
from repro.kernels.kl_mutual import kl_mutual_pair as _kl_mutual_pair
from repro.kernels.sparse_kl import sparse_kl_topk as _sparse_kl_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

IMPLS = ("ref", "interpret", "pallas", "xla_flash")

_override: Optional[str] = None


def _check_impl(impl: str) -> str:
    """Every ops.* entry point funnels through here: an impl string that is
    not in ``IMPLS`` is a config bug, never a silent fallback."""
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one "
                         f"of {IMPLS}")
    return impl


def get_impl() -> str:
    return _override or os.environ.get("REPRO_KERNEL_IMPL", "ref")


def set_impl(impl: str) -> None:
    global _override
    _check_impl(impl)
    _override = impl


@contextlib.contextmanager
def use_impl(impl: str):
    """Scoped ambient override — TESTS AND TOOLING ONLY (see module doc)."""
    global _override
    old = _override
    set_impl(impl)
    try:
        yield
    finally:
        _override = old


def resolve_impl(impl: Optional[str] = None) -> str:
    """Resolve the kernel-impl policy ONCE, at construction time.

    Priority: explicit value > backend default — ``pallas`` when running
    on TPU, ``ref`` (the XLA-native oracle graph) everywhere else.
    ``None``/"auto" defers to the backend.  Off the TPU,
    ``REPRO_KERNEL_IMPL`` overrides the default; on the TPU it may only
    name ``pallas``, and any other value raises instead of silently
    running the oracle or the interpreter in place of the kernels.  The
    resolved string is what populations bake into their jit caches and
    pass down the step factories, so the hot path never reads ambient
    state.
    """
    if impl and impl != "auto":
        return _check_impl(impl)
    import jax
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if jax.default_backend() == "tpu":
        if env and env != "pallas":
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} would replace the compiled "
                "Pallas kernels on the TPU; unset it, or pass the impl "
                "explicitly where a reference run is meant")
        return "pallas"
    return _check_impl(env) if env else "ref"


# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              positions_q=None, positions_k=None, impl: Optional[str] = None):
    """(B, S, H, hd)-layout attention dispatching to flash kernel or oracle.

    Explicit positions (the decode/cache path) always use the oracle — the
    flash kernel serves the self-attention train/prefill hot path.
    DIFFERENTIABLE on every impl: the flash kernel carries a custom VJP
    (streamed recompute backward), so training steps run the same impl
    forward and backward — there is no grad-time downgrade.
    """
    impl = _check_impl(impl or get_impl())
    if positions_q is not None or positions_k is not None:
        # decode/cache path: explicit positions -> oracle
        return ref.attention(q, k, v, causal=causal, window=window,
                             positions_q=positions_q, positions_k=positions_k)
    if impl == "ref":
        return ref.attention(q, k, v, causal=causal, window=window)
    if impl == "xla_flash":
        return ref.attention_xla_flash(q, k, v, causal=causal, window=window)
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          interpret=(impl == "interpret"))
    return out.transpose(0, 2, 1, 3)


def mutual_kl(logits, *, temperature: float = 1.0, impl: Optional[str] = None):
    """(K, B, V) -> (K, B) average pairwise KL (paper Eq. 2)."""
    impl = _check_impl(impl or get_impl())
    if impl == "ref":
        return ref.mutual_kl(logits, temperature=temperature)
    return _kl_mutual_pallas(logits, temperature=temperature,
                             interpret=(impl == "interpret"))


def mutual_kl_pair(live, fixed, pair_w, *, temperature: float = 1.0,
                   impl: Optional[str] = None):
    """Pair-weighted rectangular Eq. 2: (Kl, B, V) live x (Kg, B, V) fixed
    with (Kl, Kg) weights -> (Kl, B).  DIFFERENTIABLE: kernel impls carry
    a custom VJP whose backward streams over vocab blocks; 'ref' is the
    plain-JAX oracle graph (AD-derived gradients).  The Eq.-2 training
    hot path — ``core.mutual.mutual_kl_terms`` routes here."""
    impl = _check_impl(impl or get_impl())
    if impl == "ref":
        return ref.mutual_kl_pair(live, fixed, pair_w,
                                  temperature=temperature)
    return _kl_mutual_pair(live, fixed, pair_w, temperature=temperature,
                           interpret=(impl == "interpret"))


def sparse_mutual_kl(live, idx, logp_top, pair_w, *,
                     temperature: float = 1.0, impl: Optional[str] = None):
    """Pair-weighted Eq. 2 against RECEIVED sparse (top-k) predictions.

    live (Kl, B, V) x idx/logp_top (J, B, k) with (Kl, J) weights ->
    (Kl, B).  DIFFERENTIABLE on the live side: kernel impls fuse the top-k
    gather with a streaming softmax/entropy pass (``kernels.sparse_kl``)
    and carry a custom VJP whose backward streams over vocab blocks; 'ref'
    is the plain-JAX oracle graph (AD-derived gradients).  The SparseDML
    combine hot path — ``core.mutual.sparse_mutual_kl_loss`` and
    ``core.mutual.sparse_kl_to_received`` route here."""
    impl = _check_impl(impl or get_impl())
    if impl == "ref":
        return ref.sparse_kl_pair(live, idx, logp_top, pair_w,
                                  temperature=temperature)
    return _sparse_kl_pallas(live, idx, logp_top, pair_w,
                             temperature=temperature,
                             interpret=(impl == "interpret"))


def ssd(x, dt, A, B_mat, C_mat, *, chunk: int = 256, initial_state=None,
        impl: Optional[str] = None):
    """Mamba2 SSD scan -> (y, final_state).

    DIFFERENTIABLE on every impl: the Pallas kernel carries a custom VJP
    (chunked reverse-scan backward).  ``initial_state`` continuation (the
    decode/cache path) always uses the oracle.
    """
    impl = _check_impl(impl or get_impl())
    # "xla_flash" is an attention-only variant; SSD has no XLA-flash
    # formulation, so that (VALID, documented) policy runs the oracle here
    if impl in ("ref", "xla_flash") or initial_state is not None:
        return ref.ssd(x, dt, A, B_mat, C_mat, chunk=chunk,
                       initial_state=initial_state)
    return _ssd_pallas(x, dt, A, B_mat, C_mat, chunk=chunk,
                       interpret=(impl == "interpret"))
