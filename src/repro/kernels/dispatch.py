"""Kernel-backend dispatch: one switch for every fused-kernel call site.

The repo carries two implementations of each hot op — a Pallas TPU
kernel and a pure-jnp oracle.  Which one runs is a *deployment* choice,
not something each call site should re-derive, so this module owns the
single rule:

    backend = "auto"    -> "pallas" on TPU, "jnp" everywhere else
    backend = "pallas"  -> the kernel, compiled on TPU, interpret-mode
                           (Pallas's Python emulator) elsewhere — the
                           validation configuration the kernel tests use
    backend = "jnp"     -> the jnp oracle, always

Consumed by ``kernels.ops.prism_attention_op`` (prefill),
``kernels.segment_means.segment_means_op``,
``kernels.decode_attention.flash_decode_stats`` (the serving hot path),
and plumbed through ``ServeHParams.backend`` / ``launch.serve
--backend``.  Nothing in the environment changes what ``"auto"``
resolves to: on a TPU the kernels run compiled unless a caller names
another backend.
"""
from __future__ import annotations

import jax

BACKENDS = ("auto", "pallas", "jnp")


def platform() -> str:
    """The default JAX backend platform ('tpu' | 'gpu' | 'cpu')."""
    return jax.default_backend()


def resolve_backend(backend: str | None = None) -> str:
    """'auto' (or None) -> 'pallas' on TPU / 'jnp' elsewhere; explicit
    'pallas'/'jnp' are returned as given.  Raises on anything outside
    BACKENDS."""
    if backend is None:
        backend = "auto"
    if backend not in BACKENDS:
        raise ValueError(
            f"backend {backend!r} not in {BACKENDS}")
    if backend == "auto":
        return "pallas" if platform() == "tpu" else "jnp"
    return backend


def use_pallas(backend: str | None = None) -> bool:
    return resolve_backend(backend) == "pallas"


def pallas_interpret() -> bool:
    """Whether a Pallas call must run in interpret mode: anywhere but a
    real TPU.  Forcing backend='pallas' on CPU therefore runs the kernel
    through the Pallas interpreter — slow, but the exact kernel code the
    TPU compiles, which is what the oracle tests exercise."""
    return platform() != "tpu"


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` kwarg: None means platform auto-detect
    (the old hard-coded ``interpret=True`` defaults silently ran the
    emulator on TPU — slow-by-default; this is the fix)."""
    return pallas_interpret() if interpret is None else bool(interpret)
