"""Compile-or-interpret rule shared by the authored Pallas kernels."""
from __future__ import annotations


def default_interpret() -> bool:
    """True when ``pallas_call`` must run in the interpreter: on every
    platform but a TPU, where it is a parity tool for the CPU tests. On a
    TPU the kernels compile through Mosaic, and one that cannot is an
    error at its call site, not a fallback."""
    import jax
    return jax.default_backend() != "tpu"
