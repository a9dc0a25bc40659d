"""Discrete Fourier transforms — ``paddle.fft`` surface.

TPU-native rebuild of the reference's fft tower (public API
``python/paddle/fft.py:175-1427``, C++ kernels ``paddle/phi/kernels/funcs/fft.h``
via pocketfft/cuFFT): here every transform lowers to ``jnp.fft`` so XLA emits the
FFT HLO directly; autograd rides the tape dispatcher like every other op.

Norm semantics match the reference (and numpy): "backward" (default), "ortho",
"forward". The helper ``fft_c2c/r2c/c2r`` internal names from the reference
collapse into the jnp calls.
"""
from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.core.autograd import apply
from paddle_tpu.ops.common import ensure_tensor

__all__ = [
    "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
    "fft2", "ifft2", "rfft2", "irfft2", "hfft2", "ihfft2",
    "fftn", "ifftn", "rfftn", "irfftn", "hfftn", "ihfftn",
    "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]

_NORMS = ("backward", "ortho", "forward")


def _check_norm(norm):
    if norm not in _NORMS:
        raise ValueError(
            f"Unexpected norm: {norm!r}. Norm should be 'forward', 'backward' or 'ortho'"
        )
    return norm


def _wrap1(jfn, name):
    def op(x, n=None, axis=-1, norm="backward", name=None):
        _check_norm(norm)
        x = ensure_tensor(x)
        return apply(lambda a: jfn(a, n=n, axis=axis, norm=norm), x,
                              op_name=name)

    op.__name__ = name
    op.__doc__ = f"1-D ``{name}`` (paddle.fft.{name}; ref python/paddle/fft.py)."
    return op


def _wrapn(jfn, name):
    def op(x, s=None, axes=None, norm="backward", name=None):
        _check_norm(norm)
        x = ensure_tensor(x)
        return apply(lambda a: jfn(a, s=s, axes=axes, norm=norm), x,
                              op_name=name)

    op.__name__ = name
    op.__doc__ = f"N-D ``{name}`` (paddle.fft.{name}; ref python/paddle/fft.py)."
    return op


def _wrap2(jfn, name):
    def op(x, s=None, axes=(-2, -1), norm="backward", name=None):
        _check_norm(norm)
        x = ensure_tensor(x)
        if axes is not None and len(axes) != 2:
            raise ValueError(f"{name} expects exactly 2 axes, got {axes}")
        return apply(lambda a: jfn(a, s=s, axes=axes, norm=norm), x,
                              op_name=name)

    op.__name__ = name
    op.__doc__ = f"2-D ``{name}`` (paddle.fft.{name}; ref python/paddle/fft.py:877-1243)."
    return op


fft = _wrap1(jnp.fft.fft, "fft")
ifft = _wrap1(jnp.fft.ifft, "ifft")
rfft = _wrap1(jnp.fft.rfft, "rfft")
irfft = _wrap1(jnp.fft.irfft, "irfft")
hfft = _wrap1(jnp.fft.hfft, "hfft")
ihfft = _wrap1(jnp.fft.ihfft, "ihfft")

fftn = _wrapn(jnp.fft.fftn, "fftn")
ifftn = _wrapn(jnp.fft.ifftn, "ifftn")
rfftn = _wrapn(jnp.fft.rfftn, "rfftn")
irfftn = _wrapn(jnp.fft.irfftn, "irfftn")

fft2 = _wrap2(jnp.fft.fftn, "fft2")
ifft2 = _wrap2(jnp.fft.ifftn, "ifft2")
rfft2 = _wrap2(jnp.fft.rfftn, "rfft2")
irfft2 = _wrap2(jnp.fft.irfftn, "irfft2")


def _hfftn(a, s=None, axes=None, norm="backward"):
    # hfftn = irfftn of the conjugate with "inverse" normalization flipped;
    # numpy has no hfftn — compose it the way the reference's fftn_c2r does
    # (python/paddle/fft.py:781).
    if axes is None:
        axes = tuple(range(a.ndim))
    inv = {"backward": "forward", "forward": "backward", "ortho": "ortho"}[norm]
    return jnp.fft.irfftn(jnp.conj(a), s=s, axes=axes, norm=inv)


def _ihfftn(a, s=None, axes=None, norm="backward"):
    if axes is None:
        axes = tuple(range(a.ndim))
    inv = {"backward": "forward", "forward": "backward", "ortho": "ortho"}[norm]
    return jnp.conj(jnp.fft.rfftn(a, s=s, axes=axes, norm=inv))


hfftn = _wrapn(_hfftn, "hfftn")
ihfftn = _wrapn(_ihfftn, "ihfftn")
hfft2 = _wrap2(_hfftn, "hfft2")
ihfft2 = _wrap2(_ihfftn, "ihfft2")


def fftfreq(n, d=1.0, dtype=None, name=None):
    """Sample frequencies for ``fft`` output bins (paddle.fft.fftfreq)."""
    out = jnp.fft.fftfreq(int(n), d=float(d))
    if dtype is not None:
        out = out.astype(dtype)
    return Tensor(out, _internal=True)


def rfftfreq(n, d=1.0, dtype=None, name=None):
    """Sample frequencies for ``rfft`` output bins (paddle.fft.rfftfreq)."""
    out = jnp.fft.rfftfreq(int(n), d=float(d))
    if dtype is not None:
        out = out.astype(dtype)
    return Tensor(out, _internal=True)


def fftshift(x, axes=None, name=None):
    """Shift the zero-frequency component to the center (paddle.fft.fftshift)."""
    x = ensure_tensor(x)
    return apply(lambda a: jnp.fft.fftshift(a, axes=axes), x, op_name="fftshift")


def ifftshift(x, axes=None, name=None):
    """Inverse of ``fftshift`` (paddle.fft.ifftshift)."""
    x = ensure_tensor(x)
    return apply(lambda a: jnp.fft.ifftshift(a, axes=axes), x, op_name="ifftshift")
