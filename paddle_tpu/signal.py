"""Signal processing — ``paddle.signal`` surface.

Rebuild of the reference's ``python/paddle/signal.py`` (frame :31, overlap_add
:164, stft :249, istft :424; C++ kernels ``paddle/phi/kernels/frame_kernel.h``,
``overlap_add_kernel.h``). Framing is a gather with a statically-computed index
grid — XLA turns it into an efficient strided slice; overlap_add is its
scatter-add transpose, so autograd round-trips exactly.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.core.autograd import apply
from paddle_tpu.ops.common import ensure_tensor
from paddle_tpu import fft as _fft

__all__ = ["frame", "overlap_add", "stft", "istft"]


def _frame_np_axis(a, frame_length, hop_length, axis):
    # signal axis is the last (axis=-1) or first (axis=0) per the reference API
    n = a.shape[axis]
    if frame_length > n:
        raise ValueError(
            f"Attribute frame_length should be less equal than sequence length, "
            f"but got ({frame_length}) > ({n})."
        )
    num_frames = 1 + (n - frame_length) // hop_length
    starts = jnp.arange(num_frames) * hop_length
    offsets = jnp.arange(frame_length)
    idx = starts[None, :] + offsets[:, None]          # [frame_length, num_frames]
    if axis in (-1, a.ndim - 1):
        out = jnp.take(a, idx.T, axis=-1)             # [..., num_frames, frame_length]
        return jnp.swapaxes(out, -1, -2)              # [..., frame_length, num_frames]
    elif axis == 0:
        return jnp.take(a, idx, axis=0)               # [frame_length, num_frames, ...]
    raise ValueError(f"Attribute axis should be 0 or -1, got {axis}")


def frame(x, frame_length, hop_length, axis=-1, name=None):
    """Slice a signal into overlapping frames (paddle.signal.frame; ref :31)."""
    if frame_length <= 0 or hop_length <= 0:
        raise ValueError("frame_length and hop_length should be positive")
    x = ensure_tensor(x)
    return apply(
        lambda a: _frame_np_axis(a, int(frame_length), int(hop_length), axis),
        x, op_name="frame",
    )


def _overlap_add_axis(a, hop_length, axis):
    if axis in (-1, a.ndim - 1):
        frame_length, num_frames = a.shape[-2], a.shape[-1]
        seq = (num_frames - 1) * hop_length + frame_length
        starts = jnp.arange(num_frames) * hop_length
        idx = starts[None, :] + jnp.arange(frame_length)[:, None]  # [fl, nf]
        out = jnp.zeros(a.shape[:-2] + (seq,), a.dtype)
        return out.at[..., idx].add(a)
    elif axis == 0:
        frame_length, num_frames = a.shape[0], a.shape[1]
        seq = (num_frames - 1) * hop_length + frame_length
        starts = jnp.arange(num_frames) * hop_length
        idx = starts[None, :] + jnp.arange(frame_length)[:, None]
        out = jnp.zeros((seq,) + a.shape[2:], a.dtype)
        return out.at[idx].add(a)
    raise ValueError(f"Attribute axis should be 0 or -1, got {axis}")


def overlap_add(x, hop_length, axis=-1, name=None):
    """Reconstruct a signal from overlapping frames (paddle.signal.overlap_add; ref :164)."""
    if hop_length <= 0:
        raise ValueError("hop_length should be positive")
    x = ensure_tensor(x)
    return apply(lambda a: _overlap_add_axis(a, int(hop_length), axis), x,
                 op_name="overlap_add")


def stft(x, n_fft, hop_length=None, win_length=None, window=None, center=True,
         pad_mode="reflect", normalized=False, onesided=True, name=None):
    """Short-time Fourier transform (paddle.signal.stft; ref :249).

    x: [..., seq_len] real or complex. Returns [..., n_fft(/2+1), num_frames].
    """
    x = ensure_tensor(x)
    if hop_length is None:
        hop_length = n_fft // 4
    if win_length is None:
        win_length = n_fft
    if window is not None:
        w = ensure_tensor(window).numpy()
        if w.shape != (win_length,):
            raise ValueError(f"window must have shape ({win_length},)")
    else:
        w = np.ones(win_length, np.float32)
    # center-pad the window to n_fft like the reference (:382)
    if win_length < n_fft:
        pad_l = (n_fft - win_length) // 2
        w = np.pad(w, (pad_l, n_fft - win_length - pad_l))
    w = jnp.asarray(w)
    is_complex = np.issubdtype(np.dtype(x.dtype), np.complexfloating)
    if is_complex and onesided:
        raise ValueError("onesided is not supported for complex input")

    def _stft(a):
        if center:
            pad = n_fft // 2
            widths = [(0, 0)] * (a.ndim - 1) + [(pad, pad)]
            a = jnp.pad(a, widths, mode=pad_mode)
        frames = _frame_np_axis(a, n_fft, hop_length, -1)   # [..., n_fft, nf]
        frames = frames * w[:, None]
        if onesided and not is_complex:
            spec = jnp.fft.rfft(frames, axis=-2)
        else:
            spec = jnp.fft.fft(frames, axis=-2)
        if normalized:
            spec = spec / jnp.sqrt(jnp.asarray(float(n_fft), spec.real.dtype))
        return spec

    return apply(_stft, x, op_name="stft")


def istft(x, n_fft, hop_length=None, win_length=None, window=None, center=True,
          normalized=False, onesided=True, length=None, return_complex=False,
          name=None):
    """Inverse STFT with overlap-add + window-envelope normalization
    (paddle.signal.istft; ref :424)."""
    x = ensure_tensor(x)
    if hop_length is None:
        hop_length = n_fft // 4
    if win_length is None:
        win_length = n_fft
    if window is not None:
        w = ensure_tensor(window).numpy().astype(np.float32)
    else:
        w = np.ones(win_length, np.float32)
    if win_length < n_fft:
        pad_l = (n_fft - win_length) // 2
        w = np.pad(w, (pad_l, n_fft - win_length - pad_l))
    w = jnp.asarray(w)

    def _istft(spec):
        if normalized:
            spec = spec * jnp.sqrt(jnp.asarray(float(n_fft), spec.real.dtype))
        if onesided:
            frames = jnp.fft.irfft(spec, n=n_fft, axis=-2)
        else:
            frames = jnp.fft.ifft(spec, axis=-2)
            if not return_complex:
                frames = frames.real
        frames = frames * w[:, None]
        sig = _overlap_add_axis(frames, hop_length, -1)
        env = _overlap_add_axis(
            jnp.broadcast_to((w * w)[:, None], frames.shape[-2:]), hop_length, -1)
        sig = sig / jnp.where(env > 1e-11, env, 1.0)
        if center:
            pad = n_fft // 2
            sig = sig[..., pad:sig.shape[-1] - pad]
        if length is not None:
            sig = sig[..., :length]
        return sig

    return apply(_istft, x, op_name="istft")
