"""Authored Pallas TPU fused layer-norm kernel (forward + analytic backward).

Counterpart of the reference's fused layernorm CUDA kernels
(`paddle/phi/kernels/fusion/` / `paddle/fluid/operators/fused/fused_layernorm_*`):
one pass over each row computes mean/rstd and the normalized output; the
backward kernel computes dx in one pass plus per-block dgamma/dbeta partials
that a cheap XLA reduction finishes off.

Rows are processed in blocks of ``block_rows`` so the (rows, D) problem tiles
onto the VPU; all statistics are f32 regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.core.autograd import x64_off_scope


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rs_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rs_ref[:] = rstd


def _bwd_kernel(x_ref, g_ref, mu_ref, rs_ref, dy_ref, dx_ref, dg_ref, db_ref,
                *, n_rows, block_rows):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mu, rstd = mu_ref[:], rs_ref[:]
    # rows past n_rows are block padding: their dy/xhat hold garbage that must
    # not leak into the dgamma/dbeta partial sums
    row = pl.program_id(0) * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], 1), 0)
    valid = row < n_rows
    dy = jnp.where(valid, dy, 0.0)
    xhat = jnp.where(valid, (x - mu) * rstd, 0.0)
    wdy = dy * g
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[0] = jnp.sum(dy, axis=0, keepdims=True)


def _fwd(x, gamma, beta, eps, block_rows, interpret):
    n, d = x.shape
    block_rows = min(block_rows, n)
    grid = (pl.cdiv(n, block_rows),)
    with x64_off_scope():
        return pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, d), x.dtype),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            interpret=interpret,
        )(x, gamma.reshape(1, d), beta.reshape(1, d))


def _bwd(x, gamma, mu, rstd, dy, block_rows, interpret):
    n, d = x.shape
    block_rows = min(block_rows, n)
    nb = pl.cdiv(n, block_rows)
    # per-block dgamma/dbeta partials ride a [nb, 1, d] array: a (1, d)
    # block of an [nb, d] array is not (8, 128)-tiled
    with x64_off_scope():
        dx, dg_part, db_part = pl.pallas_call(
            functools.partial(_bwd_kernel, n_rows=n, block_rows=block_rows),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, d), x.dtype),
                jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
                jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
            ],
            interpret=interpret,
        )(x, gamma.reshape(1, d), mu, rstd, dy)
    return dx, dg_part.sum((0, 1)), db_part.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x, gamma, beta, eps, block_rows, interpret):
    y, _, _ = _fwd(x, gamma, beta, eps, block_rows, interpret)
    return y


def _ln_fwd(x, gamma, beta, eps, block_rows, interpret):
    y, mu, rstd = _fwd(x, gamma, beta, eps, block_rows, interpret)
    return y, (x, gamma, mu, rstd)


def _ln_bwd(eps, block_rows, interpret, res, dy):
    x, gamma, mu, rstd = res
    dx, dg, db = _bwd(x, gamma, mu, rstd, dy, block_rows, interpret)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-5, *, block_rows=256,
                     interpret=None):
    """Fused layernorm over the last axis. x: [..., D] jax array."""
    if interpret is None:
        from paddle_tpu.kernels.pallas._compat import default_interpret
        interpret = default_interpret()
    shape = x.shape
    d = shape[-1]
    out = _ln(x.reshape(-1, d), gamma, beta, float(eps), int(block_rows),
              bool(interpret))
    return out.reshape(shape)
