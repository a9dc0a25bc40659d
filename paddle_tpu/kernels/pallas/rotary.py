"""Authored Pallas TPU fused rotary-position-embedding kernel.

Counterpart of the reference's fused rope CUDA path (the reference snapshot
applies rotary embeddings with unfused elementwise ops; newer branches ship
`fused_rope`). One kernel applies the rotation to Q and K simultaneously so
the cos/sin tables are read from VMEM once per block.

Convention: pairs are (x[..., :D/2], x[..., D/2:]) (GPT-NeoX style, matching
`paddle_tpu.models.gpt`'s rotary helper).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.core.autograd import x64_off_scope


def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, qo_ref, ko_ref):
    cos = cos_ref[0].astype(jnp.float32)          # [block_s, D/2]
    sin = sin_ref[0].astype(jnp.float32)

    def rot(x):
        x = x.astype(jnp.float32)
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    qo_ref[0] = rot(q_ref[0]).astype(qo_ref.dtype)
    ko_ref[0] = rot(k_ref[0]).astype(ko_ref.dtype)


def apply_rotary_emb(q, k, cos, sin, *, block_s=256, interpret=None):
    """Apply rotary embeddings to q and k in one fused pass.

    q/k: [B, H, S, D]; cos/sin: [S, D/2]. Returns (q_rot, k_rot).
    """
    if interpret is None:
        from paddle_tpu.kernels.pallas._compat import default_interpret
        interpret = default_interpret()
    b, h, s, d = q.shape
    block_s = min(block_s, s)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    grid = (b * h, pl.cdiv(s, block_s))
    with x64_off_scope():
        qo, ko = pl.pallas_call(
            _rope_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_s, d), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, block_s, d), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, block_s, d // 2), lambda bh, i: (0, i, 0)),
                pl.BlockSpec((1, block_s, d // 2), lambda bh, i: (0, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_s, d), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, block_s, d), lambda bh, i: (bh, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qf.shape, q.dtype),
                jax.ShapeDtypeStruct(kf.shape, k.dtype),
            ],
            interpret=interpret,
        )(qf, kf, cos[None], sin[None])
    return qo.reshape(b, h, s, d), ko.reshape(b, h, s, d)
