"""Authored Pallas TPU forward of the fused LM-head + cross-entropy.

One product ``W @ h^T`` whose tiles take their softmax statistics while
they are still in VMEM: the running row maximum, the running
``sum(exp)`` rescaled by the online rule and the label's logit. XLA needs
a second pass over the float32 logits for the sum, since it cannot form a
row's ``sum(exp)`` before it has the row's maximum
(`kernels/fused_ce.py` holds the custom VJP, whose backward stays XLA's).

What the chip is handed (`_plan` sizes it from the shapes, no flag):

- grid ``(row blocks, vocabulary tiles)``, the vocabulary innermost: a
  block of ``h``'s rows stays resident while ``W``'s tiles stream past it,
  and the statistics of the block's rows accumulate in VMEM across them;
- a tile is ``W_tile @ h_block^T``, bf16 operands accumulated float32
  (`preferred_element_type`), written ``[V, N]``: the layout XLA gives the
  logits it holds for the backward (``f32[N, V]{0,1}``: its
  weight-gradient product ``dlogits^T h`` reads it without a transpose).
  The statistics then reduce over SUBLANES, element-wise over a tile's
  vregs with one fold of eight at the end, and come out as ROWS of ``N``
  lanes (a column would pad every value to 128 lanes in HBM);
- a cell's tile is walked in chunks of lanes, unrolled: the scheduler
  overlaps one chunk's product with the previous chunk's exponentials;
- everything a statistic touches is float32, the exponentials those of
  float32 logits.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope

_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
# what one cell may hold of VMEM (v5e has 128 MiB; Mosaic's default scoped
# limit of 16 MiB is raised to what the plan needs)
_CELL_BYTES = 40 << 20


class Plan(NamedTuple):
    """``rows`` rows of ``h`` a cell keeps resident (the lanes of a tile of
    logits), ``tile_v`` rows of ``W`` a grid step streams (its sublanes),
    ``chunk`` lanes one product of the unrolled walk covers."""
    rows: int
    tile_v: int
    chunk: int


def _cell_bytes(rows, tile_v, chunk, hid):
    # h block, W tile and the float32 tile of logits, double-buffered, and
    # a chunk's float32 intermediates (scores, exponentials, the pick)
    return 2 * (rows * hid * 2 + tile_v * hid * 2 + tile_v * rows * 4) \
        + 4 * tile_v * chunk * 4


def _plan(n, hid, v):
    """Tile sizes from the shapes and the VMEM a cell needs, or None where
    the kernel does not fit them: ``hid`` and ``v`` multiples of 128 lanes,
    ``n`` a multiple of a row block that fits."""
    if hid % _LANES or v % _LANES:
        return None
    # at [16384, 768] x [50304, 768] on the chip, ms a launch: 4,096 rows
    # in chunks of 256 lanes 7.25, 2,048 7.41, 1,024 7.74; chunks of 512
    # or 1,024 lanes 0.3 more; tiles of 128 to 2,096 vocabulary rows
    # within 0.1; the product alone 6.6-6.8
    tile_v = next(t for t in (512, 384, 256, 128) if v % t == 0)
    for rows in (4096, 2048, 1024, 512, 256, 128):
        chunk = min(rows, 256)
        if n % rows == 0 and _cell_bytes(rows, tile_v, chunk,
                                         hid) <= _CELL_BYTES:
            return Plan(rows, tile_v, chunk)
    return None


def _fwd_kernel(lab_ref, w_ref, h_ref, logits_ref, lse_ref, pick_ref,
                m_ref, l_ref, *, plan, tiles):
    # lab_ref/lse_ref/pick_ref/m_ref/l_ref: [1, rows]; w_ref: [tile_v, hid];
    # h_ref: [rows, hid]; logits_ref: [tile_v, rows]
    rows, tile_v, chunk = plan
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        pick_ref[...] = jnp.zeros_like(pick_ref)

    w = w_ref[...]
    vrow = jax.lax.broadcasted_iota(jnp.int32, (tile_v, chunk), 0)
    for c in range(rows // chunk):
        at = slice(c * chunk, (c + 1) * chunk)
        s = jax.lax.dot_general(w, h_ref[at, :], _NT,
                                preferred_element_type=jnp.float32)
        logits_ref[:, at] = s
        m = m_ref[:, at]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        l_ref[:, at] = l_ref[:, at] * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(s - m_new), axis=0, keepdims=True)
        m_ref[:, at] = m_new
        # the label's logit, where the label falls in this tile
        hit = vrow == lab_ref[:, at] - j * tile_v
        pick_ref[:, at] += jnp.sum(jnp.where(hit, s, 0.0), axis=0,
                                   keepdims=True)

    @pl.when(j == tiles - 1)
    def _():
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def forward(h, w, labels, *, plan, interpret):
    """``h`` [N, hid], ``w`` [V, hid], ``labels`` [N] int32 ->
    (logits [V, N] float32, lse [N], picked [N]): ``picked`` is the
    label's logit, 0 where the label names no row of ``w``."""
    n, hid = h.shape
    v = w.shape[0]
    rows, tile_v, chunk = plan
    tiles = v // tile_v
    row = pl.BlockSpec((1, rows), lambda i, j: (0, i))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 2 * _cell_bytes(
                rows, tile_v, chunk, hid)))))}
    with x64_off_scope():
        logits, lse, picked = pl.pallas_call(
            functools.partial(_fwd_kernel, plan=plan, tiles=tiles),
            grid=(n // rows, tiles),
            in_specs=[
                row,
                pl.BlockSpec((tile_v, hid), lambda i, j: (j, 0)),
                pl.BlockSpec((rows, hid), lambda i, j: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tile_v, rows), lambda i, j: (j, i)),
                row, row,
            ],
            out_shape=[
                jax.ShapeDtypeStruct((v, n), jnp.float32),
                jax.ShapeDtypeStruct((1, n), jnp.float32),
                jax.ShapeDtypeStruct((1, n), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((1, rows), jnp.float32)] * 2,
            interpret=interpret,
            **params,
        )(labels.astype(jnp.int32).reshape(1, n), w.astype(jnp.bfloat16),
          h.astype(jnp.bfloat16))
    return logits, lse[0], picked[0]
