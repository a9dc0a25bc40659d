"""Authored Pallas TPU kernel of a prefill chunk's latent (MLA) attention
in the per-head form: the Pallas arm of `kernels/mla.py::latent_prefill`.

The XLA arm walks the context a block of keys at a time and, for each group
of heads, writes the block's ``[heads, queries, keys]`` float32 scores to
HBM, reads them for the row maximum, again for ``exp`` and the row sum and
again for the product with the values. Here a tile of scores lives in VMEM
from the product that makes it to the product that consumes it: HBM sees
the queries, a group's columns of ``W_ukv``, the sequence's latent rows and
the mask once a group of heads, and the output once.

What the chip is handed (`plan` sizes it from the shapes, no flag):

- grid ``(groups of heads, blocks of keys)``, the keys innermost and
  sequential: a group's queries and its columns of ``W_ukv`` stay resident
  while the sequence's latent rows stream past, and the softmax's running
  maximum and sum and the carried output of the group's heads live in VMEM
  scratch across the blocks. Only the last step of a group writes its
  output;
- the blocks that exist, ``(max(qpos) + block) // block``, arrive by scalar
  prefetch: a block past them is skipped (`pl.when`) and its index maps
  are clamped to the last block that exists, so no copy is issued for it:
  cost goes by the keys in sight, never by what the page row could hold;
- the latent rows ``[ckv | k_rope | 0...]`` come as ONE array ``[keys,
  width]``, the sequence's whole pages gathered through its page row by
  the caller (43 MB at 33,792 keys); a block of them is an ordinary
  pipelined operand;
- in VMEM, a head at a time: the block's keys and values ``ckv @
  W_ukv[:, head]`` (operands as stored, float32 accumulation, held in the
  stored type), the scores ``[queries, block]`` in float32 from ONE
  product over ``[k_nope | k_rope]`` against ``[q_nope | q_rope]`` (the
  rope half rides the lane tile behind ``k_nope``, the lanes past it
  zeroed), the scale, the mask as an additive ``0 / -1e30`` made once a
  block for all the group's heads (float32 absorbs any score into -1e30
  exactly, so it equals a select), the online softmax, the product with
  the values from probabilities rounded to the stored type: the XLA arm's
  precision at every point;
- the mask is the causal one, made from ``qpos``, or the selection's
  ``keep`` as int8 ``[queries, keys]`` (`kernels/mla.py::chosen_mask`);
- a query that has seen no key yet carries garbage the first real key's
  rescale (``exp(-1e30 - m) = 0``) wipes exactly; one that never sees a
  key (padding, ``qpos`` -1) gets zeros at the end, as the XLA arm gives.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope

NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
HEADS = 8               # heads a grid cell holds, where the count allows
KEY_BLOCK = 512         # keys a grid step expands and attends


class Plan(NamedTuple):
    """``heads`` heads a grid cell holds (their queries and columns of
    ``W_ukv`` resident), ``block`` keys a grid step expands and attends."""
    heads: int
    block: int


def _cell_bytes(t, heads, block, dn, dv, rank, width, itemsize):
    # q, W, a block of rows and of the mask, double-buffered; the float32
    # output block and accumulator; the running maximum and sum (a column
    # pads to a lane tile); [k_nope | k_rope], the bias; a head's float32
    # scores, probabilities and expansion
    dq = dn + _LANES
    return 2 * (t * heads * dq * itemsize + rank * heads * (dn + dv) * itemsize
                + block * width * itemsize + t * max(block, _LANES)) \
        + 3 * t * heads * dv * 4 + 2 * heads * t * _LANES * 4 \
        + block * dq * itemsize + t * block * 4 \
        + 3 * t * block * 4 + block * (dn + dv) * 6


def plan(t, h, dn, rope, dv, rank, width, page_size, heads=None,
         block=None):
    """Tile sizes from the shapes, or None where the kernel does not fit
    them: the chunk length, ``rank``, ``dn`` and ``dv`` multiples of 128
    lanes, the rope half inside one lane tile behind ``rank`` in a row of
    whole tiles, the head count a multiple of the group. ``heads`` /
    ``block`` override the tiles (tests drive tiny ones)."""
    if t % _LANES or dn % _LANES or dv % _LANES or rank % _LANES \
            or width % _LANES or not 0 < rope <= _LANES \
            or width < rank + _LANES:
        return None
    g = heads or next((c for c in (HEADS, 4, 2, 1) if h % c == 0))
    if h % g:
        return None
    kb = block or KEY_BLOCK
    if kb % page_size or kb % 32:
        return None
    return Plan(g, kb)


def _kernel(nb_ref, mask_ref, q_ref, w_ref, lat_ref, o_ref, *rest, plan,
            rank, rope, dn, dv, scale, masked, has_visits, steps):
    # one grid cell: the group's queries q_ref [T, g * (dn + 128)] and
    # columns w_ref [rank, g * (dn + dv)], block j of the rows lat_ref
    # [block, width]; mask_ref: keep [T, block] int8, or qpos [T, 1];
    # o_ref [T, g * dv] float32. Scratch: kcat [block, dn + 128] (a head's
    # k_nope, then the block's k_rope), bias [T, block], the running
    # maximum and sum [g, T, 1], the carried output [T, g * dv].
    if has_visits:
        visits_ref, *rest = rest
    kcat, bias, m_scr, l_scr, acc = rest
    g, kb = plan
    j = pl.program_id(1)
    t = q_ref.shape[0]
    dq, dkv = dn + _LANES, dn + dv

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        if has_visits:
            visits_ref[...] = jnp.zeros(visits_ref.shape, jnp.int32)

    @pl.when(j < nb_ref[0])
    def _():
        lat = lat_ref[...]
        ckv = lat[:, :rank]
        rope_tile = lat[:, rank:rank + _LANES]
        lane = jax.lax.broadcasted_iota(jnp.int32, rope_tile.shape, 1)
        kcat[:, dn:] = jnp.where(lane < rope, rope_tile,
                                 jnp.zeros_like(rope_tile))
        if masked:
            bias[...] = (mask_ref[...].astype(jnp.float32) - 1.0) * -NEG_INF
        else:
            kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (t, kb), 1)
            bias[...] = jnp.where(kpos <= mask_ref[...], 0.0, NEG_INF)
        for a in range(g):
            kv = jnp.dot(ckv, w_ref[:, a * dkv:(a + 1) * dkv],
                         preferred_element_type=jnp.float32).astype(lat.dtype)
            kcat[:, :dn] = kv[:, :dn]
            s = jax.lax.dot_general(
                q_ref[:, a * dq:(a + 1) * dq], kcat[...], _NT,
                preferred_element_type=jnp.float32) * scale + bias[...]
            m = m_scr[a]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_scr[a] = l_scr[a] * alpha + jnp.sum(p, axis=1, keepdims=True)
            out = slice(a * dv, (a + 1) * dv)
            acc[:, out] = acc[:, out] * alpha + jnp.dot(
                p.astype(lat.dtype), kv[:, dn:],
                preferred_element_type=jnp.float32)
            m_scr[a] = m_new
        if has_visits:
            visits_ref[...] += 1

    @pl.when(j == steps - 1)
    def _():
        for a in range(g):
            out = slice(a * dv, (a + 1) * dv)
            o_ref[:, out] = jnp.where(
                m_scr[a] > NEG_INF,
                acc[:, out] / jnp.maximum(l_scr[a], 1e-30), 0.0)


@functools.partial(jax.jit, static_argnames=(
    "plan", "rank", "rope", "dv", "scale", "interpret", "return_visits"))
def latent_prefill(q_nope, q_rope, w_ukv, lat, qpos, keep=None, *, plan,
                   rank, rope, dv, scale, interpret, return_visits=False):
    """A chunk's queries over ONE sequence's latent rows, per head.

    q_nope : [T, H, dn]; q_rope : [T, H, rope]; w_ukv : [rank, H * (dn +
    dv)], a head's columns ``[k_nope | v]``; lat : [S, width] the
    sequence's rows ``[ckv | k_rope | 0...]`` in position order, ``S`` a
    whole number of ``plan.block``; qpos : [T] int32 positions (negative:
    padding); keep : None (every key ``s <= qpos``) or int8 [T, S], 1
    where the query attends the key. Returns out [T, H, dv] float32 and,
    with ``return_visits``, the key blocks each group of heads visited
    [H / plan.heads] int32: blocks past the furthest query are not."""
    t, h, dn = q_nope.shape
    g, kb = plan
    s_len, width = lat.shape
    steps = s_len // kb
    dq, dkv = dn + _LANES, dn + dv
    q = jnp.concatenate(
        [q_nope, q_rope, jnp.zeros((t, h, _LANES - rope), q_nope.dtype)],
        axis=-1).astype(lat.dtype).reshape(t, h * dq)
    n_blocks = jnp.minimum((jnp.max(qpos) + kb) // kb, steps) \
        .astype(jnp.int32).reshape(1)
    masked = keep is not None

    def at(i, j, nb):                     # the last block that exists
        return jnp.minimum(j, jnp.maximum(nb[0] - 1, 0))
    mask_spec = pl.BlockSpec((t, kb), lambda i, j, nb: (0, at(i, j, nb))) \
        if masked else pl.BlockSpec((t, 1), lambda i, j, nb: (0, 0))
    out_specs = [pl.BlockSpec((t, g * dv), lambda i, j, nb: (0, i))]
    out_shape = [jax.ShapeDtypeStruct((t, h * dv), jnp.float32)]
    if return_visits:
        out_specs.append(pl.BlockSpec((1, 1, _LANES),
                                      lambda i, j, nb: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((h // g, 1, _LANES),
                                              jnp.int32))
    itemsize = lat.dtype.itemsize
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(min(100 << 20, max(32 << 20, _cell_bytes(
                t, g, kb, dn, dv, rank, width, itemsize) * 3 // 2))))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h // g, steps),
        in_specs=[
            mask_spec,
            pl.BlockSpec((t, g * dq), lambda i, j, nb: (0, i)),
            pl.BlockSpec((rank, g * dkv), lambda i, j, nb: (0, i)),
            pl.BlockSpec((kb, width), lambda i, j, nb: (at(i, j, nb), 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((kb, dq), lat.dtype),
            pltpu.VMEM((t, kb), jnp.float32),
            pltpu.VMEM((g, t, 1), jnp.float32),
            pltpu.VMEM((g, t, 1), jnp.float32),
            pltpu.VMEM((t, g * dv), jnp.float32),
        ])
    with x64_off_scope():
        outs = pl.pallas_call(
            functools.partial(_kernel, plan=plan, rank=rank, rope=rope,
                              dn=dn, dv=dv, scale=float(scale),
                              masked=masked, has_visits=return_visits,
                              steps=steps),
            grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
            **params,
        )(n_blocks, keep if masked else qpos.astype(jnp.int32).reshape(t, 1),
          q, w_ukv.astype(lat.dtype), lat)
    out = outs[0].reshape(t, h, dv)
    if return_visits:
        return out, outs[1][:, 0, 0]
    return out
