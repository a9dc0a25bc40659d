"""Authored Pallas TPU flash attention, forward and backward.

Online-softmax blockwise attention (Dao et al.) written directly against the
Pallas TPU API — the in-repo counterpart of the reference's fused attention
CUDA op (`paddle/fluid/operators/fused/fused_attention_op.cu`, which is
non-flash: it materialises the full [S, S] score matrix via `fmha_ref.h`).
A block's scores and probabilities live in VMEM only: HBM sees q, k, v, o
and their gradients once each, plus one float32 row statistic a query.

What the chip is handed (`_plan` and `_pack` size it from the shapes, no
flag):

- the kernels read and write ``[B, S, H * D]``, what the projections
  around an attention layer give and take, so nothing is transposed on the
  way in or out. Heads narrower than 128 lanes share a group of lanes
  (two heads of 64): a product over the whole group with the neighbours'
  lanes of ONE operand zeroed is the head's own, at the MXU passes a lone
  head of 64 would pad to anyway, and no lane of HBM or VMEM is padding.
  Heads that fill no group whole (three heads of 64) go to the batch axis
  first;
- products take their operands in the dtype they arrive in and accumulate
  float32 (`preferred_element_type`): bf16 inputs run the MXU in one pass,
  float32 inputs keep float32 products. The probabilities and ``ds`` are
  rounded to the value dtype before their products; scores, the running
  maximum, the sums and the accumulators stay float32;
- a grid cell holds the heads of one group of lanes and, where their
  sequence fits, all of it: K and V stay resident while the query tiles
  pass, and when the cell covers the whole sequence every tile bound is
  static and the walk unrolls (the scheduler overlaps one tile's products
  with the next tile's exponentials);
- causal tiles wholly below the diagonal take no mask, tiles wholly above
  are not visited;
- the backward is ONE kernel (Dao et al. algorithm 2 with dQ accumulated in
  VMEM): a tile's probabilities are recomputed once, transposed
  (keys x queries), so dV and dK are plain products and the per-query
  statistics broadcast along lanes;
- the logsumexp travels as a ROW a head, ``[B, H, 1, S]``: a column would
  pad every value to 128 lanes in HBM.

`flash_attention` takes ``[B, H, S, D]`` and swaps the axes itself: a
caller that swapped them to get here (`kernels/flash_attention.py`) has
both swaps cancelled by XLA.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope

NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


class Plan(NamedTuple):
    """Tile sizes of one call. ``block_q`` x ``block_k`` is the score tile
    both kernels compute; a forward cell holds ``cell_q`` query rows (a
    multiple of ``block_q``) with all their keys, a backward cell
    ``cell_k`` keys with all their queries, of the ``pack`` heads of one
    batch row that share one group of lanes."""
    block_q: int
    block_k: int
    cell_q: int
    cell_k: int
    pack: int


# what one cell may hold of VMEM (v5e has 128 MiB; Mosaic's default scoped
# limit of 16 MiB is raised to what the plan needs, `_vmem_limit`)
_CELL_BYTES = 24 << 20
# a walk over at most this many tiles a head is unrolled (static bounds)
_UNROLL_TILES = 16


def _round_up(x, m):
    return -(-x // m) * m


def _pack(h, d):
    """How many heads share one group of lanes, read in place from a
    ``[B, S, H * D]`` array: a block's last dimension has to be a multiple
    of 128 lanes or the whole ``H * D``. 0: no such group, the heads are
    moved to the batch axis first (``[B * H, S, D]``)."""
    if d % _LANES == 0:
        return 1
    if _LANES % d == 0 and h % (_LANES // d) == 0:
        return _LANES // d
    return h if h * d <= _LANES else 0


def _cell_bytes(sq_pad, sk_pad, width, itemsize):
    # a backward cell, the larger of the two: q, k, v, do, dq, dk, dv
    # double-buffered with lanes padded to 128, and the float32 dQ
    # accumulator
    lanes = _round_up(width, _LANES)
    return (sq_pad * 3 + sk_pad * 4) * lanes * itemsize * 2 \
        + sq_pad * lanes * 4


def _plan(sq, sk, d, itemsize, pack=1, block_q=None, block_k=None):
    """Tile sizes from what the call can observe: sequence lengths, head
    width, itemsize and the VMEM a cell may hold. ``block_q`` /
    ``block_k`` override the score tile (tests drive ragged and tiny tiles
    with them). At S 1,024 / D 64 on the chip, tiles of 128 to 512 and one
    to four batch rows a cell all read within 5%: 256 and one row stand."""
    sub = max(8, 32 // itemsize)                   # sublanes of one tile
    forced = block_q is not None or block_k is not None
    bq = min(block_q or 256, _round_up(sq, sub))
    bk = min(block_k or 256, _round_up(sk, sub))
    sq_pad, sk_pad = _round_up(sq, bq), _round_up(sk, bk)
    tiles = (sq_pad // bq) * (sk_pad // bk)
    whole = (not forced and tiles <= _UNROLL_TILES and _cell_bytes(
        sq_pad, sk_pad, pack * d, itemsize) <= _CELL_BYTES)
    # the whole sequence in one cell where it fits, else a tile a cell
    return Plan(bq, bk, sq_pad if whole else bq, sk_pad if whole else bk,
                pack)


def _vmem_limit(plan, sq_pad, sk_pad, width, itemsize):
    tile = plan.block_q * plan.block_k * 4
    return int(min(100 << 20, max(32 << 20, _cell_bytes(
        sq_pad, sk_pad, width, itemsize) + 16 * tile)))


def _loop(lo, hi, body, carry):
    """``fori_loop``, unrolled in Python where both bounds are static."""
    if isinstance(lo, int) and isinstance(hi, int):
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _start(i, block):
    """Row offset of block ``i``: a Python int, or a traced int32 the
    compiler is told is a multiple of the block."""
    return i * block if isinstance(i, int) else pl.multiple_of(i * block,
                                                               block)


def _min(a, b):
    """``min`` of an int, or a traced int32, and an int."""
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _scaled(x, sm_scale):
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype)


def _head_masks(shape, d, pack):
    """``[lanes of head a]`` over a ``[.., pack * d]`` tile, or ``[None]``
    where a group of lanes is one head's own."""
    if pack == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return [(lane >= a * d) & (lane < (a + 1) * d) for a in range(pack)]


def _only(mask, x, other=0):
    """``x`` on one head's lanes, ``other`` on its neighbours'."""
    return x if mask is None else jnp.where(
        mask, x, jnp.asarray(other, x.dtype))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *vx_ref, sm_scale,
                causal, plan, d, seq_q, seq_k, q_cells):
    # q_ref/o_ref: [cell_q, W]; k_ref/v_ref: [sk_pad, W], W = P * d;
    # lse_ref: [P, 1, cell_q]; vx_ref: ([P, sk_pad, Wx],) where a head is
    # narrower than its (padded) group of lanes
    bq, bk, pack = plan.block_q, plan.block_k, plan.pack
    qi = 0 if q_cells == 1 else pl.program_id(2)
    off = seq_k - seq_q
    nk, nk_whole = pl.cdiv(seq_k, bk), seq_k // bk
    ragged_k = seq_k % bk != 0
    width = q_ref.shape[-1]
    # key index minus query index inside one tile: the causal and ragged
    # masks are one comparison of it against a scalar
    diff = (jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def _mask(c0, r0):
        mask = diff <= r0 + off - c0 if causal else None
        if ragged_k:                               # the tail's padding
            tail = kcol < seq_k - c0
            mask = tail if mask is None else mask & tail
        return mask

    q_heads = _head_masks((bq, width), d, pack)
    vx = vx_ref[0] if vx_ref else None
    if vx is not None:
        # a head narrower than its group of lanes pays for the whole
        # group in P @ V anyway: ones on the lanes that are not its
        # own make the MXU return the row sums too
        v = v_ref[...]
        if vx.shape[-1] > width:
            v = jnp.concatenate(
                [v, jnp.ones((v.shape[0], vx.shape[-1] - width),
                             v.dtype)], axis=1)
        for a, m in enumerate(_head_masks(v.shape, d, pack)):
            vx[a] = _only(m, v, 1)

    def attend(a, q, r0, carry, spans):
        # one online-softmax step of head ``a`` over ``spans`` of keys,
        # [(first key, width, masked)]: one maximum for all of them
        m, l, acc = carry
        scores = []
        for c0, w, masked in spans:
            sc = jax.lax.dot_general(q, k_ref[pl.ds(c0, w), :], _NT,
                                     preferred_element_type=jnp.float32)
            if masked:
                sc = jnp.where(_mask(c0, r0), sc, NEG_INF)
            scores.append(sc)
        m_new = m
        for sc in scores:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        l, acc = l * alpha, acc * alpha
        for (c0, w, _), sc in zip(spans, scores):
            p = jnp.exp(sc - m_new)
            if vx is None:
                pv = jnp.dot(p.astype(v_ref.dtype), v_ref[pl.ds(c0, w), :],
                             preferred_element_type=jnp.float32)
                l = l + jnp.sum(p, axis=1, keepdims=True)
            else:
                pv = jnp.dot(p.astype(v_ref.dtype), vx[a, pl.ds(c0, w), :],
                             preferred_element_type=jnp.float32)
                ones = (a + 1) % pack * d if pack > 1 else d
                l = l + pv[:, ones:ones + 1]
            acc = acc + pv
        return m_new, l, acc

    lanes = width if vx is None else vx.shape[-1]
    for t in range(plan.cell_q // bq):
        r0 = qi * plan.cell_q + t * bq             # first query row
        q_all = _scaled(q_ref[t * bq:(t + 1) * bq, :], sm_scale)
        if causal:
            # bottom-right-aligned diagonal (matches _reference's tril
            # k=sk-sq): row r sees keys <= r + off. Tiles wholly below
            # the line take no mask, tiles wholly above are not visited
            n_full = _min((r0 + off + 1) // bk, nk_whole)
            n_all = _min((r0 + bq - 1 + off) // bk + 1, nk)
        else:
            n_full, n_all = nk_whole, nk
        out = None
        for a in range(pack):
            # the neighbours' lanes of q are zero, so the product over
            # the whole group of lanes is this head's scores
            step = functools.partial(attend, a, _only(q_heads[a], q_all), r0)
            carry = (jnp.full((bq, 1), NEG_INF, jnp.float32),
                     jnp.zeros((bq, 1), jnp.float32),
                     jnp.zeros((bq, lanes), jnp.float32))
            if isinstance(n_full, int) and isinstance(n_all, int):
                # static bounds: the unmasked tiles are ONE wide product
                # and the diagonal's join it under one maximum
                spans = [(0, n_full * bk, False)] if n_full else []
                spans += [(j * bk, bk, True) for j in range(n_full, n_all)]
                m, l, acc = step(carry, spans)
            else:
                tile = lambda j, c, masked: step(  # noqa: E731
                    c, [(_start(j, bk), bk, masked)])
                carry = jax.lax.fori_loop(
                    0, n_full, functools.partial(tile, masked=False), carry)
                m, l, acc = jax.lax.fori_loop(
                    n_full, n_all, functools.partial(tile, masked=True),
                    carry)
            l = jnp.maximum(l, 1e-30)
            o = (acc * (1.0 / l))[:, :width]       # one division a row
            out = o if out is None else jnp.where(q_heads[a], o, out)
            # the statistic leaves as a row: a column would pad each
            # value to a tile of 128 lanes in HBM
            lse = jnp.broadcast_to(m + jnp.log(l), (bq, _LANES))
            lse_ref[a, :, t * bq:(t + 1) * bq] = lse.T[:1]
        o_ref[t * bq:(t + 1) * bq, :] = out.astype(o_ref.dtype)


def _params(semantics, limit, interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=limit)}


def _pad_rows(x, pad, axis=1):
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("heads", "sm_scale", "causal",
                                             "plan", "interpret"))
def _fwd(q, k, v, *, heads, sm_scale, causal, plan, interpret):
    # one jitted function for forward (and `_bwd` for backward): every
    # layer of a step program is the same call of it, so the program
    # traces the kernel's body and lowers it through Mosaic once
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // heads
    width = plan.pack * d
    # dynamic slices clamp at the array edge, so a ragged K tail is
    # zero-padded up front (the ragged mask discards the padding)
    pad_q, pad_k = (-sq) % plan.block_q, (-sk) % plan.block_k
    q, k, v = _pad_rows(q, pad_q), _pad_rows(k, pad_k), _pad_rows(v, pad_k)
    sq_pad, sk_pad = sq + pad_q, sk + pad_k
    q_cells = sq_pad // plan.cell_q
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                             plan=plan, d=d, seq_q=sq, seq_k=sk,
                             q_cells=q_cells)
    # lanes of a head's values: its group's, padded to a tile of lanes
    # where the group is one narrow head
    vx_lanes = width if plan.pack > 1 else _round_up(d, _LANES)
    with x64_off_scope():
        out, lse = pl.pallas_call(
            kern,
            grid=(b, heads // plan.pack, q_cells),
            in_specs=[
                pl.BlockSpec((None, plan.cell_q, width),
                             lambda n, j, i: (n, i, j)),
                pl.BlockSpec((None, sk_pad, width),
                             lambda n, j, i: (n, 0, j)),
                pl.BlockSpec((None, sk_pad, width),
                             lambda n, j, i: (n, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((None, plan.cell_q, width),
                             lambda n, j, i: (n, i, j)),
                pl.BlockSpec((None, plan.pack, 1, plan.cell_q),
                             lambda n, j, i: (n, j, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((b, heads, 1, sq_pad), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((plan.pack, sk_pad, vx_lanes),
                                       v.dtype)] if vx_lanes > d else [],
            interpret=interpret,
            **_params(("parallel", "parallel", "parallel"),
                      _vmem_limit(plan, sq_pad, sk_pad, width,
                                  q.dtype.itemsize), interpret),
        )(q, k, v)
    return out[:, :sq], lse[..., :sq]


def _reference(q, k, v, sm_scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_acc, *, sm_scale, causal, plan, d, seq_q,
                seq_k, k_cells):
    # k/v/dk/dv: [cell_k, W]; q/do/dq: [sq_pad, W], W = P * d;
    # lse/delta: [P, nq, 1, block_q] rows; dq_acc: float32 [sq_pad, W]
    bq, bk, pack = plan.block_q, plan.block_k, plan.pack
    kj = 0 if k_cells == 1 else pl.program_id(2)
    off = seq_k - seq_q
    nq = q_ref.shape[0] // bq
    ragged_k = seq_k % bk != 0
    # transposed tiles (keys x queries): key index minus query index
    diff = (jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1))
    krow = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)

    def _mask(c0, r0):
        mask = diff <= r0 + off - c0 if causal else None
        if ragged_k:                               # the tail's padding
            tail = krow < seq_k - c0
            mask = tail if mask is None else mask & tail
        return mask

    k_heads = _head_masks((bk, k_ref.shape[-1]), d, pack)

    if k_cells == 1:
        dq_acc[...] = jnp.zeros_like(dq_acc)
    else:
        @pl.when(kj == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(a, ks, v, c0, i, carry, masked):
        # query block ``i`` against one head's tile of keys: ks, v [bk, W]
        dk, dv = carry
        r0 = _start(i, bq)
        q = q_ref[pl.ds(r0, bq), :]
        do = do_ref[pl.ds(r0, bq), :]
        st = jax.lax.dot_general(ks, q, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            st = jnp.where(_mask(c0, r0), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[a, i])                      # [bk, bq]
        dv = dv + jnp.dot(pt.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[a, i])
        dk = dk + jnp.dot(dst.astype(q.dtype), q,
                          preferred_element_type=jnp.float32)
        dq_acc[pl.ds(r0, bq), :] += jnp.dot(
            dst.T.astype(ks.dtype), ks, preferred_element_type=jnp.float32)
        return dk, dv

    for t in range(plan.cell_k // bk):
        c0 = kj * plan.cell_k + t * bk             # first key of the tile
        k_all = _scaled(k_ref[t * bk:(t + 1) * bk, :], sm_scale)
        v_all = v_ref[t * bk:(t + 1) * bk, :]
        if causal:
            # query blocks strictly above the tile see none of its keys;
            # those wholly below the diagonal take no mask
            first = (c0 - off) // bq
            full = -((off - c0 - bk + 1) // bq)    # ceil
            if isinstance(c0, int):
                first = max(first, 0)
                full = min(max(full, first), nq)
            else:
                first = jnp.maximum(first, 0)
                full = jnp.clip(full, first, nq)
        else:
            first = full = 0
        if ragged_k:
            # the padded keys' probabilities are masked to zero on
            # every tile of the cell that holds the tail
            full = nq
        dk_out = dv_out = None
        for a in range(pack):
            # the neighbours' lanes of k and v are zero: a product over
            # the whole group of lanes is this head's own, and what it
            # adds to dQ lands on this head's lanes alone
            step = functools.partial(tile, a, _only(k_heads[a], k_all),
                                     _only(k_heads[a], v_all), c0)
            z = jnp.zeros((bk, k_ref.shape[-1]), jnp.float32)
            carry = _loop(first, full,
                          functools.partial(step, masked=True), (z, z))
            dk, dv = _loop(full, nq,
                           functools.partial(step, masked=False), carry)
            # dK and dV of a head are right on its own lanes only
            dk_out = dk if dk_out is None else jnp.where(
                k_heads[a], dk, dk_out)
            dv_out = dv if dv_out is None else jnp.where(
                k_heads[a], dv, dv_out)
        dk_ref[t * bk:(t + 1) * bk, :] = \
            (dk_out * sm_scale).astype(dk_ref.dtype)
        dv_ref[t * bk:(t + 1) * bk, :] = dv_out.astype(dv_ref.dtype)

    if k_cells == 1:
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
    else:
        @pl.when(kj == k_cells - 1)
        def _():
            dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "sm_scale", "causal",
                                             "plan", "interpret"))
def _bwd(q, k, v, out, lse, do, *, heads, sm_scale, causal, plan, interpret):
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // heads
    bq, width = plan.block_q, plan.pack * d
    pad_q, pad_k = (-sq) % bq, (-sk) % plan.block_k
    sq_pad, sk_pad = sq + pad_q, sk + pad_k
    # zero padding needs no mask on the query side: a padded row has
    # do = 0 and delta = 0, so it adds nothing to dK and dV, and its dQ
    # row is cut off below
    # delta = rowsum(do * out) a head, as a product with the heads'
    # indicator [h * d, h]: a sum over part of the lanes makes XLA relay
    # the float32 products through HBM, a product fuses them into its read
    head_of = np.arange(hd)[:, None] // d == np.arange(heads)[None, :]
    delta = jnp.einsum(
        "bsw,wh->bhs", do.astype(jnp.float32) * out.astype(jnp.float32),
        head_of.astype(np.float32), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:, :, None, :]         # as lse
    rows = lambda x: _pad_rows(x, pad_q, axis=3).reshape(      # noqa: E731
        b, heads, sq_pad // bq, 1, bq)
    k_cells = sk_pad // plan.cell_k
    seq = pl.BlockSpec((None, sq_pad, width), lambda n, j, i: (n, 0, j))
    keys = pl.BlockSpec((None, plan.cell_k, width),
                        lambda n, j, i: (n, i, j))
    stat = pl.BlockSpec((None, plan.pack, sq_pad // bq, 1, bq),
                        lambda n, j, i: (n, j, 0, 0, 0))
    kern = functools.partial(_bwd_kernel, sm_scale=sm_scale, causal=causal,
                             plan=plan, d=d, seq_q=sq, seq_k=sk,
                             k_cells=k_cells)
    with x64_off_scope():
        dq, dk, dv = pl.pallas_call(
            kern,
            grid=(b, heads // plan.pack, k_cells),
            in_specs=[seq, keys, keys, seq, stat, stat],
            out_specs=[seq, keys, keys],
            out_shape=[
                jax.ShapeDtypeStruct((b, sq_pad, hd), q.dtype),
                jax.ShapeDtypeStruct((b, sk_pad, hd), k.dtype),
                jax.ShapeDtypeStruct((b, sk_pad, hd), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((sq_pad, width), jnp.float32)],
            interpret=interpret,
            **_params(("parallel", "parallel", "arbitrary"),
                      _vmem_limit(plan, sq_pad, sk_pad, width,
                                  q.dtype.itemsize), interpret),
        )(_pad_rows(q, pad_q), _pad_rows(k, pad_k), _pad_rows(v, pad_k),
          _pad_rows(do, pad_q), rows(lse), rows(delta))
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, heads, sm_scale, causal, plan, interpret):
    out, _ = _fwd(q, k, v, heads=heads, sm_scale=sm_scale, causal=causal,
                  plan=plan, interpret=interpret)
    return out


def _flash_fwd(q, k, v, heads, sm_scale, causal, plan, interpret):
    out, lse = _fwd(q, k, v, heads=heads, sm_scale=sm_scale, causal=causal,
                    plan=plan, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(heads, sm_scale, causal, plan, interpret, res, g):
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, g, heads=heads, sm_scale=sm_scale,
                causal=causal, plan=plan, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=False, sm_scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Blockwise flash attention. q/k/v: [B, H, S, D] jax arrays.

    The kernels read ``[B, S, H * D]``, the layout the projections around
    an attention layer give and take, so a caller that swapped the axes to
    get here (`kernels/flash_attention.py::flash_attention_fn`) has its
    swap undone by XLA and nothing is moved.
    ``block_q`` / ``block_k`` override the score tile `_plan` would pick.
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU so tests run
    on the CPU mesh; on TPU the kernel compiles through Mosaic.
    """
    if interpret is None:
        from paddle_tpu.kernels.pallas._compat import default_interpret
        interpret = default_interpret()
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if causal and sq > sk:
        # bottom-right alignment leaves rows with no visible keys; the
        # reference math degenerates to a uniform softmax over -1e30 scores
        # there, which a streaming kernel cannot reproduce blockwise
        raise NotImplementedError(
            "causal flash_attention requires seq_q <= seq_k "
            f"(got {sq} > {sk})")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    pack = _pack(h, d)
    if pack:
        heads = h
        flat = lambda x: jnp.swapaxes(x, 1, 2).reshape(  # noqa: E731
            b, x.shape[2], h * d)
    else:
        # no group of lanes holds whole heads: every head a batch row
        heads, pack = 1, 1
        flat = lambda x: x.reshape(b * h, x.shape[2], d)  # noqa: E731
    plan = _plan(sq, sk, d, q.dtype.itemsize, pack, block_q, block_k)
    out = _flash(flat(q), flat(k), flat(v), heads, float(sm_scale),
                 bool(causal), plan, bool(interpret))
    if heads == 1 and h > 1:
        return out.reshape(b, h, sq, d)
    return jnp.swapaxes(out.reshape(b, sq, h, d), 1, 2)
