"""Authored Pallas TPU ragged PREFILL attention kernel (the prefill half
of arxiv 2604.15464 — the decode half is `pallas/paged_attention.py`).

The XLA prefill arm (`kernels/paged_attention.py::_xla_prefill_attention`,
the math `models/gpt.py::prefill_chunk_step` always ran) gathers the FULL
padded ``[pages_per_slot * page_size, nh, dh]`` K and V windows per layer
per chunk — HBM traffic and FLOPs scale with the slot's CAPACITY and the
chunk's pow-2 bucket, not with the request's true uncached tail. Since
chunked prefill (PR 6) made bucketed prefill the dominant non-decode cost
and the PR 13 prefill-worker tier runs nothing else, this kernel is the
drop-in the registry routes to:

- **grid over chunk-row blocks** — one grid cell owns a ``[block_q, nh *
  dh]`` slice of the chunk's queries, all heads;
- **scalar-prefetched per-slot lengths** — ``start`` (absolute position of
  the chunk's first token) and ``valid`` (true token count in this chunk)
  arrive via scalar prefetch with the page-table row, so every bound below
  is known before the body runs;
- **length-aware stop** — a q block whose rows all sit past ``valid``
  (bucket padding) visits ZERO pages; an active block's page loop runs
  ``ceil((start + last_active_row + 1) / page_size)`` iterations — compute
  AND DMA scale with the request's true context (cached prefix + real
  tail), never with ``pages_per_slot`` or the pow-2 bucket. Per-cell trip
  counts are a kernel output (``return_visits``) so tests assert the
  scaling;
- **double-buffered page DMA** — the K/V pools stay in HBM
  (``memory_space=ANY``); each cell streams one whole ``[page_size, nh *
  dh]`` page at a time into a two-slot VMEM scratch, next page's DMA in
  flight while the current page is on the MXU, folding into an f32 online
  softmax per head — the same rhythm and the same operand as the decode
  kernel (see its "Layout" note): the pool as the engine stores it,
  stacked and merged ``[nl, num_pages, page_size, nh * dh]``, read at
  ``pool[layer, page]`` with the layer index among the scalar-prefetched
  operands, so nothing of the pool is sliced or copied on the way in;
- **int8 pools** — under ``k_scale``/``v_scale`` the pages are int8 and
  the sequence's f32 scale window rides a VMEM operand; the dequant is
  in-register after the copy lands, so page traffic is the int8 bytes.

Numerics match the XLA arm (f32 scores, absolute-position mask, f32
softmax) to token identity — parity in interpret mode off-TPU is enforced
by tests/test_prefill_pallas.py; selection lives in the kernel registry
(``FLAGS_tpu_prefill_impl``, `kernels/registry.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope
from paddle_tpu.kernels.pallas.paged_attention import (exact_dot, head_segments,
                                                       scale_window)

NEG_INF = -1e30


def block_visits(start, valid, row0, block_q, page_size):
    """Trip count of one q block's page loop — the length-aware stop. A
    block with no row < ``valid`` visits zero pages; otherwise it walks
    ``ceil((start + last_active_row_in_block + 1) / page_size)`` pages."""
    nrows = jnp.clip(valid - row0, 0, block_q)
    last_pos = start + row0 + nrows - 1
    return jnp.where(nrows > 0, (last_pos + page_size) // page_size, 0)


def default_block_q(c: int) -> int:
    """Query rows per grid cell: the whole chunk for the small chunk sizes
    serving uses (<= 256 keeps the [block_q, page_size] score tile modest),
    capped so giant one-shot buckets still tile."""
    return min(int(c), 256)


def _prefill_kernel(meta_ref, pt_ref, q_ref, k_hbm, v_hbm, *rest,
                    page_size, block_q, nh, scale, quant=False,
                    has_visits=False):
    # one grid cell per q block i, all heads: q_ref [block_q, nh*dh] in
    # VMEM, k_hbm/v_hbm the stacked [nl, num_pages, page_size, nh*dh]
    # pools in HBM, meta (start, valid, layer) + the page-table row
    # scalar-prefetched into SMEM. Operand order mirrors the decode
    # kernel: inputs (q, k, v[, k_scale, v_scale windows [maxp*ps, nh]]),
    # outputs (o[, visits]), scratch (kbuf, vbuf, sem, m, l, acc). The
    # running max and denominator live lane-broadcast over each head's dh
    # lanes ([block_q, nh*dh]), so every per-head update is a same-shape
    # slice of the three scratches.
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, *rest = rest
    if has_visits:
        visits_ref, *rest = rest
    kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    start = meta_ref[0]
    valid = meta_ref[1]
    lyr = meta_ref[2]
    row0 = i * block_q
    nrows = jnp.clip(valid - row0, 0, block_q)     # active rows this block
    # never walk past the page-table row: an out-of-range page index is a
    # wild DMA, which halts the chip (the XLA arm clamps the same way)
    npages = jnp.minimum(
        block_visits(start, valid, row0, block_q, page_size),
        pt_ref.shape[0])
    if has_visits:
        # the loop bound, exported for tests (lane-dense row; lane 0 read)
        visits_ref[...] = jnp.full(visits_ref.shape, npages, jnp.int32)

    def dma(slot, j):
        pg = pt_ref[j]                 # page j of this sequence, whole
        return [pltpu.make_async_copy(k_hbm.at[lyr, pg], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[lyr, pg], vbuf.at[slot],
                                      sem.at[1, slot])]

    @pl.when(npages > 0)
    def _():                           # a fully-padded block DMAs nothing
        for c in dma(0, 0):
            c.start()

    hd = q_ref.shape[-1]
    dh = hd // nh
    _, segt = head_segments(nh, dh)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    pos = start + row0 + rows                              # [block_q, 1]
    row_ok = rows < nrows
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def body(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < npages)
        def _():                       # overlap: next page's DMA in flight
            for c in dma(1 - slot, j + 1):
                c.start()

        for c in dma(slot, j):
            c.wait()
        k = kbuf[slot].astype(jnp.float32)                 # [ps, nh*dh]
        v = vbuf[slot].astype(jnp.float32)
        if quant:
            # dequantize in-register AFTER the page copy: the DMA moved
            # int8 bytes; only the VMEM-resident working tile widens
            prow = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            k = k * exact_dot(ks_ref[prow, :], segt)
            v = v * exact_dot(vs_ref[prow, :], segt)
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        # absolute-position causality: query at position p sees keys 0..p
        # — within-chunk future tokens mask out exactly like unwritten
        # pages; padded rows (>= valid) contribute nothing
        mask = (kpos <= pos) & row_ok                      # [block_q, ps]
        for h in range(nh):
            sl = slice(h * dh, (h + 1) * dh)
            q = q_ref[:, sl].astype(jnp.float32) * scale   # [block_q, dh]
            s = jax.lax.dot_general(q, k[:, sl], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            m = m_scr[:, sl]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m - m_new)
            m_scr[:, sl] = m_new
            l_scr[:, sl] = l_scr[:, sl] * alpha + jnp.sum(
                p, axis=1, keepdims=True)
            acc_scr[:, sl] = acc_scr[:, sl] * alpha + jnp.dot(
                p, v[:, sl], preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, npages, body, None)
    out = jnp.where(row_ok, acc_scr[...] / jnp.maximum(l_scr[...], 1e-30),
                    0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def prefill_attention(q, k_pages, v_pages, page_table, start, valid, *,
                      layer=None, interpret=None, return_visits=False,
                      block_q=None, k_scale=None, v_scale=None, scale=None):
    """One CHUNK of ragged prefill attention for ONE sequence over paged
    K/V (the chunk's own K/V already written to its pages):

    q          : [C, nh, dh] — the chunk's queries (rows >= valid are
                 bucket padding; their output is zeroed)
    k_pages    : [nl, num_pages, page_size, nh * dh] — the stored pool,
                 read at ``layer`` (without ``layer``: one layer's pool,
                 the decode kernel's "Layout" note)
    v_pages    : as k_pages
    page_table : [pages_per_slot] int32 — THIS sequence's page row
    start      : scalar int32 — absolute position of q[0]
    valid      : scalar int32 — true token count in this chunk
    k_scale/v_scale : optional [nl, num_pages, page_size, nh] f32 (int8
                 pools)
    scale      : what multiplies the scores (None: ``1 / sqrt(dh)``)
    returns    : [C, nh, dh] in q.dtype; with ``return_visits=True`` also
                 the page-loop trip counts [ceil(C / block_q), nh] int32
                 (one walk serves every head of a q block, so a row
                 repeats one count) — the ragged-stop proof.

    ``interpret=None`` selects the Pallas interpreter off-TPU (CPU parity
    tests); on TPU the kernel compiles through Mosaic.
    """
    if interpret is None:
        from paddle_tpu.kernels.pallas._compat import default_interpret
        interpret = default_interpret()
    from paddle_tpu.kernels.paged_attention import stored_pools
    k_pages, v_pages, k_scale, v_scale, layer = stored_pools(
        "prefill_attention", k_pages, v_pages, k_scale, v_scale, layer)
    return _stored_call(q, k_pages, v_pages, page_table,
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(valid, jnp.int32),
                        jnp.asarray(layer, jnp.int32), k_scale, v_scale,
                        interpret=bool(interpret),
                        return_visits=bool(return_visits),
                        block_q=None if block_q is None else int(block_q),
                        scale=None if scale is None else float(scale))


@functools.partial(jax.jit, static_argnames=("interpret", "return_visits",
                                             "block_q", "scale"))
def _stored_call(q, k_pages, v_pages, page_table, start, valid, layer,
                 k_scale, v_scale, *, interpret, return_visits, block_q=None,
                 scale=None):
    # the kernel over the stored pools at a TRACED layer, as a function of
    # its own (the decode kernel's `_stored_call` is its twin): every layer
    # of a prefill program is the same call of it, so a program traces the
    # kernel's body and lowers it through Mosaic once, not once a layer
    # (which was 4.8-5.2 s of each prefill program's 5.8-6.9 at GPT-2
    # medium's 24: PERF.md, PR 39)
    quant = k_scale is not None
    c, nh, dh = q.shape
    ps = k_pages.shape[2]
    hd = nh * dh
    if k_pages.shape[-1] != hd:
        raise ValueError(f"{nh} query heads of width {dh} over a pool row of "
                         f"{k_pages.shape[-1]}: grouped queries take the "
                         "xla arm")
    bq = default_block_q(c) if block_q is None else min(int(block_q), c)
    nq = pl.cdiv(c, bq)
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    kern = functools.partial(_prefill_kernel, page_size=ps, block_q=bq,
                             nh=nh, scale=float(scale), quant=quant,
                             has_visits=return_visits)
    rows = pl.BlockSpec((bq, hd), lambda i, *_: (i, 0))
    out_specs = [rows]
    out_shape = [jax.ShapeDtypeStruct((c, hd), q.dtype)]
    if return_visits:
        out_specs.append(pl.BlockSpec((1, 1, 128), lambda i, *_: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nq, 1, 128), jnp.int32))
    in_specs = [
        rows,
        pl.BlockSpec(memory_space=pl.ANY),            # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),            # V pool stays in HBM
    ]
    operands = [q.reshape(c, hd), k_pages, v_pages]
    if quant:
        win = pl.BlockSpec((page_table.shape[0] * ps, nh),
                           lambda i, *_: (0, 0))
        in_specs += [win, win]
        operands += [scale_window(k_scale, page_table, layer),
                     scale_window(v_scale, page_table, layer)]
    meta = jnp.stack([start, valid, layer])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, ps, hd), k_pages.dtype),   # K double buffer
            pltpu.VMEM((2, ps, hd), v_pages.dtype),   # V double buffer
            pltpu.SemaphoreType.DMA((2, 2)),          # (k|v, slot)
            pltpu.VMEM((bq, hd), jnp.float32),        # running max
            pltpu.VMEM((bq, hd), jnp.float32),        # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),        # accumulator
        ],
    )
    with x64_off_scope():
        outs = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(meta, page_table.astype(jnp.int32), *operands)
    out = outs[0].reshape(c, nh, dh)
    if return_visits:
        return out, jnp.broadcast_to(outs[1][:, 0, :1], (nq, nh))
    return out
