"""Authored Pallas TPU ragged paged-attention decode kernel (arxiv 2604.15464).

The XLA reference path (`kernels/paged_attention.py`) materializes the FULL
padded ``[B, pages_per_slot * page_size, nh, dh]`` K and V windows per layer
per step — HBM traffic and FLOPs scale with the pool's *capacity*, not the
live sequences' lengths. This kernel is the drop-in the reference module was
shaped for:

- **grid over sequences** — one grid cell owns one sequence and produces
  the context vectors of all its heads;
- **pages streamed block-by-block** — the K/V pools stay in HBM
  (``memory_space=ANY``); each cell DMAs one whole ``[page_size, nh * dh]``
  page at a time into a double-buffered VMEM scratch (next page's DMA in
  flight while the current page is being reduced) and folds it into a
  running online softmax (max, denom, accumulator) per head;
- **length-aware stop** — the page loop's trip count is
  ``ceil((pos[b]+1) / page_size)``, read from the scalar-prefetched ``pos``,
  so compute AND DMA traffic scale with each sequence's true length instead
  of ``pages_per_slot``. A 1-token sequence in a 4096-token slot touches one
  page, not 256.

**Layout.** The chip's compiler only slices a page out of a pool whose last
two dims are tile-aligned, and ``(nh, dh) = (12, 64)`` or ``(16, 64)`` is
not (dh < 128 lanes). The engine therefore STORES the pool merged and
stacked over layers, ``[num_layers, num_pages, page_size, nh * dh]`` (768,
1024 or 1280 lanes; a bf16 page of 16 rows is whole ``(16, 128)`` tiles),
and this kernel takes that array as it is: it stays in HBM, the layer index
arrives with the scalar-prefetched operands (one kernel body for every
layer of a program), and each DMA reads ``pool[layer, page]``. Nothing of
the pool is sliced, reshaped or copied on the way in. The lane axis is never
split: per-head sums and broadcasts are small matmuls against a 0/1
head-segment matrix. A caller that holds ONE layer's pool
(``[num_pages, page_size, nh, dh]``, or merged rank 3) may leave ``layer``
out: the pool is then viewed as a stack of one, which on a TPU is a copy of
that whole pool per call (`kernels.paged_attention.stored_pools` counts
such calls); no step program of the engine does that.

Numerics match the reference: f32 scores, f32 online softmax, masked tail
positions excluded — parity with the XLA path is enforced by
tests/test_paged_pallas.py in interpret mode on CPU; on TPU the kernel
compiles through Mosaic (tests/test_tpu_compile.py). Selection between the
two lives in `kernels/paged_attention.py` (``FLAGS_tpu_paged_impl``),
measured winners in `kernels/autotune.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope

NEG_INF = -1e30


def pages_needed(pos, page_size):
    """Trip count of the kernel's page loop for position ``pos`` — the
    length-aware stop: ``ceil((pos + 1) / page_size)``, NOT pages_per_slot."""
    return (pos + page_size) // page_size


def head_segments(nh, dh):
    """0/1 matrices mapping the merged ``nh * dh`` lane axis to heads:
    ``seg [nh*dh, nh]`` sums each head's dh lanes (``x @ seg``) and its
    transpose ``[nh, nh*dh]`` broadcasts a per-head value back over them."""
    hd = nh * dh
    seg = (jax.lax.broadcasted_iota(jnp.int32, (hd, nh), 0) // dh
           == jax.lax.broadcasted_iota(jnp.int32, (hd, nh), 1))
    segt = (jax.lax.broadcasted_iota(jnp.int32, (nh, hd), 1) // dh
            == jax.lax.broadcasted_iota(jnp.int32, (nh, hd), 0))
    return seg.astype(jnp.float32), segt.astype(jnp.float32)


def exact_dot(a, b):
    # exact f32: the segment matrices are 0/1, so the only rounding left is
    # the accumulation order
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _decode_kernel(pos_ref, pt_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                   page_size, nh, scale, quant=False, has_visits=False):
    # one grid cell per sequence b, all heads at once: q_ref [1, 1, nh*dh]
    # in VMEM, k_hbm/v_hbm the stacked [nl, num_pages, page_size, nh*dh]
    # pools in HBM, pos/page_table/layer scalar-prefetched into SMEM (the
    # layer is an operand, not a constant: every layer of a program runs
    # this one kernel). Operand order is
    # inputs (q, k, v[, k_scale, v_scale]), outputs (o[, visits]), scratch
    # (kbuf, vbuf, sem); ``quant`` and ``has_visits`` are static flags,
    # never inferred from argument counts. Under ``quant`` the pools are
    # int8 and ks_ref/vs_ref hold this sequence's [1, maxp*ps, nh] f32
    # scale window (a [page_size, nh] page slice of the scale pool is
    # below one tile, so the window is gathered by XLA, 1/dh of the value
    # bytes); the dequant happens in-register after the page copy lands,
    # so the page DMA traffic is the int8 bytes.
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, *rest = rest
    if has_visits:
        visits_ref, *rest = rest
    kbuf, vbuf, sem = rest
    b = pl.program_id(0)
    pos = pos_ref[b]
    lyr = layer_ref[0]
    # never walk past the page-table row: an out-of-range page index is a
    # wild DMA, which halts the chip (the XLA arm clamps the same way)
    npages = jnp.minimum(pages_needed(pos, page_size), pt_ref.shape[1])
    if has_visits:
        # the loop bound, exported for tests (lane-dense row; lane 0 read)
        visits_ref[...] = jnp.full(visits_ref.shape, npages, jnp.int32)

    def dma(slot, j):
        # page j of sequence b: the whole page from HBM into the double
        # buffer
        pg = pt_ref[b, j]
        return [pltpu.make_async_copy(k_hbm.at[lyr, pg], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[lyr, pg], vbuf.at[slot],
                                      sem.at[1, slot])]

    for c in dma(0, 0):
        c.start()
    hd = q_ref.shape[-1]
    seg, segt = head_segments(nh, hd // nh)
    q = q_ref[0].astype(jnp.float32) * scale                   # [1, nh*dh]

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < npages)
        def _():                       # overlap: next page's DMA in flight
            for c in dma(1 - slot, j + 1):
                c.start()

        for c in dma(slot, j):
            c.wait()
        k = kbuf[slot].astype(jnp.float32)                     # [ps, nh*dh]
        v = vbuf[slot].astype(jnp.float32)
        if quant:
            # dequantize in-register AFTER the page copy: the DMA moved
            # int8 bytes; only the VMEM-resident working tile widens
            rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            k = k * exact_dot(ks_ref[0, rows, :], segt)
            v = v * exact_dot(vs_ref[0, rows, :], segt)
        s = exact_dot(k * q, seg)                                   # [ps, nh]
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        s = jnp.where(kpos <= pos, s, NEG_INF)  # tail of the last page
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))  # [1, nh]
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc_new = acc * exact_dot(alpha, segt) + jnp.sum(
            exact_dot(p, segt) * v, axis=0, keepdims=True)          # [1, nh*dh]
        return m_new, l_new, acc_new

    m0 = jnp.full((1, nh), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, nh), jnp.float32)
    a0 = jnp.zeros((1, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, npages, body, (m0, l0, a0))
    o_ref[0] = (acc / exact_dot(jnp.maximum(l, 1e-30), segt)).astype(o_ref.dtype)


def scale_window(scales, page_table, layer):
    """[..., pages_per_slot] page rows -> the rows' ``[..., pages_per_slot *
    page_size, nh]`` f32 scale window of one layer of an int8 pool's
    stacked ``[nl, num_pages, page_size, nh]`` scales (one gather)."""
    win = scales[layer, page_table].astype(jnp.float32)
    return win.reshape(*page_table.shape[:-1], -1, scales.shape[-1])


def paged_attention(q, k_pages, v_pages, page_table, pos, *, layer=None,
                    interpret=None, return_visits=False, k_scale=None,
                    v_scale=None):
    """One decode step of ragged paged attention. Same contract as the XLA
    reference `kernels.paged_attention.paged_attention`:

    q          : [B, nh, dh] current-token query
    k_pages    : [nl, num_pages, page_size, nh * dh] — the stored pool,
                 read at ``layer`` (without ``layer``: one layer's pool,
                 see "Layout")
    v_pages    : as k_pages
    page_table : [B, pages_per_slot] int32
    pos        : [B] int32 — attends positions 0..pos inclusive
    k_scale/v_scale : optional [nl, num_pages, page_size, nh] f32 — int8
                 pools: the dequant runs in-register after each page copy,
                 so the kernel's page traffic is the int8 bytes (~1/4 of
                 f32)
    returns    : [B, nh, dh] in q.dtype; with ``return_visits=True`` also
                 the page-loop trip counts [B, nh] int32 (one walk serves
                 every head of a sequence, so a row repeats one count) —
                 the ragged-stop proof the parity tests assert on.

    ``interpret=None`` selects the Pallas interpreter off-TPU (CPU parity
    tests); on TPU the kernel compiles through Mosaic.
    """
    if interpret is None:
        from paddle_tpu.kernels.pallas._compat import default_interpret
        interpret = default_interpret()
    from paddle_tpu.kernels.paged_attention import stored_pools
    k_pages, v_pages, k_scale, v_scale, layer = stored_pools(
        "paged_attention", k_pages, v_pages, k_scale, v_scale, layer)
    quant = k_scale is not None
    b, nh, dh = q.shape
    ps = k_pages.shape[2]
    hd = nh * dh
    scale = 1.0 / (dh ** 0.5)
    kern = functools.partial(_decode_kernel, page_size=ps, nh=nh,
                             scale=float(scale), quant=quant,
                             has_visits=bool(return_visits))
    row = pl.BlockSpec((1, 1, hd), lambda i, *_: (i, 0, 0))
    out_specs = [row]
    out_shape = [jax.ShapeDtypeStruct((b, 1, hd), q.dtype)]
    if return_visits:
        out_specs.append(pl.BlockSpec((1, 1, 128), lambda i, *_: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, 1, 128), jnp.int32))
    in_specs = [
        row,
        pl.BlockSpec(memory_space=pl.ANY),            # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),            # V pool stays in HBM
    ]
    operands = [q.reshape(b, 1, hd), k_pages, v_pages]
    if quant:
        win = pl.BlockSpec((1, page_table.shape[1] * ps, nh),
                           lambda i, *_: (i, 0, 0))
        in_specs += [win, win]
        operands += [scale_window(k_scale, page_table, layer),
                     scale_window(v_scale, page_table, layer)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, ps, hd), k_pages.dtype),   # K double buffer
            pltpu.VMEM((2, ps, hd), v_pages.dtype),   # V double buffer
            pltpu.SemaphoreType.DMA((2, 2)),          # (k|v, slot)
        ],
    )
    with x64_off_scope():
        outs = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=bool(interpret),
        )(pos.astype(jnp.int32), page_table.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    out = outs[0].reshape(b, nh, dh)
    if return_visits:
        return out, jnp.broadcast_to(outs[1][:, 0, :1], (b, nh))
    return out
