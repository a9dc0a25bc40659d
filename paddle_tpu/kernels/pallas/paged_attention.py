"""Authored Pallas TPU ragged paged-attention decode kernel (arxiv 2604.15464).

The XLA reference path (`kernels/paged_attention.py`) materializes the FULL
padded ``[B, pages_per_slot * page_size, nh, dh]`` K and V windows per layer
per step — HBM traffic and FLOPs scale with the pool's *capacity*, not the
live sequences' lengths. This kernel is the drop-in the reference module was
shaped for:

- **grid over sequences** — one grid cell owns one sequence and produces
  the context vectors of all its heads;
- **a block of pages a loop turn** — the K/V pools stay in HBM
  (``memory_space=ANY``). A turn takes ``block_pages`` of the sequence's
  pages (256 tokens for the GPT-2 widths; read off the pool's shape and
  dtype, see :func:`block_pages`): all of the block's page copies are
  started together into one ``[tokens, nh * dh]`` VMEM buffer for K and one
  for V, and the block is folded into a running online softmax (max, denom,
  accumulator per head, f32) in ONE update. The buffers are a ring of
  ``DEPTH + 1`` slots: the copies of the next ``DEPTH`` turns are in flight
  while a turn is reduced, and because the grid runs in sequence and the
  ring outlives a cell, those turns run on into the NEXT sequences — a
  sequence's first block is on its way before its cell begins;
- **length-aware stop** — only the pages a sequence has,
  ``ceil((pos[b]+1) / page_size)`` clamped to the page-table row, are ever
  fetched (``visits``); a block's tail beyond them is not fetched but
  masked, and a turn reduces no more leading rows of its block than the
  smallest of three rungs (an eighth, a half, the whole) that holds its
  live tokens. Compute AND DMA traffic scale with each sequence's true
  length instead of ``pages_per_slot``: a 1-token sequence in a 4096-token
  slot copies one page and reduces 32 rows.

**Arithmetic.** Heads sit on sublanes and the lane axis is never split: q
enters block-diagonal, ``Qd [heads, nh * dh]`` (row h holds q's head h on
that head's dh lanes), so the block's scores are one MXU product ``Qd x
K_block^T -> [heads, tokens]`` (lane-dense for the softmax) and its values
another, ``P [heads, tokens] x V_block -> [heads, nh * dh]``, of which row
h's own dh lanes are kept. No operand is rounded below what the pool
holds: against a bf16 pool the left operand goes in as bf16 pieces that sum
to it exactly (a bf16 q is one piece; the f32 probabilities are three), one
bf16 pass with f32 accumulation, whose products are exact in f32; an f32
pool, and an int8 pool dequantised after its copies land, take the f32
``HIGHEST`` form (:func:`_lossless_dot`).

**Differential pairs** (``value_heads=2``; `kernels/diff_attention.py::
diff_attention_paged`, Phi-4-mini-flash's shared cache: 40 query heads over
20 K/V heads of 64). The same walk, plan, ring and stop; what differs is a
fact of the heads, in two places. Which query head reads which K head:
query head ``(p * g + j) * 2 + c`` reads K head ``2p + c``, the wrapper's
one transpose on the way in and one on the way out. And what a row keeps
of its mix: not its own head's ``dh`` lanes but its PAIR's ``2 * dh`` (V
heads ``2p`` and ``2p + 1`` are one value head of twice the width), so the
two rows of a pair cannot share an output row: the result is ``[B, 2 * g,
nkv * dh]`` float32, each row's softmax normalised, and the difference of a
pair's two softmaxes and its norm are the caller's. This form mixes from
probabilities ROUNDED to the pool's type, as its XLA arm does (one bf16
pass where `_lossless_dot` makes three of float32 probabilities: 48 rows
against a ``[tokens, 1280]`` block are paced by loading the block into the
MXU, and three passes would pace the kernel), and a sequence whose ``pos``
is negative is a dead slot: no turn, no copy, zeros. With ``value_heads=1``
none of this is traced.

**Layout.** The chip's compiler only slices a page out of a pool whose last
two dims are tile-aligned, and ``(nh, dh) = (12, 64)`` or ``(16, 64)`` is
not (dh < 128 lanes). The engine therefore STORES the pool merged and
stacked over layers, ``[num_layers, num_pages, page_size, nh * dh]`` (768,
1024 or 1280 lanes; a bf16 page of 16 rows is whole ``(16, 128)`` tiles),
and this kernel takes that array as it is: it stays in HBM, the layer index
arrives with the scalar-prefetched operands (one kernel body for every
layer of a program), and each DMA reads ``pool[layer, page]``. Nothing of
the pool is sliced, reshaped or copied on the way in. A caller that holds
ONE layer's pool (``[num_pages, page_size, nh, dh]``, or merged rank 3) may
leave ``layer`` out: the pool is then viewed as a stack of one, which on a
TPU is a copy of that whole pool per call
(`kernels.paged_attention.stored_pools` counts such calls); no step program
of the engine does that.

Numerics match the reference: f32 scores, f32 online softmax, masked tail
positions excluded — parity with the XLA path is enforced by
tests/test_paged_pallas.py in interpret mode on CPU; on TPU the kernel
compiles through Mosaic (tests/test_tpu_compile.py). Selection between the
two lives in `kernels/paged_attention.py` (``FLAGS_tpu_paged_impl``),
measured winners in `kernels/registry.py`; the block a build chose is
counted in ``kernel.paged_block.{pages}`` (docs/OBSERVABILITY.md).
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
    """Pages the kernel fetches for position ``pos`` — the length-aware
    stop: ``ceil((pos + 1) / page_size)``, NOT pages_per_slot."""
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


# Read on the chip at GPT-2 medium's width (24 sequences, bf16): 256
# tokens a turn beat 128 on every mix (a turn is bound by the latency of
# its chain of two MXU products, not by their size, so fewer turns win:
# 170 live tokens are one turn, not two) and 512 gained nothing more;
# two turns ahead is as good as any deeper ring.
BLOCK_TOKENS = 256      # tokens a loop turn takes
DEPTH = 2               # turns whose copies are in flight ahead of a turn
VMEM_BUDGET = 8 << 20   # bytes the K and V rings may take together


def block_pages(page_size, hd, itemsize):
    """Pages a loop turn of the kernel takes, from the pool's shape:
    ``BLOCK_TOKENS`` tokens' worth, halved while the ``DEPTH + 1`` K and V
    buffers of ``[tokens, hd]`` would pass ``VMEM_BUDGET``, and never under
    one page."""
    tokens = BLOCK_TOKENS
    while (tokens > page_size
           and 2 * (DEPTH + 1) * tokens * hd * itemsize > VMEM_BUDGET):
        tokens //= 2
    return max(1, tokens // page_size)


def _bf16_pieces(x):
    """``x`` as bf16 arrays whose sum is ``x`` exactly: itself if it is
    bf16, else the three 8-bit slices of an f32 mantissa."""
    if x.dtype == jnp.bfloat16:
        return [x]
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return [hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _lossless_dot(a, b, dims):
    """``dot_general(a, b)`` over ``[rows, c]`` operands with f32
    accumulation and no operand rounded. A bf16 ``b`` (a bf16 pool's K or
    V block) is exact as the MXU takes it, so ``a`` goes in as its bf16
    pieces stacked over the rows — ONE bf16 pass, whose products are exact
    in f32 — and the pieces' results are summed. Any other ``b`` takes the
    f32 ``HIGHEST`` form."""
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(a.astype(jnp.float32), b, (dims, ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.HIGHEST)
    pieces = _bf16_pieces(a)
    n = a.shape[0]
    out = jax.lax.dot_general(jnp.concatenate(pieces, axis=0), b,
                              (dims, ((), ())),
                              preferred_element_type=jnp.float32)
    return sum(out[i * n:(i + 1) * n] for i in range(len(pieces)))


def _decode_kernel(pos_ref, pt_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                   page_size, nh, bp, scale, g=1, vh=1, quant=False,
                   has_visits=False):
    # one grid cell per sequence b, all heads at once: q_ref [1, g, nkv*dh]
    # in VMEM (``nh`` query heads over ``nkv = nh / g`` K/V heads; row j
    # holds, on K/V head k's lanes, query head k * g + j; g = 1: the one
    # row of GPT-2's one-to-one heads), k_hbm/v_hbm the stacked [nl, num_pages, page_size, nh*dh]
    # pools in HBM, pos/page_table/layer scalar-prefetched into SMEM (the
    # layer is an operand, not a constant: every layer of a program runs
    # this one kernel). ``vh`` K/V heads' lanes are what a row keeps of its
    # mix: 1, its own head's (o_ref [1, g, nkv*dh]); 2, its PAIR's (the
    # differential form, module docstring: o_ref [1, 2*g, nkv*dh] float32,
    # and row j of q holds, on K/V head p*2+c's lanes, query head
    # (p*g+j)*2+c).
    # Operand order is
    # inputs (q, k, v[, k_scale, v_scale]), outputs (o[, visits]), scratch
    # (kbuf, vbuf, sem, ring); ``quant`` and ``has_visits`` are static
    # flags, never inferred from argument counts. Under ``quant`` the pools
    # are int8 and ks_ref/vs_ref hold this sequence's [1, >= maxp*ps, nh]
    # f32 scale window (a [page_size, nh] page slice of the scale pool is
    # below one tile, so the window is gathered by XLA, 1/dh of the value
    # bytes); the dequant happens in-register after the block's copies
    # land, so the page DMA traffic is the int8 bytes.
    #
    # A loop turn is a BLOCK of ``bp`` pages = ``tokens`` rows: kbuf/vbuf
    # are rings of [nslots, tokens, nh*dh]; turn t of the whole call (cells
    # in order, blocks in order) owns slot t % nslots, and a block's page
    # copies all signal its slot's semaphore (one for K, one for V) and
    # are waited one by one. ``ring`` (SMEM, it outlives a cell) holds the
    # turns reduced so far and the cursor (sequence, block) whose copies
    # start next: the cursor runs nslots - 1 turns ahead, on into the next
    # cells' sequences.
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, *rest = rest
    if has_visits:
        visits_ref, *rest = rest
    kbuf, vbuf, sem, ring = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    pos = pos_ref[b]
    lyr = layer_ref[0]
    nslots, tokens, hd = kbuf.shape
    nkv = nh // g
    dh = hd // nkv
    last_page = k_hbm.shape[1] - 1

    def npages_of(seq):
        # never walk past the page-table row: an out-of-range page index
        # is a wild DMA, which halts the chip (the XLA arm clamps the same
        # way). Entries of the row past this count are never read.
        n = jnp.clip(pages_needed(pos_ref[seq], page_size), 1,
                     pt_ref.shape[1])
        if vh > 1:
            n = jnp.where(pos_ref[seq] < 0, 0, n)           # a dead slot
        return n

    def nblocks_of(seq):
        return (npages_of(seq) + bp - 1) // bp

    def after(seq):
        # the sequence whose block 0 follows ``seq``'s last block: the next
        # one, or (the differential form) the next that is not dead
        if vh == 1:
            return seq + 1
        return jax.lax.while_loop(
            lambda s: (s < nb) & (nblocks_of(jnp.minimum(s, nb - 1)) == 0),
            lambda s: s + 1, seq + 1)

    def copies(seq, j, turn, act):
        # block j of sequence seq <-> the ring slot of its turn: only the
        # pages the sequence has; the block's tail beyond them is not
        # fetched (what an earlier turn left there is masked below)
        slot = jax.lax.rem(turn, nslots)
        first = j * bp
        count = jnp.minimum(bp, npages_of(seq) - first)

        def page(i, _):
            pg = jnp.clip(pt_ref[seq, first + i], 0, last_page)
            rows = pl.ds(pl.multiple_of(i * page_size, page_size), page_size)
            for pool, buf, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                act(pltpu.make_async_copy(pool.at[lyr, pg],
                                          buf.at[slot, rows],
                                          sem.at[kv, slot]))
            return 0

        jax.lax.fori_loop(0, count, page, 0)
        return slot

    def prefetch(turn):
        # start the copies of the turn the cursor stands on, and move the
        # cursor to the turn after it: the next block of its sequence, or
        # block 0 of the next sequence
        seq, j = ring[1], ring[2]

        @pl.when(seq < nb)
        def _():
            copies(seq, j, turn, lambda c: c.start())
            more = j + 1 < nblocks_of(seq)
            ring[1] = jnp.where(more, seq, after(seq))
            ring[2] = jnp.where(more, j + 1, 0)

    @pl.when(b == 0)
    def _():
        # a masked position's probability is 0, and 0 * NaN is NaN: V's
        # never-fetched rows must hold numbers, which pool data is and
        # fresh VMEM need not be
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        ring[0] = 0
        ring[1] = after(-1)
        ring[2] = 0
        for turn in range(nslots - 1):
            prefetch(turn)

    npages = npages_of(b)
    nblocks = nblocks_of(b)
    # the last position attended: pos, or the row's last token where pos
    # lies past the row (rows beyond the fetched pages hold no data)
    pos = jnp.minimum(pos, npages * page_size - 1)
    turn0 = ring[0]
    ring[0] = turn0 + nblocks
    if has_visits:
        # the pages fetched, exported for tests (lane-dense row; lane 0
        # read)
        visits_ref[...] = jnp.full(visits_ref.shape, npages, jnp.int32)

    # heads live on SUBLANES here, padded to whole bf16 tiles: row r =
    # j * nkv + k is query head k * g + j, and ``own`` marks the dh lanes
    # of ITS K/V head k (a padded row marks none, so it scores 0 everywhere
    # and is dropped by the final reduce); q goes in block-diagonal,
    # [nhp, nkv*dh]
    nhp = -(-nh // 16) * 16
    lane = jax.lax.broadcasted_iota(jnp.int32, (nhp, hd), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (nhp, hd), 0)
    lane0 = jax.lax.rem(head, nkv) * dh
    own = ((lane >= lane0) & (lane < lane0 + dh)
           & (head < nh)).astype(jnp.float32)
    if g == 1:
        q_rows = q_ref[0]
    else:
        q_rows = jnp.concatenate(
            [jnp.broadcast_to(q_ref[0, j:j + 1], (nkv, hd))
             for j in range(g)]
            + [jnp.zeros((nhp - nh, hd), q_ref.dtype)] * (nhp > nh), axis=0)
    qd = (own * q_rows.astype(jnp.float32)).astype(q_ref.dtype)
    nt = ((1,), (1,))                  # [r, c] x [t, c] -> [r, t]
    nn = ((1,), (0,))                  # [r, t] x [t, c] -> [r, c]
    # rows of a block a turn may reduce: an eighth, a half (where those
    # are whole int8 tiles of 32 rows), the whole
    rungs = [r for r in (tokens // 8, tokens // 2) if r % 32 == 0] + [tokens]

    def reduce(rows, j, slot, m, l, acc):
        # fold the block's first ``rows`` rows into the running softmax
        k = kbuf[slot, :rows]                              # [rows, nh*dh]
        v = vbuf[slot, :rows]
        if k.dtype != jnp.bfloat16:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        if quant:
            # dequantize in-register AFTER the copies: the DMAs moved int8
            # bytes; only the VMEM-resident working block widens
            at = pl.ds(pl.multiple_of(j * tokens, tokens), rows)
            k = k * exact_dot(ks_ref[0, at, :], own[:nkv])
            v = v * exact_dot(vs_ref[0, at, :], own[:nkv])
        s = _lossless_dot(qd, k, nt) * scale               # [nhp, rows]
        kpos = j * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)  # the block's tail past pos
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # [nhp,1]
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if vh > 1:
            # the differential form mixes from probabilities rounded to
            # the pool's type, as its XLA arm does: one pass
            p = p.astype(v.dtype)
        acc_new = acc * alpha + _lossless_dot(p, v, nn)    # [nhp, nh*dh]
        return m_new, l_new, acc_new

    def body(j, carry):
        turn = turn0 + j
        prefetch(turn + nslots - 1)
        slot = copies(b, j, turn, lambda c: c.wait())
        # the smallest rung that holds the block's live tokens: a one-page
        # sequence costs a short turn, not a block
        live = pos + 1 - j * tokens
        rung = sum((live > r).astype(jnp.int32) for r in rungs[:-1])
        return jax.lax.switch(
            rung, [functools.partial(reduce, r) for r in rungs],
            j, slot, *carry)

    m0 = jnp.full((nhp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nhp, 1), jnp.float32)
    a0 = jnp.zeros((nhp, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))
    # row r of acc holds its head's sum over ALL lanes; keep its own dh
    # (one nonzero term a lane among the nkv rows of one j, so these
    # reduces are exact)
    if vh == 1:
        num, den = acc * own, jnp.maximum(l, 1e-30) * own
        for j in range(g):
            rows = slice(j * nkv, (j + 1) * nkv) if g > 1 else slice(None)
            o_ref[0, j:j + 1] = (
                jnp.sum(num[rows], axis=0, keepdims=True)
                / jnp.sum(den[rows], axis=0, keepdims=True)
            ).astype(o_ref.dtype)
        return
    # a row keeps the lanes of its GROUP of vh K/V heads (k // vh), which
    # the group's vh rows of one j share: output row j * vh + c sums the
    # rows whose K/V head is c within its group, again one term a lane. A
    # dead slot took no turn: 0 / 1e-30
    normed = acc / jnp.maximum(l, 1e-30)
    kv = jax.lax.rem(head, nkv)
    group0 = jax.lax.div(kv, vh) * (vh * dh)
    mine = (lane >= group0) & (lane < group0 + vh * dh) & (head < nh)
    for j in range(g):
        for c in range(vh):
            keep = mine & (jax.lax.div(head, nkv) == j) \
                & (jax.lax.rem(kv, vh) == c)
            o_ref[0, j * vh + c:j * vh + c + 1] = jnp.sum(
                jnp.where(keep, normed, 0.0), axis=0, keepdims=True)


def scale_window(scales, page_table, layer):
    """[..., pages_per_slot] page rows -> the rows' ``[..., pages_per_slot *
    page_size, nh]`` f32 scale window of one layer of an int8 pool's
    stacked ``[nl, num_pages, page_size, nh]`` scales (one gather)."""
    win = scales[layer, page_table].astype(jnp.float32)
    return win.reshape(*page_table.shape[:-1], -1, scales.shape[-1])


def paged_attention(q, k_pages, v_pages, page_table, pos, *, layer=None,
                    interpret=None, return_visits=False, k_scale=None,
                    v_scale=None, scale=None, value_heads=1, op=None):
    """One decode step of ragged paged attention. Same contract as the XLA
    reference `kernels.paged_attention.paged_attention`:

    q          : [B, nh, dh] current-token query; ``nh`` may be ``g`` x the
                 pool's heads (grouped queries: head h reads K/V head
                 h // g)
    k_pages    : [nl, num_pages, page_size, nkv * dh] — the stored pool,
                 read at ``layer`` (without ``layer``: one layer's pool,
                 see "Layout")
    v_pages    : as k_pages
    page_table : [B, pages_per_slot] int32
    pos        : [B] int32 — attends positions 0..pos inclusive
    k_scale/v_scale : optional [nl, num_pages, page_size, nh] f32 — int8
                 pools: the dequant runs in-register after a block's
                 copies, so the kernel's page traffic is the int8 bytes
                 (~1/4 of f32)
    scale      : what multiplies the scores (None: ``1 / sqrt(dh)``)
    value_heads: 2 is the differential form ("Differential pairs" above):
                 returns [B, nh, 2 * dh] float32, head h's mix over the
                 lanes of its K/V head's PAIR, not normalised against its
                 twin; ``pos`` < 0 is a dead slot: no page fetched, zeros
    op         : the op the block is counted for, where it is not this
                 module's own (``kernel.paged_block.{op}.{pages}``)
    returns    : [B, nh, dh] in q.dtype; with ``return_visits=True`` also
                 the pages fetched [B, nh] int32 (one walk serves every
                 head of a sequence, so a row repeats one count) — the
                 ragged-stop proof the parity tests assert on.

    ``interpret=None`` selects the Pallas interpreter off-TPU (CPU parity
    tests); on TPU the kernel compiles through Mosaic.
    """
    if interpret is None:
        from paddle_tpu.kernels.pallas._compat import default_interpret
        interpret = default_interpret()
    from paddle_tpu.kernels.paged_attention import stored_pools
    k_pages, v_pages, k_scale, v_scale, layer = stored_pools(
        "paged_attention", k_pages, v_pages, k_scale, v_scale, layer)
    from paddle_tpu.kernels import registry
    registry.count_paged_block(block_pages(
        k_pages.shape[2], k_pages.shape[3], k_pages.dtype.itemsize), op=op)
    return _stored_call(q, k_pages, v_pages, page_table, pos,
                        jnp.asarray(layer, jnp.int32), k_scale, v_scale,
                        interpret=bool(interpret),
                        return_visits=bool(return_visits),
                        scale=None if scale is None else float(scale),
                        value_heads=int(value_heads))


@functools.partial(jax.jit, static_argnames=("interpret", "return_visits",
                                             "scale", "value_heads"))
def _stored_call(q, k_pages, v_pages, page_table, pos, layer, k_scale,
                 v_scale, *, interpret, return_visits, scale=None,
                 value_heads=1):
    # the kernel over the stored pools at a TRACED layer, as a function of
    # its own: every layer of a step program is the same call of it, so a
    # program traces and lowers the kernel once, not once a layer (which
    # was 4 s of each start for GPT-2 medium's 24)
    quant = k_scale is not None
    b, nh, dh = q.shape
    ps, hd = k_pages.shape[2:]
    from paddle_tpu.kernels.paged_attention import query_groups
    g = query_groups(nh, dh, hd)
    nkv = nh // g
    vh = value_heads
    if nkv % vh:
        raise ValueError(f"{nkv} K/V heads in groups of {vh}")
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    bp = block_pages(ps, hd, k_pages.dtype.itemsize)
    kern = functools.partial(_decode_kernel, page_size=ps, nh=nh, bp=bp,
                             scale=float(scale), g=g, vh=vh, quant=quant,
                             has_visits=return_visits)
    row = pl.BlockSpec((1, g, hd), lambda i, *_: (i, 0, 0))
    out_specs = [row if vh == 1 else
                 pl.BlockSpec((1, g * vh, hd), lambda i, *_: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct(
        (b, g * vh, hd), q.dtype if vh == 1 else jnp.float32)]
    if return_visits:
        out_specs.append(pl.BlockSpec((1, 1, 128), lambda i, *_: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, 1, 128), jnp.int32))
    in_specs = [
        row,
        pl.BlockSpec(memory_space=pl.ANY),            # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),            # V pool stays in HBM
    ]
    # row j of a sequence's q: query head k * g + j on K/V head k's lanes
    # (vh > 1: head (p * g + j) * vh + c on those of K/V head p * vh + c)
    q_rows = q.reshape(b, nkv, g, dh).swapaxes(1, 2) if vh == 1 else \
        q.reshape(b, nkv // vh, g, vh, dh).transpose(0, 2, 1, 3, 4)
    operands = [q_rows.reshape(b, g, hd), k_pages, v_pages]
    if quant:
        # whole blocks of scales, so that the last block's rows exist; and
        # zeros past the pages a sequence has, whose table entries may name
        # any page: a block's unfetched tail then dequantises to 0
        maxp = page_table.shape[1]
        rows = -(-maxp // bp) * bp * ps
        live = jnp.minimum(pages_needed(pos, ps), maxp) * ps
        keep = (jnp.arange(rows) < live[:, None])[..., None]
        win = pl.BlockSpec((1, rows, nkv), lambda i, *_: (i, 0, 0))
        in_specs += [win, win]
        operands += [jnp.where(keep, jnp.pad(
            scale_window(s, page_table, layer),
            ((0, 0), (0, rows - maxp * ps), (0, 0))), 0.0)
            for s in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((DEPTH + 1, bp * ps, hd), k_pages.dtype),  # K ring
            pltpu.VMEM((DEPTH + 1, bp * ps, hd), v_pages.dtype),  # V ring
            pltpu.SemaphoreType.DMA((2, DEPTH + 1)),        # (k|v, slot)
            # the ring's state across cells: turns reduced so far, and
            # the (sequence, block) whose copies start next
            pltpu.SMEM((3,), jnp.int32),
        ],
    )
    with x64_off_scope():
        outs = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=out_shape,
            # in sequence: a cell starts the next cell's first copies
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(pos.astype(jnp.int32), page_table.astype(jnp.int32),
          layer.reshape(1), *operands)
    if vh == 1:
        out = outs[0].reshape(b, g, nkv, dh).swapaxes(1, 2) \
            .reshape(b, nh, dh)
    else:
        # row j * vh + c, group p's lanes -> head (p * g + j) * vh + c
        out = outs[0].reshape(b, g, vh, nkv // vh, vh * dh) \
            .transpose(0, 3, 1, 2, 4).reshape(b, nh, vh * dh)
    if return_visits:
        return out, jnp.broadcast_to(outs[1][:, 0, :1], (b, nh))
    return out
