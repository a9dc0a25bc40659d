"""Authored Pallas TPU kernel of a decode step's latent (MLA) attention in
the absorbed form over a paged latent pool: the Pallas arm of
`kernels/mla.py::latent_decode_paged`.

The XLA arm walks every slot's context a block of keys at a time: each turn
gathers the block's pages of every slot into a copy in HBM (written once,
read for the scores and again for the mix), and the block's ``[slots, heads,
keys]`` float32 scores go through HBM for the maximum, for ``exp`` and the
sum and for the product with the values; every slot is walked to the
FURTHEST query. Here the pages are read where they lie and a turn's scores
never leave VMEM:

- **the pool stays in HBM** (``memory_space=ANY``), ``[layers, P, page,
  W]`` as the engine stores it, never sliced, reshaped or copied; the layer
  index, the page table and what was found in it arrive by scalar prefetch,
  and every copy reads ``pool[layer, page]``: one kernel body for every
  layer of a program;
- **grid over sequences**, in order; a sequence's ``[H, W]`` query (``[q_abs
  | q_rope | 0...]``) is resident while its context streams past. A loop
  turn takes a BLOCK of pages (`plan`: read off the shapes inside
  ``VMEM_BUDGET``) into one ``[block keys, W]`` buffer of a ring of
  ``DEPTH + 1``: the next ``DEPTH`` turns' copies are in flight while a
  turn is reduced, and because the ring outlives a grid cell they run on
  into the NEXT sequences, as `pallas/paged_attention.py`'s ring does;
- **a run of consecutive page ids is one copy.** A page of 16 rows x 640
  lanes of bf16 is 20 KB, 24 ns at the memory's rate: copy by copy the
  scalar core's issue would set the pace, not the bytes. The table shows
  which of a row's groups of ``plan.run`` entries hold CONSECUTIVE ids
  (found once a call, before the kernel: `_runs`), and such a group, where
  the sequence has all of it, is fetched by one copy of ``run`` pages; any
  other group page by page. Any table is read correctly; one whose rows were
  allocated in order (a prefix store's contexts) costs an eighth of the
  copies;
- **length-aware**: only the pages a sequence has, ``qpos // page + 1``
  clamped to its row, are fetched; a block's tail beyond them is masked,
  not fetched; a dead slot (``qpos`` < 0) takes no turn, fetches nothing and
  returns zeros; entries of a row past a sequence's pages (the trash page)
  are never read as pages;
- **a turn's arithmetic** is the XLA arm's at every point: the scores ``q x
  block^T -> [H, keys]`` in float32 from ONE product over the whole row
  (the lanes past ``rank + rope`` are zeros in both operands), the scale,
  the mask ``s <= qpos`` (-1e30), the running maximum and sum a head and
  the ``[H, rank]`` output in float32 VMEM scratch, the mix from
  probabilities rounded to the pool's type against the block's first
  ``rank`` lanes. The SAME block serves as keys and as values, read once. A
  masked key's probability is ``exp(-1e30 - m) = 0`` exactly (every block
  visited holds a key in sight), and the ring is zeroed once a call so that
  a never-fetched row holds numbers.

Parity with the XLA arm in the interpreter: tests/test_mla_pallas.py; on a
TPU the kernel compiles through Mosaic (tests/test_tpu_compile_kimi.py,
tests/test_tpu_compile.py). Which arm a program was built with and the
block it took: ``kernel.dispatch.mla_decode_paged.{xla,pallas}``,
``kernel.paged_block.mla_decode_paged.{pages}`` (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.autograd import x64_off_scope
from paddle_tpu.kernels.pallas.paged_attention import pages_needed

NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
BLOCK_KEYS = 1024       # keys a loop turn takes, where the budget allows
RUN_PAGES = 16          # pages one copy takes where a row's ids are
#                         consecutive
DEPTH = 2               # turns whose copies are in flight ahead of a turn
VMEM_BUDGET = 12 << 20  # bytes the ring and a turn's scores may take
SMEM_BUDGET = 768 << 10  # bytes the prefetched page table and its runs may
#                          take of a v5e's 1 MiB of scalar memory


class Plan(NamedTuple):
    """``block`` pages a loop turn takes, ``run`` pages a merged copy
    takes (``block`` a whole number of them)."""
    block: int
    run: int


def _turn_bytes(keys, h, width, rank, itemsize):
    # the ring; a turn's float32 scores and probabilities and the rounded
    # probabilities; the carried output and the resident query and output
    # blocks, double-buffered
    return (DEPTH + 1) * keys * width * itemsize + h * keys * (8 + itemsize) \
        + h * rank * 4 + 2 * h * (width + rank) * itemsize


def _table_bytes(slots, row_pages, run):
    # int32 [slots, pages] and [slots, pages / run], as scalar memory holds
    # them: rows in eights, entries in lane tiles
    rows = -(-slots // 8) * 8
    pages = -(-row_pages // run) * run
    return 4 * rows * (-(-pages // _LANES) + -(-pages // run // _LANES)) \
        * _LANES


def plan(h, width, rank, page_size, itemsize, slots, row_pages, block=None,
         run=None):
    """The kernel's block from the shapes, or None where it does not fit
    them: the row width and ``rank`` whole lane tiles, a page whole sublane
    tiles of the pool's type (so that a block's pages ARE its rows), the
    heads whole float32 sublane tiles, the page table inside
    ``SMEM_BUDGET`` (32 slots of 5,632 pages, or 128 of 1,408). ``block``
    / ``run`` override the sizes in pages (tests drive tiny ones)."""
    if width % _LANES or rank % _LANES or not 0 < rank <= width \
            or itemsize not in (1, 2, 4) or page_size % (32 // itemsize) \
            or h % 8 or row_pages < 1 or _table_bytes(
                slots, row_pages, run or RUN_PAGES) > SMEM_BUDGET:
        return None
    g = run or RUN_PAGES
    if block is None:
        keys = max(BLOCK_KEYS, g * page_size)
        while keys > g * page_size and _turn_bytes(
                keys, h, width, rank, itemsize) > VMEM_BUDGET:
            keys //= 2
        block = max(g, keys // (g * page_size) * g)
        # no wider than the row, in whole runs
        block = min(block, -(-row_pages // g) * g)
    if block % g:
        return None
    return Plan(block, g)


def _runs(table, run):
    """Which groups of ``run`` entries of each row of ``table`` [B, pages]
    (a whole number of groups) hold consecutive page ids: int32 [B, pages /
    run]."""
    grp = table.reshape(table.shape[0], -1, run)
    return jnp.all(grp[..., 1:] - grp[..., :-1] == 1, axis=-1) \
        .astype(jnp.int32)


def _kernel(layer_ref, np_ref, next_ref, pos_ref, pt_ref, run_ref, q_ref,
            pool, o_ref, *rest, plan, rank, scale, has_visits):
    # one grid cell per sequence b: q_ref [1, H, W] in VMEM, pool the
    # stacked [layers, P, page, W] latent rows in HBM. Scalar-prefetched:
    # the layer; np_ref [B] the pages each sequence has (0: a dead slot);
    # next_ref [B + 1] the first live sequence at or after an index (B:
    # none); pos_ref [B] the last position attended; pt_ref [B, pages] the
    # page table, clipped to the pool; run_ref [B, pages / run] which of a
    # row's groups hold consecutive ids. Scratch: buf [nslots, block, page,
    # W] the ring, sem one DMA semaphore a slot, ring (SMEM, it outlives a
    # cell) the turns reduced so far and the cursor (sequence, block) whose
    # copies start next, m / l [H, 1] and acc [H, rank] the carried softmax.
    #
    # Turn t of the whole call (cells in order, blocks in order) owns slot
    # t % nslots; a block's copies all signal its slot's semaphore.
    if has_visits:
        visits_ref, *rest = rest
    buf, sem, ring, m_scr, l_scr, acc = rest
    bp, g = plan
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    lyr = layer_ref[0]
    nslots, _, ps, width = buf.shape
    kb = bp * ps

    def nblocks_of(seq):
        return jax.lax.div(np_ref[seq] + bp - 1, bp)

    def copies(seq, j, turn, wait):
        # block j of sequence seq <-> the ring slot of its turn, started or
        # (``wait``) waited for: only the pages the sequence has. A DMA
        # semaphore counts bytes, so a whole group is waited for by ONE
        # descriptor of its size however it was started, and a waited
        # descriptor's source only lends its shape. Returns (slot, the
        # copies that STARTED the block: what `return_visits` exports).
        slot = jax.lax.rem(turn, nslots)
        count = jnp.minimum(bp, np_ref[seq] - j * bp)

        def move(src, dst):
            c = pltpu.make_async_copy(src, dst, sem.at[slot])
            c.wait() if wait else c.start()

        def group(r, n):
            at = j * bp + r * g
            have = jnp.minimum(g, count - r * g)
            ran = (have == g) & (run_ref[seq, j * (bp // g) + r] == 1)
            one = have == g if wait else ran

            @pl.when(one)
            def _():
                move(pool.at[lyr, pl.ds(0 if wait else pt_ref[seq, at], g)],
                     buf.at[slot, pl.ds(r * g, g)])

            @pl.when(jnp.logical_not(one))
            def _():
                def page(i, _):
                    move(pool.at[lyr, 0 if wait else pt_ref[seq, at + i]],
                         buf.at[slot, r * g + i])
                    return 0
                jax.lax.fori_loop(0, have, page, 0)
            return n + jnp.where(ran, 1, have)

        return slot, jax.lax.fori_loop(
            0, jax.lax.div(count + g - 1, g), group, 0)

    def prefetch(turn):
        # start the copies of the turn the cursor stands on, and move the
        # cursor to the turn after it: the next block of its sequence, or
        # block 0 of the next LIVE sequence
        seq, j = ring[1], ring[2]

        @pl.when(seq < nb)
        def _():
            copies(seq, j, turn, wait=False)
            more = j + 1 < nblocks_of(seq)
            ring[1] = jnp.where(more, seq, next_ref[seq + 1])
            ring[2] = jnp.where(more, j + 1, 0)

    @pl.when(b == 0)
    def _():
        # a masked key's probability is 0, and 0 * NaN is NaN: the rows a
        # turn does not fetch must hold numbers, which pool data is and
        # fresh VMEM need not be
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        ring[0] = 0
        ring[1] = next_ref[0]
        ring[2] = 0
        for turn in range(nslots - 1):
            prefetch(turn)

    nblocks = nblocks_of(b)
    pos = pos_ref[b]
    turn0 = ring[0]
    ring[0] = turn0 + nblocks
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def body(j, n):
        turn = turn0 + j
        prefetch(turn + nslots - 1)
        slot, made = copies(b, j, turn, wait=True)
        blk = buf[slot].reshape(kb, width)
        s = jax.lax.dot_general(q_ref[0], blk, _NT,
                                preferred_element_type=jnp.float32) * scale
        kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(blk.dtype), blk[:, :rank],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return n + made

    made = jax.lax.fori_loop(0, nblocks, body, 0)
    o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)
    if has_visits:
        # what this sequence fetched, exported for tests: lane 0 the
        # pages, lane 1 the copies that brought them
        lane = jax.lax.broadcasted_iota(jnp.int32, visits_ref.shape, 2)
        visits_ref[...] = jnp.where(lane == 0, np_ref[b], made)


@functools.partial(jax.jit, static_argnames=(
    "plan", "rank", "scale", "interpret", "return_visits"))
def latent_decode_paged(q, lat_pool, layer, table, qpos, *, plan, rank,
                        scale, interpret, return_visits=False):
    """One decode step's queries over everything in their sight, pages read
    in place. The contract of `kernels/mla.py::latent_decode_paged`:

    q : [B, H, W] (``[q_abs | q_rope | 0...]``); lat_pool : [layers, P,
    page, W], a row ``[ckv | k_rope | 0...]``, read at ``layer`` (traced:
    every layer of a program is the same call); table : [B, pages]; qpos :
    [B] int32 (negative: a dead slot, zeros). Returns ``o_lat`` [B, H,
    rank] in q's type and, with ``return_visits``, int32 [B, 2]: the pages
    each sequence fetched and the copies that brought them."""
    b, h, width = q.shape
    pages, ps = lat_pool.shape[1:3]
    bp, g = plan
    row = table.shape[1]
    # never walk past the row, never name a page outside the pool: a wild
    # DMA halts the chip (the XLA arm's gather clamps the same way)
    npages = jnp.where(qpos >= 0, jnp.clip(pages_needed(qpos, ps), 1, row),
                       0).astype(jnp.int32)
    pos = jnp.minimum(qpos, npages * ps - 1).astype(jnp.int32)
    table = jnp.clip(jnp.pad(table.astype(jnp.int32),
                             ((0, 0), (0, -row % g))), 0, pages - 1)
    live = jnp.where(npages > 0, jnp.arange(b, dtype=jnp.int32), b)
    nxt = jnp.concatenate([jax.lax.cummin(live, reverse=True),
                           jnp.full((1,), b, jnp.int32)])
    out_specs = [pl.BlockSpec((1, h, rank), lambda i, *_: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, h, rank), q.dtype)]
    if return_visits:
        out_specs.append(pl.BlockSpec((1, 1, _LANES),
                                      lambda i, *_: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, 1, _LANES), jnp.int32))
    itemsize = lat_pool.dtype.itemsize
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            # in sequence: a cell starts the next cell's first copies
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(max(32 << 20, 2 * _turn_bytes(
                bp * ps, h, width, rank, itemsize))))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, width), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],   # the pool: in HBM
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((DEPTH + 1, bp, ps, width), lat_pool.dtype),
            pltpu.SemaphoreType.DMA((DEPTH + 1,)),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
        ])
    with x64_off_scope():
        outs = pl.pallas_call(
            functools.partial(_kernel, plan=plan, rank=rank,
                              scale=float(scale), has_visits=return_visits),
            grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
            **params,
        )(jnp.asarray(layer, jnp.int32).reshape(1), npages, nxt, pos, table,
          _runs(table, g), q.astype(lat_pool.dtype), lat_pool)
    if return_visits:
        return outs[0], outs[1][:, 0, :2]
    return outs[0]
