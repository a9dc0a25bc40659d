"""Latent (MLA) attention over the serving engine's caches, a learned
indexer that picks the keys a query attends, and the same latent attention
over a window ring.

**Latent attention** (DeepSeek-V2's multi-head latent attention). A token
keeps ONE row for all heads: its normed latent ``ckv`` (``rank`` values)
and its rotated ``k_rope`` beside it, ``[ckv | k_rope]``; a head's key and
value are ``[k_nope | v]_i = ckv W_ukv_i``. Two forms of the same
attention:

- **absorbed** (`latent_attention`): the query is carried into the latent,
  ``q_abs_i = q_nope_i W_uk_i^T``, scores are ``[q_abs_i | q_rope_i] . [ckv
  | k_rope]`` over the stored rows as they are, the output is a mix of
  ``ckv`` rows that the caller takes through ``W_uv_i``. No key or value is
  ever expanded: right for decode and for any query whose keys are PICKED,
  since each query then reads its own rows. The rows were found THROUGH
  the page table (`index_select`) and are gathered from the pool
  (``[layers, P, page, width]``, flattened to rows: free), one gather a
  call, the pool never sliced by layer (XLA copies a layer out of a stack
  to slice it).
- **absorbed, paged** (`latent_decode_paged`): the same absorbed form for
  a model with NO indexer, whose decode query attends everything in its
  sight: the context is walked a block of keys at a time through the page
  table, pages read whole (a row gather costs 21 ns a row on a v5e: 8 ms a
  step for 128 sequences of 3,000), the softmax carried across blocks.
  One algorithm, two arms (op ``mla_decode_paged``, ``xla`` and
  ``pallas``), picked as `latent_prefill`'s are, by what the code can see
  (`_decode_plan`: the backend, the mesh, the shapes):

  - the Pallas arm (`kernels/pallas/latent_decode.py`; a TPU, no
    multi-device mesh, the row width and ``rank`` whole lane tiles, a page
    whole sublane tiles of the pool's type): a grid cell a sequence, its
    pages copied from the pool WHERE THEY LIE into a ring of VMEM buffers
    (a run of consecutive page ids by one copy), each sequence to its own
    length, a block's scores in VMEM from the product that makes them to
    the mix, the block read once as keys and as values;
  - the XLA arm (anything else): a `fori_loop` with a DYNAMIC trip count,
    by the longest live sequence, whose every turn gathers all slots'
    pages of the block into a copy and sends the ``[slots, heads, keys]``
    float32 scores through HBM three times.
- **per head** (`latent_prefill`): the context is walked a block of keys
  at a time (a DYNAMIC trip count: what the sequence has), each block's
  keys and values expanded once for all heads, and a chunk of queries
  attends them under a mask with the softmax carried across blocks: 3.4
  times fewer operations a (query, key) pair than the absorbed form. The
  mask is the causal one while a chunk attends everything, and the
  selection's from then on (`index_threshold`, `chosen`): a chunk's 512
  queries between them pick nearly every key of a context up to 33,000, so
  every row is read either way, and reading pages whole beats gathering
  each query's own 2,048 rows (21 ns a row on a v5e) up to some 26,000 keys
  in sight. One algorithm, two arms (op ``mla_attention``, ``xla`` and
  ``pallas``); which one a program is built with follows from what the
  code can see (`_prefill_plan`: the backend, the mesh, the shapes), no
  flag and nothing timed:

  - the Pallas arm (`kernels/pallas/latent_prefill.py`; a TPU, no
    multi-device mesh, ``rank``, ``dn``, ``dv`` and the chunk length
    multiples of 128, the head count a multiple of the kernel's group): a
    tile of scores lives in VMEM from the product that makes it to the
    product with the values. The sequence's whole pages are gathered once
    into ``[keys, width]`` and the selection's mask is made ONCE, before
    the kernel, as int8 ``[queries, keys]`` (`chosen_mask`: `chosen`
    walked over the whole context with its count carried, so a tie on the
    cut still goes to the lower position across any block edge);
  - the XLA arm (anything else: the CPU, a mesh, odd shapes): the walk
    below, whose ``[heads, queries, keys]`` float32 scores go through HBM
    once for the row maximum, once for ``exp`` and the row sum and once
    for the product with the values.

  Both count the same attended pairs and round at the same points.

**The indexer** (DeepSeek-V3.2's sparse attention): every token also keeps
an index key ``kI`` (pool ``[layers, P, page, index width]``); a query
scores every key in its sight, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])``, and attends the ``topk`` best. `index_select` walks the context
in blocks of keys with a DYNAMIC trip count (what a sequence has, not what
it may grow to): a block's index keys are gathered through the page table,
scored (the ``[queries, heads, keys]`` product is reduced over heads one
sub-block at a time and never held whole), and merged into the running
best ``topk`` by one stable sort of ``[kept | block]``: kept keys come
first and a block's keys in order, so a tie goes to the lower position.
What comes back are rows of the pool (the page table is read a block at a
time, never a key at a time); a query with fewer keys in sight
than ``topk`` gets them all, the rest flagged off. `index_threshold` is the
same choice for a chunk that will attend under a mask: it keeps the scores,
finds each query's cut (its ``topk``-th best score) exactly, by bisection
over the bits of the scores' order, and how many keys on the cut are in.

**Window rings** (`window_latent_decode` / `window_latent_prefill`): a
sliding layer keeps the last ``window`` latent rows of a sequence in a ring
per slot, ``[slots, R, rank + rope]`` (position ``p`` at index ``p % R``,
as kernels/diff_attention.py's rings), one array a layer. Decode is the
absorbed form over the slot's ring; a chunk is the per-head form over the
ring as it was before the chunk beside the chunk's own rows, which then
overwrite the ring's oldest.

All but the chunk's per-head walk and the paged absorbed decode is plain
XLA (ops ``mla_attention`` and ``mla_decode_paged`` with the arms ``xla``
and ``pallas``; ``mla_index``, ``mla_window``, each with the single arm
``xla``): products in the served type with float32 accumulation, scores and
softmax in float32. Further Pallas arms (the indexer's scores reduced over
heads in VMEM; a gather that DMAs rows straight into the product; a paged
walk that reads a context several sequences share ONCE) come with the chip
reading that shows them winning. Which arm a program was built with: the
trace-time counters ``kernel.dispatch.mla_attention.{xla,pallas}`` and
``kernel.dispatch.mla_decode_paged.{xla,pallas}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry
from paddle_tpu.kernels.diff_attention import ring_positions
from paddle_tpu.kernels.paged_attention import TRASH_PAGE

__all__ = ["index_scores", "index_select", "index_threshold",
           "chosen", "chosen_mask", "latent_attention",
           "latent_decode_paged", "latent_prefill",
           "window_latent_decode", "window_latent_prefill"]

registry.register_op("mla_attention", impls=("xla", "pallas"))
registry.register_op("mla_decode_paged", impls=("xla", "pallas"))
registry.register_op("mla_index", impls=("xla",))
registry.register_op("mla_window", impls=("xla",))

_NEG = -1e30
SELECT_BLOCK = 6144     # keys a chunk's 512 queries score, and count, at a
#                         time
DECODE_SELECT_BLOCK = 16384   # keys merged into the kept ``topk`` at a time
#                         for one query a slot: a sort of 24 rows takes 0.4
#                         ms on a v5e at any width up to 18k
SCORE_BLOCK = 1024      # keys whose per-head scores are held at a time
HEAD_BLOCK = 16         # heads whose dense scores are held at a time
KEY_BLOCK = 2048        # keys a chunk's walk expands and attends at a time
DECODE_KEY_BLOCK = 512  # keys of every slot a paged decode step reads at a
#                         time: [slots, 512, width] beside the scores


# ------------------------------------------------------------- the indexer

def index_scores(qi, w, ki):
    """``I[b, t, s] = sum_j w[b, t, j] relu(qi[b, t, j] . ki[b, s])``.

    qi : [B, T, HI, DI]; w : [B, T, HI] float32; ki : [B, S, DI]. Returns
    [B, T, S] float32. The per-head scores ``[B, T, HI, S]`` exist for one
    call: callers pass a block of keys."""
    sc = jnp.einsum("bthd,bsd->bths", qi, ki,
                    preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(sc, 0.0) * w[..., None], axis=2)


def _block_scores(qi, w, ki, score_block):
    """`index_scores` over ``ki`` [B, S, DI] in sub-blocks of
    ``score_block`` keys."""
    b, s, di = ki.shape
    if s <= score_block or s % score_block:
        return index_scores(qi, w, ki)
    sub = ki.reshape(b, s // score_block, score_block, di).swapaxes(0, 1)
    out = jax.lax.map(lambda k: index_scores(qi, w, k), sub)   # [n, B, T, sb]
    return jnp.moveaxis(out, 0, 2).reshape(b, qi.shape[1], s)


def _walk(table, qpos, block, page_size):
    """How a walk over a context's keys is cut: (block rounded to pages,
    pages a block, the table padded to whole blocks with the trash page,
    blocks up to the furthest query: a traced count)."""
    block = max(page_size, min(block, table.shape[-1] * page_size))
    block -= block % page_size
    pages = block // page_size
    pad = -table.shape[-1] % pages
    table = jnp.pad(table, [(0, 0)] * (table.ndim - 1) + [(0, pad)],
                    constant_values=TRASH_PAGE)
    return block, pages, table, (jnp.max(qpos) + block) // block


def index_select(qi, w, ki_pool, layer, table, qpos, topk, *,
                 block=SELECT_BLOCK, score_block=SCORE_BLOCK, packed=None):
    """The ``topk`` keys of largest index score for each query, exactly, as
    ROWS of the pool.

    qi : [B, T, HI, DI] index queries; w : [B, T, HI] float32 head weights;
    ki_pool : [layers, P, page, DI], the index keys' pool, ``layer`` the
    one read; table : [B, pages] page table of each sequence; qpos : [B, T]
    int32 each query's position (it sees keys ``0..qpos``; negative: a
    padding query, sees nothing). Returns ``(rows [B, T, topk] int32, ok
    [B, T, topk] bool)``: where each chosen key's row lies in a pool
    flattened to ``[layers, P * page, width]`` (``page * page_size +
    offset``: what `latent_attention` gathers), best first, and which of
    them are keys at all (a query with fewer than ``topk`` keys in sight).
    Ties go to the lower position. Costs by the furthest query's position:
    the walk over key blocks stops there.

    The merge is ONE `jax.lax.sort` of ``[kept | block]`` by score with
    where each key lies as its payload, and the page table is read a block
    at a time, never a key at a time (`jax.lax.top_k` lowers to the same
    sort over an iota and leaves a gather of 2,048 single elements a query
    to find what the iota meant, and another to find their pages: 3 ms a
    decode step on a v5e). While positions and pages fit 16 bits each
    (``packed``: a table of at most 65,536 tokens, a pool of at most 65,536
    pages) a key's position and page ride ONE uint32 that is the sort's
    second key, so that a tie goes to the lower position with no third
    operand; past that the sort is a stable one with the row as payload,
    which the chip runs ten times slower (4.2 ms against 0.4 for 24 rows of
    18,432)."""
    registry.count("mla_index", "xla")
    b, t = qpos.shape
    ps = ki_pool.shape[2]
    block, pages_blk, table_p, n_blocks = _walk(table, qpos, block, ps)
    if packed is None:
        packed = table_p.shape[1] * ps <= 1 << 16 \
            and ki_pool.shape[1] <= 1 << 16

    def body(i, carry):
        worst, where = carry                 # -score, ascending: best first
        pg = jax.lax.dynamic_slice_in_dim(table_p, i * pages_blk, pages_blk,
                                          axis=1)
        ki = ki_pool[layer, pg].reshape(b, block, ki_pool.shape[3])
        s = i * block + jnp.arange(block, dtype=jnp.int32)
        sc = _block_scores(qi, w, ki, score_block)
        neg = jnp.where(s[None, None, :] <= qpos[..., None], -sc, jnp.inf)
        if packed:
            at = (s.astype(jnp.uint32) << 16)[None] \
                | jnp.repeat(pg, ps, axis=1).astype(jnp.uint32)
        else:
            at = (pg[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)) \
                .reshape(b, block)
        worst, where = jax.lax.sort(
            (jnp.concatenate([worst, neg], axis=-1),
             jnp.concatenate([where, jnp.broadcast_to(at[:, None],
                                                      (b, t, block))],
                             axis=-1)),
            dimension=2, is_stable=not packed, num_keys=2 if packed else 1)
        return worst[..., :topk], where[..., :topk]

    worst, where = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((b, t, topk), jnp.inf, jnp.float32),
         jnp.zeros((b, t, topk), jnp.uint32 if packed else jnp.int32)))
    if packed:
        where = ((where & 0xFFFF) * ps + (where >> 16) % ps) \
            .astype(jnp.int32)
    return where, worst < jnp.inf


def _order_key(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _from_order_key(k):
    u = jnp.where(k >> 31 == 1, k ^ jnp.uint32(0x80000000), ~k)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def index_threshold(qi, w, ki_pool, layer, row, qpos, topk, *,
                    block=SELECT_BLOCK, score_block=SCORE_BLOCK):
    """The selection of ``topk`` keys a query, as what a MASK needs: for a
    chunk of ONE sequence, every index score and each query's cut.

    qi : [T, HI, DI]; w : [T, HI] float32; ki_pool : [layers, P, page, DI];
    row : [pages] the sequence's page row; qpos : [T] (negative: padding).
    Returns ``(scores [T, S], cut [T], room [T])``, ``S`` the table's
    length rounded up to whole blocks: a key ``s <= qpos`` is chosen if its
    score lies above ``cut``, or on it and fewer than ``room`` keys on it
    came before (`chosen`): exactly the ``topk`` best with ties to the
    lower position, all of them while there are no more than ``topk``
    (``cut`` is then -inf). Costs by the furthest query's position; scores
    past it are not made (and never read: they are out of every query's
    sight).

    The cut is the ``topk``-th largest score in sight, found EXACTLY by
    bisection over the 32 bits of the scores' order (a count of the scores
    at or above a trial value, one bit at a time): 33 light passes over
    the scores in place of a sort of every block (2.7 ms a block of 6,144
    keys and 512 queries on a v5e: 16 of a chunk's 118 ms at 12,000 keys
    in sight)."""
    registry.count("mla_index", "xla")
    t = qpos.shape[0]
    ps = ki_pool.shape[2]
    block, pages_blk, row_p, n_blocks = _walk(row, qpos, block, ps)

    def score(i, scores):
        pg = jax.lax.dynamic_slice_in_dim(row_p, i * pages_blk, pages_blk)
        ki = ki_pool[layer, pg].reshape(1, block, ki_pool.shape[3])
        sc = _block_scores(qi[None], w[None], ki, score_block)[0]
        return jax.lax.dynamic_update_slice_in_dim(scores, sc, i * block,
                                                   axis=1)

    scores = jax.lax.fori_loop(
        0, n_blocks, score, jnp.zeros((t, row_p.shape[0] * ps), jnp.float32))

    def at_or_above(trial):
        """Keys in sight whose order key is >= ``trial`` [T], a query."""
        def count(i, n):
            blk = jax.lax.dynamic_slice_in_dim(scores, i * block, block,
                                               axis=1)
            s = i * block + jnp.arange(block, dtype=jnp.int32)
            hit = (_order_key(blk) >= trial[:, None]) \
                & (s[None, :] <= qpos[:, None])
            return n + jnp.sum(hit, axis=-1, dtype=jnp.int32)
        return jax.lax.fori_loop(0, n_blocks, count, jnp.zeros(t, jnp.int32))

    def bit(j, key):
        trial = key | (jnp.uint32(1) << (31 - j).astype(jnp.uint32))
        return jnp.where(at_or_above(trial) >= topk, trial, key)

    key = jax.lax.fori_loop(0, 32, bit, jnp.zeros(t, jnp.uint32))
    few = qpos + 1 <= topk                       # everything in sight is in
    cut = jnp.where(few, -jnp.inf, _from_order_key(key))
    room = topk - at_or_above(key + jnp.uint32(1))
    return scores, cut, jnp.where(few, 0, room)


def chosen(scores, cut, room, seen, sight):
    """Which keys of a block of ``scores`` [T, K] each query attends
    (`index_threshold`): above its ``cut``, or on it while fewer than
    ``room`` on it came before; ``seen`` [T] counts those in earlier
    blocks, ``sight`` [T, K] says which keys the query may see at all.
    Returns (keep [T, K] bool, seen after the block)."""
    level = (scores == cut[:, None]) & sight
    before = seen[:, None] + jnp.cumsum(level, axis=-1, dtype=jnp.int32)
    keep = sight & ((scores > cut[:, None])
                    | (level & (before <= room[:, None])))
    return keep, seen + jnp.sum(level, axis=-1, dtype=jnp.int32)


def chosen_mask(select, qpos, width, *, block=KEY_BLOCK):
    """`chosen` over the WHOLE context in one pass, as what a kernel takes:
    ``select`` is `index_threshold`'s ``(scores, cut, room)``, qpos : [T]
    (negative: padding), ``width`` the keys the mask spans, a whole number
    of ``block``. Returns (keep int8 [T, width], 1 where the query attends
    the key, and how many pairs that is: an int32 scalar). The walk carries
    `chosen`'s count of keys on the cut from block to block, so the mask is
    the one a block-by-block walk applies whatever its block; it stops at
    the furthest query (a DYNAMIC trip count), and past it the mask is 0:
    out of every query's sight."""
    scores, cut, room = select
    t = qpos.shape[0]
    if scores.shape[1] < width:
        scores = jnp.pad(scores, ((0, 0), (0, width - scores.shape[1])),
                         constant_values=-jnp.inf)

    def body(i, carry):
        keep, seen, n = carry
        blk = jax.lax.dynamic_slice_in_dim(scores, i * block, block, axis=1)
        s = i * block + jnp.arange(block, dtype=jnp.int32)
        k, seen = chosen(blk, cut, room, seen, s[None, :] <= qpos[:, None])
        keep = jax.lax.dynamic_update_slice_in_dim(
            keep, k.astype(jnp.int8), i * block, axis=1)
        return keep, seen, n + jnp.sum(k, dtype=jnp.int32)

    keep, _, n = jax.lax.fori_loop(
        0, jnp.minimum((jnp.max(qpos) + block) // block, width // block),
        body, (jnp.zeros((t, width), jnp.int8), jnp.zeros(t, jnp.int32),
               jnp.int32(0)))
    return keep, n


# ------------------------------------------------- latent attention, paged

def latent_attention(q, lat_pool, layer, rows, ok, *, rank, scale):
    """The absorbed form over PICKED rows of the latent pool.

    q : [B, T, H, W] (``[q_abs | q_rope | 0...]``); lat_pool : [layers,
    P, page, W], a row ``[ckv | k_rope | 0...]`` (W a whole number of lane
    tiles: the caller pads); rows, ok : [B, T, K] each query's chosen rows
    of the pool flattened to ``[layers, P * page, W]`` (`index_select`
    found them through the page table) and which of them count. Returns
    ``o_lat`` [B, T, H, rank] in q's type: the mix of ``ckv`` rows, before
    ``W_uv``. A query's K rows are its own, so all ``B x T x K`` are
    gathered at once: the caller is the decode step, with T = 1."""
    registry.count("mla_attention", "xla")
    flat = lat_pool.reshape(lat_pool.shape[0], -1, lat_pool.shape[3])
    kv = flat[layer, rows]                               # [B, T, K, W]
    sc = jnp.einsum("bthw,btkw->bthk", q, kv,
                    preferred_element_type=jnp.float32) * scale
    pr = jax.nn.softmax(jnp.where(ok[:, :, None, :], sc, _NEG), axis=-1)
    return jnp.einsum("bthk,btkc->bthc", pr.astype(kv.dtype), kv[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_decode_paged(q, lat_pool, layer, table, qpos, *, rank, scale,
                        key_block=DECODE_KEY_BLOCK):
    """The absorbed form over EVERYTHING in a query's sight, pages read
    whole through the page table: the decode step of a latent layer with no
    indexer.

    q : [B, H, W] (``[q_abs | q_rope | 0...]``); lat_pool : [layers, P,
    page, W], a row ``[ckv | k_rope | 0...]``; table : [B, pages]; qpos :
    [B] int32 each query's position (it sees keys ``0..qpos``; negative: a
    dead slot, sees nothing and gets zeros). Returns ``o_lat`` [B, H, rank]
    in q's type: the mix of ``ckv`` rows, before ``W_uv``.

    Two arms of one walk (op ``mla_decode_paged``). Where `_decode_plan`
    finds the Pallas kernel fits what it can see (a TPU, no multi-device
    mesh, the row width and ``rank`` whole lane tiles, a page whole sublane
    tiles of the pool's type) the pages are read where they lie, a block of
    them a turn, each sequence to ITS OWN length, and a turn's scores stay
    in VMEM (`kernels/pallas/latent_decode.py`; its block comes from the
    shapes, not from ``key_block``). Anywhere else the loop below runs: the
    context walked ``key_block`` keys at a time with a DYNAMIC trip count
    (to the furthest query), every slot's block of pages gathered at once
    ([B, block, W]: whole pages, 20 KB each at a page of 16), scored
    against the slot's heads, and mixed into the carried output with the
    softmax's running maximum and sum a head. Every product, accumulation
    and rounding is at the same point in both."""
    plan = _decode_plan(q, lat_pool, table, rank)
    if plan is not None:
        from paddle_tpu.kernels.pallas import _compat, latent_decode as kernel
        registry.count("mla_decode_paged", "pallas")
        registry.count_paged_block(plan.block, op="mla_decode_paged")
        return kernel.latent_decode_paged(
            q, lat_pool, layer, table, qpos, plan=plan, rank=rank,
            scale=float(scale), interpret=_compat.default_interpret())
    registry.count("mla_decode_paged", "xla")
    b, h, _ = q.shape
    ps = lat_pool.shape[2]
    kb, pages_blk, table_p, n_blocks = _walk(table, qpos, key_block, ps)

    def body(i, carry):
        m, l, acc = carry
        pg = jax.lax.dynamic_slice_in_dim(table_p, i * pages_blk, pages_blk,
                                          axis=1)
        lat = lat_pool[layer, pg].reshape(b, kb, -1)
        s = i * kb + jnp.arange(kb, dtype=jnp.int32)
        keep = (s[None, :] <= qpos[:, None])[:, None]          # [B, 1, K]
        sc = jnp.einsum("bhw,bsw->bhs", q, lat,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(keep, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        pr = jnp.where(keep, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(pr, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhs,bsc->bhc", pr.astype(lat.dtype), lat[..., :rank],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((b, h), _NEG, jnp.float32), jnp.zeros((b, h), jnp.float32),
         jnp.zeros((b, h, rank), jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def latent_prefill(q_nope, q_rope, lat_pool, layer, row, qpos, w_ukv, *, rank,
                   rope, dv, scale, select=None, key_block=KEY_BLOCK,
                   head_block=HEAD_BLOCK):
    """The per-head form for a chunk of ONE sequence, over everything in
    its sight or over what the selection chose, as a MASK.

    q_nope : [T, H, dn]; q_rope : [T, H, rope]; lat_pool : rows ``[ckv |
    k_rope | 0...]``; row : [pages] the sequence's page row; qpos : [T]
    positions (negative: padding); w_ukv : [rank, H * (dn + dv)], a head's
    columns ``[k_nope | v]``; select : None (every key ``s <= qpos``) or
    `index_threshold`'s ``(scores, cut, room)``. Returns (out [T, H, dv]
    float32, keys attended: int32 scalar).

    Walks the context a block of keys at a time with a DYNAMIC trip count
    (to the furthest query): the block's latent rows are read through the
    page table as whole pages, a group of heads' keys and values expanded
    from them where they are used, and the softmax carried across blocks
    (running maximum and sum a head and query). A picked key costs what an
    unpicked one costs here; the gather of picked rows (`latent_attention`)
    costs 21 ns a row on a v5e whatever is done with it, which is more than
    this walk up to some 26,000 keys in sight (PERF.md section 6, PR
    40).

    Two arms of that walk. Where `_prefill_plan` finds the Pallas kernel
    fits what it can see (a TPU, no multi-device mesh, ``rank``, ``dn``,
    ``dv`` and ``T`` multiples of 128, the heads a multiple of the
    kernel's group) the scores stay in VMEM
    (`kernels/pallas/latent_prefill.py`): the sequence's pages are gathered
    whole into ``[keys, width]`` once, the selection's mask is made once
    for the whole context (`chosen_mask`) and the kernel is handed both.
    Anywhere else the loop below runs, ``key_block`` keys and
    ``head_block`` heads at a time (the Pallas arm takes its own tiles
    from the shapes). The same pairs are attended and counted, and every
    product and rounding is the same."""
    t, h, dn = q_nope.shape
    plan = _prefill_plan(t, h, dn, rope, dv, rank, lat_pool)
    if plan is not None:
        registry.count("mla_attention", "pallas")
        return _pallas_prefill(q_nope, q_rope, lat_pool, layer, row, qpos,
                               w_ukv, plan, rank=rank, rope=rope, dv=dv,
                               scale=scale, select=select)
    registry.count("mla_attention", "xla")
    kb, pages_blk, row_p, n_blocks = _walk(row, qpos, key_block,
                                           lat_pool.shape[2])
    g = head_block if h % head_block == 0 else h
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = q.reshape(t, h // g, g, dn + rope).transpose(1, 2, 0, 3)
    w_g = w_ukv.reshape(rank, h // g, g, dn + dv).swapaxes(0, 1)
    if select is not None:
        scores, cut, room = select
        if scores.shape[1] % kb:
            scores = jnp.pad(scores, ((0, 0), (0, -scores.shape[1] % kb)),
                             constant_values=-jnp.inf)

    def body(i, carry):
        m, l, acc, seen, n = carry
        pg = jax.lax.dynamic_slice_in_dim(row_p, i * pages_blk, pages_blk)
        lat = lat_pool[layer, pg].reshape(kb, -1)
        ckv = lat[:, :rank]
        kr = jnp.broadcast_to(lat[None, :, rank:rank + rope], (g, kb, rope))
        s = i * kb + jnp.arange(kb, dtype=jnp.int32)
        keep = s[None, :] <= qpos[:, None]
        if select is not None:
            blk = jax.lax.dynamic_slice_in_dim(scores, i * kb, kb, axis=1)
            keep, seen = chosen(blk, cut, room, seen, keep)

        def heads(args):
            qg, wg, mg, lg, ag = args        # [g, T, .], [rank, g, dn + dv]
            kv = jnp.einsum("sc,cgd->gsd", ckv, wg)       # [g, K, dn + dv]
            sc = jnp.einsum(
                "gtd,gsd->gts", qg, jnp.concatenate([kv[..., :dn], kr], -1),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(keep[None], sc, _NEG)
            m_new = jnp.maximum(mg, jnp.max(sc, axis=-1))
            pr = jnp.where(keep[None], jnp.exp(sc - m_new[..., None]), 0.0)
            alpha = jnp.exp(mg - m_new)
            lg = lg * alpha + jnp.sum(pr, axis=-1)
            ag = ag * alpha[..., None] + jnp.einsum(
                "gts,gsv->gtv", pr.astype(kv.dtype), kv[..., dn:],
                preferred_element_type=jnp.float32)
            return m_new, lg, ag

        m, l, acc = jax.lax.map(heads, (q, w_g, m, l, acc))
        return m, l, acc, seen, n + jnp.sum(keep, dtype=jnp.int32)

    m, l, acc, _, n = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((h // g, g, t), _NEG, jnp.float32),
         jnp.zeros((h // g, g, t), jnp.float32),
         jnp.zeros((h // g, g, t, dv), jnp.float32),
         jnp.zeros(t, jnp.int32), jnp.int32(0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]           # [h/g, g, T, dv]
    return out.reshape(h, t, dv).swapaxes(0, 1), n


def _decode_plan(q, lat_pool, table, rank):
    """The Pallas arm's block where it fits `latent_decode_paged`'s call,
    else None: the XLA arm runs."""
    if not registry.on_one_tpu():
        return None
    from paddle_tpu.kernels.pallas import latent_decode as kernel
    return kernel.plan(q.shape[1], lat_pool.shape[3], rank, lat_pool.shape[2],
                       lat_pool.dtype.itemsize, *table.shape)


def _prefill_plan(t, h, dn, rope, dv, rank, lat_pool):
    """The Pallas arm's tiles where it fits `latent_prefill`'s call, else
    None: the XLA arm runs."""
    if not registry.on_one_tpu():
        return None
    from paddle_tpu.kernels.pallas import latent_prefill as kernel
    return kernel.plan(t, h, dn, rope, dv, rank, lat_pool.shape[3],
                       lat_pool.shape[2])


def _pallas_prefill(q_nope, q_rope, lat_pool, layer, row, qpos, w_ukv, plan,
                    *, rank, rope, dv, scale, select):
    """`latent_prefill` through the kernel: the sequence's pages gathered
    whole (the page row padded to whole key blocks with the trash page),
    the mask and the attended pairs found before the call."""
    from paddle_tpu.kernels.pallas import _compat, latent_prefill as kernel
    ps = lat_pool.shape[2]
    # the mask's pass takes whole blocks of its own: both divide the keys
    block = max(plan.block, KEY_BLOCK - KEY_BLOCK % plan.block)
    row_p = jnp.pad(row, (0, -row.shape[0] % (block // ps)),
                    constant_values=TRASH_PAGE)
    lat = lat_pool[layer, row_p].reshape(-1, lat_pool.shape[3])
    if select is None:
        keep, n = None, jnp.sum(jnp.maximum(qpos + 1, 0), dtype=jnp.int32)
    else:
        keep, n = chosen_mask(select, qpos, lat.shape[0], block=block)
    out = kernel.latent_prefill(
        q_nope, q_rope, w_ukv, lat, qpos, keep, plan=plan, rank=rank,
        rope=rope, dv=dv, scale=float(scale),
        interpret=_compat.default_interpret())
    return out, n


# ------------------------------------------- latent attention, window ring

def window_latent_decode(q, row, ring, pos, active, *, window, rank, scale):
    """One token per slot of a window layer, the absorbed form. q : [B, H,
    rank + rope]; row : [B, rank + rope] the new token's ``[ckv | k_rope]``;
    ring : [B, R, rank + rope] this layer's rings; pos : [B]; an inactive
    slot writes nothing. Returns (o_lat [B, H, rank], ring)."""
    registry.count("mla_window", "xla")
    r = ring.shape[1]
    idx = jnp.where(active, pos % r, r)                   # r: dropped
    ring = ring.at[jnp.arange(q.shape[0]), idx].set(row.astype(ring.dtype),
                                                    mode="drop")
    held = ring_positions(pos, r)                         # [B, R]
    see = (held >= 0) & (held > pos[:, None] - window)
    sc = jnp.einsum("bhw,bsw->bhs", q, ring,
                    preferred_element_type=jnp.float32) * scale
    pr = jax.nn.softmax(jnp.where(see[:, None], sc, _NEG), axis=-1)
    o = jnp.einsum("bhs,bsc->bhc", pr.astype(ring.dtype), ring[..., :rank],
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), ring


def window_latent_prefill(q_nope, q_rope, rows, ring, slot, start, valid,
                          w_ukv, *, window, rank, dv, scale):
    """A chunk of ONE slot through a window layer, the per-head form. q_nope
    : [T, H, dn]; q_rope : [T, H, rope]; rows : [T, rank + rope] the
    chunk's ``[ckv | k_rope]``; ring : [slots, R, rank + rope]; start : the
    chunk's first position (0: the ring's old contents are another
    sequence's); valid : true token count. Returns (out [T, H, dv],
    ring)."""
    registry.count("mla_window", "xla")
    t, h, dn = q_nope.shape
    r = ring.shape[1]
    i = jnp.arange(t)
    qpos = start + i
    held = ring_positions(start - 1, r)                   # [R]
    see_old = (held >= 0)[None, :] & (held[None, :] > qpos[:, None] - window)
    see_new = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
    see = jnp.concatenate([see_old, see_new], axis=1)     # [T, R + T]
    lat = jnp.concatenate([ring[slot], rows.astype(ring.dtype)])
    kv = (lat[:, :rank] @ w_ukv).reshape(r + t, h, dn + dv)
    sc = (jnp.einsum("thd,shd->hts", q_nope, kv[..., :dn],
                     preferred_element_type=jnp.float32)
          + jnp.einsum("thr,sr->hts", q_rope, lat[:, rank:],
                       preferred_element_type=jnp.float32)) * scale
    pr = jax.nn.softmax(jnp.where(see[None], sc, _NEG), axis=-1)
    out = jnp.einsum("hts,shv->thv", pr.astype(kv.dtype), kv[..., dn:],
                     preferred_element_type=jnp.float32)
    # only the chunk's last R valid tokens: an older one would land on the
    # ring index of a newer one
    idx = jnp.where((i < valid) & (i >= valid - r), qpos % r, r)
    ring = ring.at[slot, idx].set(rows.astype(ring.dtype), mode="drop")
    return out.astype(q_nope.dtype), ring
