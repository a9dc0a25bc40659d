"""Differential grouped-query attention over the serving engine's caches.

Differential attention (Ye et al. 2024, as Phi-4-mini-flash's published
code runs it): heads pair up, each pair takes two softmaxes over the same
value head of twice the head width, and their difference is normalised::

    o = (1 - l0) * RMSNorm(softmax(q1 k1^T / sqrt(hd)) V
                           - lam * softmax(q2 k2^T / sqrt(hd)) V)

Of a pair "1" is the even head. ``nq`` query heads make ``nq / 2`` query
pairs, ``nkv`` KV heads make ``nkv / 2`` key pairs and as many value heads
of ``2 * hd``; query pair ``j`` reads KV pair ``j // (nq / nkv)``. K and V
are stored with their heads merged into the lane axis (``nkv * hd``), as
the GPT pool is (kernels/paged_attention.py).

Two kinds of cache, for decode (every slot, one token) and for a prefill
chunk (one slot, T tokens):

- **window** (``window_decode`` / ``window_prefill``): K and V of a window
  layer live in a RING per slot, one array a layer, ``[slots, R, nkv *
  hd]`` with ``R = window + page`` tokens (33 pages of 16 for a window of
  512; a layer's rings are an array of their own because XLA slices a
  layer out of a stack by copying it: 14% of the first decode step,
  PERF.md, PR 27):
  position ``p`` sits at ring index ``p % R``, whatever the sequence's
  length, and a page is recycled each time the ring wraps onto it. A query
  sees itself and the ``window - 1`` positions before it. A chunk attends
  the ring as it was BEFORE the chunk (the positions the chunk will
  overwrite are the ones its first queries still need) beside the chunk's
  own K and V, then writes the chunk's last ``R`` valid tokens.
- **paged** (``paged_decode`` / ``paged_prefill``): the one full-attention
  layer writes its token(s) into the engine's page pool ``[1, P, page, nkv
  * hd]`` and attends positions 0..p through the page table. The
  cross-attention layers read the same K and V; they own no cache and
  write nothing. Both functions hand back ``read``, the same attention for
  a cross layer's queries over what was made ready ONCE for all of them:

  - a decode step's read is the op ``diff_attention_paged``, two arms
    picked by what the call can see (`_paged_arm`: a TPU, no multi-device
    mesh, a pool row of whole lane tiles, a page of whole sublane tiles;
    no flag). ``pallas``: the paged decode kernel walks each slot's LIVE
    pages where they lie in the pool and keeps a block's scores in VMEM
    (`kernels/pallas/paged_attention.py`, "Differential pairs"); nothing
    is gathered. ``xla`` (the CPU, a mesh, odd shapes): every slot's WHOLE
    page row (``pages_per_slot`` pages, live or not) is gathered once a
    step, and each layer reads all of it twice with float32 ``[slots, 4,
    positions]`` scores through HBM in between: 10.1 ms of a 24 ms step on
    the chip at 64 slots half full, against 2.4 ms of live K and V at the
    memory's rate (PERF.md, PR 49). Its ops run under the scope
    ``shared_kv_attn``, by which a device trace finds them;
  - a chunk's read is ``diff_attention`` on one slot's gathered row (5 MB).

The rest is the plainest correct XLA (``diff_attention``, registered with
the single impl ``xla``): scores and softmax in float32, operands in the
served type. Which arm a program was built with, and its block:
``kernel.dispatch.diff_attention_paged.{xla,pallas}``,
``kernel.paged_block.diff_attention_paged.{pages}`` (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry
from paddle_tpu.kernels.paged_attention import (chunk_page_coords,
                                                token_page_coords)

__all__ = ["diff_attention", "diff_attention_paged", "ring_positions",
           "window_decode", "window_prefill", "paged_decode",
           "paged_prefill"]

registry.register_op("diff_attention", impls=("xla",))
registry.register_op("diff_attention_paged", impls=("xla", "pallas"))

_NEG = -1e30


def diff_attention(q, k, v, mask, lam, l0, subln_w, *, nq, nkv, eps=1e-5):
    """q : [B, T, nq * hd]; k, v : [B, S, nkv * hd]; mask : [B, T, S] bool
    (True: the query sees the key); lam, l0 : scalars; subln_w : [2 * hd].
    Returns [B, T, nq * hd] in q's dtype (query pairs in order, each ``2 *
    hd`` wide)."""
    registry.count("diff_attention", "xla")
    b, t, qw = q.shape
    s = k.shape[1]
    hd = qw // nq
    g, j = nkv // 2, nq // nkv
    if t == 1:
        o = _decode_pairs(q[:, 0], k, v, mask[:, 0], g, j, hd)[:, None]
    else:
        q5 = q.reshape(b, t, g, j, 2, hd)
        k4 = k.reshape(b, s, g, 2, hd)
        v3 = v.reshape(b, s, g, 2 * hd)
        sc = jnp.einsum("btgjcd,bsgcd->bgjcts", q5, k4,
                        preferred_element_type=jnp.float32) \
            * (1.0 / hd ** 0.5)
        sc = jnp.where(mask[:, None, None, None], sc, _NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bgjcts,bsge->btgjce", pr.astype(v.dtype), v3,
                       preferred_element_type=jnp.float32)
    return _sub_norm(o, lam, l0, subln_w, eps).reshape(b, t, qw) \
        .astype(q.dtype)


def _sub_norm(o, lam, l0, subln_w, eps):
    """The two softmaxes' mixes of every pair, float32 [..., 2, 2 * hd],
    to the pair's output [..., 2 * hd]: their difference, normalised."""
    o = o[..., 0, :] - lam * o[..., 1, :]
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * subln_w.astype(jnp.float32)
    return (1.0 - l0) * o


def _decode_pairs(q, k, v, mask, g, j, hd):
    """The two softmaxes of every head pair for ONE query per sequence,
    reading K and V as they are stored. q : [B, nq * hd]; k, v : [B, S,
    nkv * hd]; mask : [B, S]. Returns [B, g, j, 2, 2 * hd] float32.

    A KV pair's lanes (``2 * hd`` = 128 at the published widths: one lane
    tile) are sliced out of the stored rows and contracted whole against a
    query row that is zero outside its own head's half, so no operand is
    reshaped below the lane tile and XLA relays nothing: the grouped
    einsum over ``[B, S, g, 2, hd]`` cost two transposed copies of K and V
    a layer and ran 2.2x slower over 2,048 tokens (PERF.md, PR 27)."""
    b = q.shape[0]
    w = 2 * hd
    q5 = q.reshape(b, g, j, 2, hd)
    half = jnp.eye(2, dtype=q.dtype)
    rows = (q5[:, :, :, :, None, :] * half[None, None, None, :, :, None]) \
        .reshape(b, g, j * 2, w)
    outs = []
    for i in range(g):
        kg, vg = k[:, :, i * w:(i + 1) * w], v[:, :, i * w:(i + 1) * w]
        sc = jnp.einsum("bqd,bsd->bqs", rows[:, i], kg,
                        preferred_element_type=jnp.float32) \
            * (1.0 / hd ** 0.5)
        pr = jax.nn.softmax(jnp.where(mask[:, None], sc, _NEG), axis=-1)
        outs.append(jnp.einsum("bqs,bse->bqe", pr.astype(v.dtype), vg,
                               preferred_element_type=jnp.float32))
    return jnp.stack(outs, axis=1).reshape(b, g, j, 2, w)


def ring_positions(last, ring):
    """The position held at each ring index once every position up to
    ``last`` is written: the largest ``p <= last`` with ``p % ring == r``
    (negative: never written). last : [...] int32 -> [..., ring]."""
    r = jnp.arange(ring)
    return last[..., None] - (last[..., None] - r) % ring


def window_decode(q, k, v, kwin, vwin, pos, active, lam, l0, subln_w, *,
                  window, nq, nkv):
    """One token per slot of a window layer. q : [B, nq * hd]; k, v :
    [B, nkv * hd] the new token's; kwin, vwin : [B, R, nkv * hd], this
    layer's rings; pos : [B] the token's position; an inactive slot writes
    nothing. Returns (out [B, nq * hd], kwin, vwin)."""
    ring = kwin.shape[1]
    idx = jnp.where(active, pos % ring, ring)             # ring: dropped
    rows = jnp.arange(q.shape[0])
    kwin = kwin.at[rows, idx].set(k.astype(kwin.dtype), mode="drop")
    vwin = vwin.at[rows, idx].set(v.astype(vwin.dtype), mode="drop")
    held = ring_positions(pos, ring)                      # [B, R]
    mask = (held >= 0) & (held > pos[:, None] - window)
    out = diff_attention(q[:, None], kwin, vwin, mask[:, None],
                         lam, l0, subln_w, nq=nq, nkv=nkv)
    return out[:, 0], kwin, vwin


def window_prefill(q, k, v, kwin, vwin, slot, start, valid, lam, l0,
                   subln_w, *, window, nq, nkv):
    """A chunk of ONE slot through a window layer. q : [T, nq * hd]; k, v :
    [T, nkv * hd]; start : position of the chunk's first token (0: the
    sequence starts here and the ring's old contents are another
    sequence's); valid : true token count. Returns (out [T, nq * hd], kwin,
    vwin)."""
    ring = kwin.shape[1]
    t = q.shape[0]
    i = jnp.arange(t)
    qpos = start + i
    held = ring_positions(start - 1, ring)                # [R]
    see_old = (held >= 0)[None, :] & (held[None, :] > qpos[:, None] - window)
    see_new = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
    keys = jnp.concatenate([kwin[slot], k.astype(kwin.dtype)])
    vals = jnp.concatenate([vwin[slot], v.astype(vwin.dtype)])
    out = diff_attention(q[None], keys[None], vals[None],
                         jnp.concatenate([see_old, see_new], axis=1)[None],
                         lam, l0, subln_w, nq=nq, nkv=nkv)
    # only the chunk's last R valid tokens: an older one would land on the
    # ring index of a newer one
    idx = jnp.where((i < valid) & (i >= valid - ring), qpos % ring, ring)
    kwin = kwin.at[slot, idx].set(k.astype(kwin.dtype), mode="drop")
    vwin = vwin.at[slot, idx].set(v.astype(vwin.dtype), mode="drop")
    return out[0], kwin, vwin


def _gather(pool, table):
    """[1, P, page, w] through table [B, maxp] -> [B, maxp * page, w]."""
    got = pool[0, table]
    return got.reshape(table.shape[0], -1, pool.shape[-1])


def _paged_arm(k_pages):
    """``"pallas"`` where the kernel fits `diff_attention_paged`'s pool, by
    what the call can see: a TPU and no multi-device mesh, a pool row of
    whole lane tiles and a page of whole sublane tiles of the pool's type
    (a block's pages are then its rows). Else ``"xla"``."""
    ps, w = k_pages.shape[2:]
    fits = w % 128 == 0 and ps % (32 // k_pages.dtype.itemsize) == 0
    return "pallas" if fits and registry.on_one_tpu() else "xla"


def diff_attention_paged(q, k_pages, v_pages, page_table, pos, lam, l0,
                         subln_w, *, nq, nkv, eps=1e-5, gathered=None):
    """One token per slot over the shared page pool, read-only: the decode
    step of every layer that reads it. q : [B, nq * hd]; k_pages, v_pages :
    [1, P, page, nkv * hd]; page_table : [B, pages]; pos : [B] int32, the
    token's position (it sees 0..pos; negative: a dead slot, which reads
    nothing and gets zeros). Returns [B, nq * hd] in q's type.

    Two arms (op ``diff_attention_paged``), picked by `_paged_arm`:

    - **pallas**: the paged decode kernel walks each slot's LIVE pages
      where they lie in the pool, a block of pages a turn, the scores in
      VMEM (`kernels/pallas/paged_attention.py`, "Differential pairs":
      `_decode_pairs`' head map, K head ``2p + c`` read by query heads
      ``4p + c`` and ``4p + 2 + c``, is that kernel's at ``value_heads=2``).
      It hands back every head's mix over its PAIR's ``2 * hd`` value
      lanes, float32 ``[B, g, j, 2, 2 * hd]`` as `_decode_pairs` does;
    - **xla**: K and V gathered whole through the page table (``gathered``:
      the caller's, where it gathered them already for another layer of
      the step), `_decode_pairs` over all ``pages * page`` positions under
      the mask ``s <= pos``.

    The difference of a pair's two softmaxes, the norm and ``1 - l0`` are
    the same XLA after either."""
    b, qw = q.shape
    hd = qw // nq
    g, j = nkv // 2, nq // nkv
    arm = _paged_arm(k_pages)
    registry.count("diff_attention_paged", arm)
    with jax.named_scope("shared_kv_attn"):
        if arm == "pallas":
            from paddle_tpu.kernels.pallas import paged_attention as kernel
            o = kernel.paged_attention(
                q.reshape(b, nq, hd), k_pages, v_pages, page_table, pos,
                layer=0, value_heads=2, op="diff_attention_paged") \
                .reshape(b, g, j, 2, 2 * hd)
        else:
            kg, vg = gathered or (_gather(k_pages, page_table),
                                  _gather(v_pages, page_table))
            mask = jnp.arange(kg.shape[1])[None, :] <= pos[:, None]
            o = _decode_pairs(q, kg, vg, mask, g, j, hd)
            o = jnp.where((pos >= 0)[:, None, None, None, None], o, 0.0)
        return _sub_norm(o, lam, l0, subln_w, eps).reshape(b, qw) \
            .astype(q.dtype)


def paged_decode(q, k, v, k_pages, v_pages, page_table, pos, active, lam,
                 l0, subln_w, *, nq, nkv):
    """One token per slot of the full-attention layer over the page pool
    (``[1, P, page, nkv * hd]``): writes the token, attends 0..pos; an
    inactive slot is a dead one (`diff_attention_paged`). Returns (out [B,
    nq * hd], k_pages, v_pages, ``read``): ``read(q, lam, l0, subln_w)`` is
    the same attention for a cross layer's queries, over what this layer
    made ready once for all of them (on the xla arm, the gathered K and
    V)."""
    page, off = token_page_coords(page_table, pos, active, k_pages.shape[2])
    k_pages = k_pages.at[0, page, off].set(k.astype(k_pages.dtype))
    v_pages = v_pages.at[0, page, off].set(v.astype(v_pages.dtype))
    qpos = jnp.where(active, pos, -1)
    gathered = None
    if _paged_arm(k_pages) == "xla":
        gathered = (_gather(k_pages, page_table),
                    _gather(v_pages, page_table))

    def read(q, lam, l0, subln_w, eps=1e-5):
        return diff_attention_paged(q, k_pages, v_pages, page_table, qpos,
                                    lam, l0, subln_w, nq=nq, nkv=nkv,
                                    eps=eps, gathered=gathered)
    return read(q, lam, l0, subln_w), k_pages, v_pages, read


def paged_prefill(q, k, v, k_pages, v_pages, row, start, valid, lam, l0,
                  subln_w, *, nq, nkv):
    """A chunk of ONE slot through the full-attention layer: writes the
    chunk's K and V into the slot's pages (padding to the trash page), then
    attends everything cached, masked by absolute position. Returns (out
    [T, nq * hd], k_pages, v_pages, ``read``): ``read(q, lam, l0,
    subln_w)`` attends a cross layer's chunk of queries to the slot's row
    as gathered here."""
    t = q.shape[0]
    page, off = chunk_page_coords(row, start, valid, t, k_pages.shape[2])
    k_pages = k_pages.at[0, page, off].set(k.astype(k_pages.dtype))
    v_pages = v_pages.at[0, page, off].set(v.astype(v_pages.dtype))
    kg, vg = _gather(k_pages, row[None]), _gather(v_pages, row[None])
    mask = (jnp.arange(kg.shape[1])[None, :]
            <= (start + jnp.arange(t))[:, None])[None]

    def read(q, lam, l0, subln_w, eps=1e-5):
        return diff_attention(q[None], kg, vg, mask, lam, l0, subln_w,
                              nq=nq, nkv=nkv, eps=eps)[0]
    return read(q, lam, l0, subln_w), k_pages, v_pages, read
