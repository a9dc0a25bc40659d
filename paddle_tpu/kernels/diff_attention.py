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

Three forms over two kinds of cache, for decode (every slot, one token) and
for a prefill chunk (one slot, T tokens):

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
- **paged, writing** (``paged_decode`` / ``paged_prefill``): the one
  full-attention layer writes its token(s) into the engine's page pool
  ``[1, P, page, nkv * hd]`` and attends positions 0..p through the page
  table. Both return the gathered K and V for
- **paged, read-only** (``diff_attention`` on that gather): the
  cross-attention layers read what the full layer gathered; they own no
  cache and write nothing.

All of it is the plainest correct XLA (registered with the single impl
``xla``): scores and softmax in float32, operands in the served type. The
gather reads every slot's whole page row (``pages_per_slot`` pages), live
or not; a Pallas arm that walks only live pages belongs here once a traced
run shows the family among a cell's largest (PERF.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry
from paddle_tpu.kernels.paged_attention import (chunk_page_coords,
                                                token_page_coords)

__all__ = ["diff_attention", "ring_positions", "window_decode",
           "window_prefill", "paged_decode", "paged_prefill"]

registry.register_op("diff_attention", impls=("xla",))

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
    o = o[..., 0, :] - lam * o[..., 1, :]                 # [B, T, g, j, 2hd]
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * subln_w.astype(jnp.float32)
    return ((1.0 - l0) * o).reshape(b, t, qw).astype(q.dtype)


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


def paged_decode(q, k, v, k_pages, v_pages, page_table, pos, active, lam,
                 l0, subln_w, *, nq, nkv):
    """One token per slot of the full-attention layer over the page pool
    (``[1, P, page, nkv * hd]``): writes the token, attends 0..pos. Returns
    (out [B, nq * hd], k_pages, v_pages, gathered K, gathered V, mask) —
    the last three are what the cross layers read."""
    page, off = token_page_coords(page_table, pos, active, k_pages.shape[2])
    k_pages = k_pages.at[0, page, off].set(k.astype(k_pages.dtype))
    v_pages = v_pages.at[0, page, off].set(v.astype(v_pages.dtype))
    kg, vg = _gather(k_pages, page_table), _gather(v_pages, page_table)
    mask = (jnp.arange(kg.shape[1])[None, :] <= pos[:, None])[:, None]
    out = diff_attention(q[:, None], kg, vg, mask, lam, l0, subln_w,
                         nq=nq, nkv=nkv)
    return out[:, 0], k_pages, v_pages, kg, vg, mask


def paged_prefill(q, k, v, k_pages, v_pages, row, start, valid, lam, l0,
                  subln_w, *, nq, nkv):
    """A chunk of ONE slot through the full-attention layer: writes the
    chunk's K and V into the slot's pages (padding to the trash page), then
    attends everything cached, masked by absolute position. Returns (out
    [T, nq * hd], k_pages, v_pages, gathered K, gathered V, mask)."""
    t = q.shape[0]
    page, off = chunk_page_coords(row, start, valid, t, k_pages.shape[2])
    k_pages = k_pages.at[0, page, off].set(k.astype(k_pages.dtype))
    v_pages = v_pages.at[0, page, off].set(v.astype(v_pages.dtype))
    kg, vg = _gather(k_pages, row[None]), _gather(v_pages, row[None])
    mask = (jnp.arange(kg.shape[1])[None, :]
            <= (start + jnp.arange(t))[:, None])[None]
    out = diff_attention(q[None], kg, vg, mask, lam, l0, subln_w,
                         nq=nq, nkv=nkv)
    return out[0], k_pages, v_pages, kg, vg, mask
