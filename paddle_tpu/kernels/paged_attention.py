"""Paged KV-cache attention — the serving-side cache layout (arxiv 2604.15464).

Dense decode caches ([B, L, nh, dh] per layer, one slab per sequence) waste
HBM on short sequences and force one compiled program per (B, L) shape. The
paged layout stores tokens in fixed-size PAGES:

    k_pages, v_pages : [num_layers, num_pages, page_size, num_heads * head_dim]

— stacked over layers, heads MERGED into the lane axis: the layout the
attention kernels read (768 / 1024 / 1280 lanes for the GPT-2 sizes; a
head_dim of 64 alone is half a TPU lane tile, and a pool stored
``[..., nh, dh]`` is relaid whole by every program that touches it).
Writers scatter lane-dense rows (``pool.at[layer, page, off].set(k.reshape(
..., nh * dh))``); readers take the stacked pool and a ``layer`` index and
never slice a layer out (on the chip ``pool[i]`` as a kernel operand is a
copy of the layer's pool). Each sequence owns an ordered list of page
indices (the host-side page table, padded to ``pages_per_slot``). Token
position ``t`` of a sequence lives at ``(page_table[t // page_size], t %
page_size)``. Pages are allocated/freed by the engine's host-side allocator
as sequences join and retire, so B live sequences of wildly different
lengths share one fixed-shape pool — the decode program never changes shape
and never recompiles.

`paged_attention` is a DISPATCH SWITCH over two implementations with one
contract (token-identical output, enforced by parity tests):

- **xla** — the JAX-native reference: gather each sequence's pages into a
  [B, Lmax] window, masked f32-softmax attention. Correct everywhere, but
  HBM traffic and FLOPs scale with the pool's capacity (`pages_per_slot`),
  not the live lengths. (One exception: a GROUPED prefill chunk, which has
  this arm alone, over a page row longer than `LONG_ROW` positions walks
  the row `WALK_BLOCK` keys a turn and stops at the chunk's last position:
  `_xla_prefill_walk`.)
- **pallas** — the authored ragged paged-attention kernel
  (`kernels/pallas/paged_attention.py`): grid over sequences, a BLOCK of a
  sequence's pages a loop turn (256 tokens at the GPT-2 widths, read off
  the pool's shape), the block's page copies started together and those of
  the next turns, the next sequences' too, in flight while one block is
  reduced on the MXU in a single online-softmax update; only
  ``ceil((pos+1)/page_size)`` pages are ever fetched, so page traffic
  scales with each sequence's true length, each DMA reading ``pool[layer,
  page]`` of the stored pool (the kernel's "Layout" note). Dot precision
  follows the pool's dtype: one bf16 pass on exact operands for a bf16
  pool, f32 ``HIGHEST`` for f32 and dequantised int8.

Both ops take the stored pool with ``layer=``. Without the keyword they
accept ONE layer's pool, ``[num_pages, page_size, nh, dh]`` or merged rank
3 — the form of callers that own no stack (the benchmark's selection
probe, tests). Rank cannot tell the two forms apart (both are rank 4), so
the keyword decides. A per-layer call pays a copy of that pool on the chip
and is counted at trace time in ``kernel.pool_relayout.{op}``; the engine's
programs count none (docs/OBSERVABILITY.md).

``FLAGS_tpu_paged_impl`` picks: ``auto`` (on a TPU the winner the kernel
registry measures per signature over this module's synthetic pool, xla
elsewhere — backend viability is decided by NAME, `_pool_cands`),
``xla``, or ``pallas`` (interpret mode off-TPU: parity tests only). Every
selection routes through `kernels/registry.py::dispatch` and is counted
per program build in ``kernel.dispatch.paged_attention.{xla|pallas}``
(plus the pre-registry alias ``paged_attention.impl.*``;
docs/OBSERVABILITY.md). The ragged PREFILL twin (`prefill_attention` /
`prefill_impl`) dispatches the same way under ``FLAGS_tpu_prefill_impl``
with counters ``kernel.dispatch.prefill_attention.*``.

Page 0 is RESERVED as the trash page: writes for inactive slots and
prompt-padding positions are routed there instead of being predicated out
(XLA scatters need valid indices; a dedicated spill target keeps the write
unconditional and the program shape static). Allocators must never hand out
page 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import registry

# the reserved spill target for masked writes — never allocated to a sequence
TRASH_PAGE = 0

# EngineConfig.kv_dtype knob values -> page storage dtypes. "int8" pairs the
# int8 pages with a per-token-slot per-head f32 scale array ([nl, P, ps, nh])
# written by the same scatters that write the pages (docs/QUANTIZATION.md).
KV_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}

__all__ = ["TRASH_PAGE", "KV_DTYPES", "stored_pools", "kv_rows", "gather_kv",
           "quantize_kv",
           "dequantize_window", "gather_scales", "paged_attention",
           "prefill_attention", "prefill_impl", "token_page_coords",
           "prompt_page_coords", "chunk_page_coords", "verify_page_coords",
           "write_token_kv", "write_prompt_kv", "export_pages",
           "import_pages"]


def quantize_kv(x):
    """Per-head abs-max int8 for a K or V write of any leading shape
    ``[..., nh, dh]`` -> (int8 values ``[..., nh, dh]``, f32 scales
    ``[..., nh]``).

    The scale granularity is per TOKEN-SLOT per head — one scale for each
    (page, offset, head) cell, stored ``[nl, P, page_size, nh]`` alongside
    the pool. A single per-page scale cannot survive the engine's
    incremental writes: decode lands one token per step into a partially
    filled page, and re-scaling the page for a later token's larger abs-max
    would silently corrupt every earlier token's dequantization. One scale
    per written cell makes each write self-contained — pages (and their
    scales) are immutable once full, which is what lets the prefix cache
    share them by reference (docs/QUANTIZATION.md)."""
    from paddle_tpu.quantization.comms import absmax_int8
    return absmax_int8(x, axis=-1)


def dequantize_window(win, scales):
    """int8 gathered window ``[..., nh, dh]`` + scales ``[..., nh]`` -> f32."""
    return win.astype(jnp.float32) * scales[..., None]


def stored_pools(op, k_pages, v_pages, k_scale=None, v_scale=None,
                 layer=None):
    """One attention call's pools in the STORED layout — stacked merged
    ``[nl, num_pages, page_size, nh * dh]`` values, ``[nl, num_pages,
    page_size, nh]`` scales — and the layer to read:
    ``(k_pages, v_pages, k_scale, v_scale, layer)``.

    With ``layer`` the operands already are that and pass through. Without
    it they are ONE layer's pools (``[num_pages, page_size, nh, dh]``, or
    merged rank 3) and are viewed as a stack of one. On a TPU that view is
    a copy of the whole pool (and a caller that sliced the layer out of a
    stack has paid another), so each such call counts in the trace-time
    ``kernel.pool_relayout.{op}``: a program that still relays the pool
    shows without a chip."""
    if layer is not None:
        if isinstance(layer, int) and not 0 <= layer < k_pages.shape[0]:
            # on the chip a layer past the stack is a wild DMA, which
            # halts the core: refuse it while tracing
            raise IndexError(f"layer {layer} of a pool of "
                             f"{k_pages.shape[0]} layers")
        return k_pages, v_pages, k_scale, v_scale, layer
    registry.count_relayout(op)

    def stack(pool):               # [P, ps, ...] -> [1, P, ps, merged]
        return None if pool is None else pool.reshape(1, *pool.shape[:2], -1)
    return (stack(k_pages), stack(v_pages), stack(k_scale), stack(v_scale),
            0)


def kv_rows(x, pool):
    """``[..., nh, dh]`` K or V values as the pool's lane-dense merged rows
    ``[..., nh * dh]`` in its dtype — what every writer scatters."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1]) \
        .astype(pool.dtype)


def gather_kv(pages, page_table, layer, nh):
    """Materialize one layer's paged K (or V) into per-sequence windows, in
    ONE gather over the stored pool (the layer is an index of the gather,
    never a slice taken first).

    pages      : [nl, num_pages, page_size, nh * dh]
    page_table : [B, pages_per_slot] int32 page indices
    returns    : [B, pages_per_slot * page_size, nh, dh]
    """
    ps = pages.shape[2]
    b, maxp = page_table.shape
    return pages[layer, page_table].reshape(b, maxp * ps, nh, -1)


def gather_scales(scales, page_table, layer):
    """[nl, num_pages, page_size, nh] scales -> [B, Lmax, nh] per-slot
    windows of one layer (the scale-side twin of :func:`gather_kv`)."""
    ps, nh = scales.shape[2:]
    b, maxp = page_table.shape
    return scales[layer, page_table].reshape(b, maxp * ps, nh)


def query_groups(q_heads, dh, pool_width):
    """Query heads that read one K/V head of a pool whose rows are
    ``pool_width`` wide: 1 for GPT-2's one-to-one heads, ``g`` for grouped
    queries (query head ``h`` reads K/V head ``h // g``)."""
    nkv, rest = divmod(pool_width, dh)
    if rest or nkv == 0 or q_heads % nkv:
        raise ValueError(f"{q_heads} query heads of width {dh} over a pool "
                         f"row of {pool_width}")
    return q_heads // nkv


def _xla_paged_attention(q, k_pages, v_pages, page_table, pos, layer,
                         k_scale=None, v_scale=None, scale=None):
    """The gather + masked f32-softmax reference implementation over the
    stored pool at ``layer``. With ``k_scale``/``v_scale`` ([nl, num_pages,
    page_size, nh] f32) the pages are int8 and dequantize in-register right
    after the gather — the same f32 score/softmax math runs on the
    dequantized values. Grouped queries (``q`` has ``g`` x the pool's
    heads) run the same math with the K/V head shared by its ``g`` query
    heads; ``scale`` multiplies the scores (None: ``1 / sqrt(dh)``)."""
    nh, dh = q.shape[-2:]
    g = query_groups(nh, dh, k_pages.shape[-1])
    nkv = nh // g
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    k = gather_kv(k_pages, page_table, layer, nkv).astype(jnp.float32)
    v = gather_kv(v_pages, page_table, layer, nkv).astype(jnp.float32)
    if k_scale is not None:                             # [B, Lmax, nh, dh]
        k = k * gather_scales(k_scale, page_table, layer)[..., None]
        v = v * gather_scales(v_scale, page_table, layer)[..., None]
    lmax = k.shape[1]
    mask = jnp.arange(lmax)[None, :] <= pos[:, None]         # [B, Lmax]
    qs = q.astype(jnp.float32) * scale
    if g > 1:
        sc = jnp.einsum("bkgd,blkd->bkgl", qs.reshape(-1, nkv, g, dh), k)
        sc = jnp.where(mask[:, None, None, :], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        att = jnp.einsum("bkgl,blkd->bkgd", pr, v).reshape(q.shape)
        return att.astype(q.dtype)
    sc = jnp.einsum("bhd,blhd->bhl", qs, k)
    sc = jnp.where(mask[:, None, :], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    att = jnp.einsum("bhl,blhd->bhd", pr, v)
    return att.astype(q.dtype)


def _impl_call(impl, q, k_pages, v_pages, page_table, pos, layer,
               k_scale=None, v_scale=None, scale=None):
    """Execute one named implementation over the stored pool at ``layer``
    (also what the selection times)."""
    if impl == "pallas":
        from paddle_tpu.kernels.pallas.paged_attention import (
            paged_attention as pallas_paged)
        return pallas_paged(q, k_pages, v_pages, page_table, pos,
                            layer=layer, k_scale=k_scale, v_scale=v_scale,
                            scale=scale)
    return _xla_paged_attention(q, k_pages, v_pages, page_table, pos, layer,
                                k_scale=k_scale, v_scale=v_scale,
                                scale=scale)


def _pool_cands(backend):
    """The two pool-reading ops' impls viable on this backend (by name,
    never by execution). Pallas is offered on a TPU only: interpret mode
    off-TPU is a parity tool, not a serving path."""
    return ["xla", "pallas"] if backend == "tpu" else ["xla"]


def _paged_cands(ctx):
    cands = _pool_cands(ctx.get("backend", registry.backend()))
    if ctx.get("grouped"):
        # grouped queries are not measured (:func:`paged_attention`): where
        # the kernel is viable it is preferred, as it read ten times
        # faster at 32 heads over 8 (PERF.md section 6, PR 31)
        cands = cands[::-1]
    return cands


def _prefill_cands(ctx):
    cands = _pool_cands(ctx.get("backend", registry.backend()))
    if not ctx.get("parity", True):
        # the pallas arm reads the PAGE POOL; when the pool dtype narrows
        # the compute dtype (bf16 pages under f32 weights, non-quant), the
        # one-shot XLA arm attends the raw full-precision K/V — offering
        # pallas there would silently change numerics, so it is not viable
        cands = [c for c in cands if c != "pallas"]
    if ctx.get("grouped"):
        # the Pallas prefill arm takes one K/V head a query head
        cands = [c for c in cands if c != "pallas"]
    return cands


registry.register_op("paged_attention", impls=("xla", "pallas"),
                     candidates=_paged_cands,
                     alias_counter="paged_attention.impl")
registry.register_op("prefill_attention", impls=("xla", "pallas"),
                     candidates=_prefill_cands)


def _selection_key(tag, dims, dtype, quant, num_pages, suffix=""):
    """A signature's key in the winner table: ``(tag, backend, *dims[,
    "pool<num_pages>"], dtype[/kv-int8][suffix])``. An int8 pool keys its
    own winner: the dequant changes each arm's arithmetic intensity."""
    return (tag, registry.backend(), *(int(d) for d in dims)) \
        + (() if num_pages is None else (f"pool{int(num_pages)}",)) \
        + (str(dtype) + ("/kv-int8" if quant else "") + suffix,)


def _synthetic_pools(num_pages, page_size, nh, dh, dtype, quant):
    """Seeded K and V pools in the engine's stored layout, a stack of one
    layer ``[1, num_pages, page_size, nh * dh]`` (read with ``layer=0``: no
    arm's cost depends on how many layers the stack holds), made on the
    device (a real pool is GBs: no host round trip): ``(k, v)``, or for
    ``quant`` int8 pools and their unit scales ``(k, v, k_scale,
    v_scale)``, in the order the arms take them."""
    kk, kv = jax.random.split(jax.random.PRNGKey(0))
    shape = (1, num_pages, page_size, nh * dh)
    kp, vp = (jax.random.normal(k, shape, "float32").astype(dtype)
              for k in (kk, kv))
    if not quant:
        return kp, vp
    ones = jnp.ones(shape[:3] + (nh,), jnp.float32)
    return kp.astype(jnp.int8), vp.astype(jnp.int8), ones, ones


def _paged_selection(b, pages_per_slot, page_size, nh, dh, dtype, *,
                     quant=False, num_pages=None):
    """``(key, measure)`` of one decode signature for `registry.dispatch`:
    its key in the winner table — ("paged", backend, B, pages_per_slot,
    page_size, nh, dh[, pool], dtype[/kv-int8]) — and ``measure(impl) ->
    seconds`` of one launch over a synthetic pool under a ragged position
    mix, so that the measurement sees the kernel's length-aware stop.

    ``dtype`` is the query's, a REAL dtype (the arrays are built with it).
    ``num_pages`` is the caller's REAL pool size: the synthetic pool is
    built that large (and the winner keyed by it), so that an arm whose
    cost depends on the pool's capacity and not only on the pages it reads
    is timed as the step programs run it. None keeps the smallest pool that
    holds every slot."""
    key = _selection_key("paged", (b, pages_per_slot, page_size, nh, dh),
                         dtype, quant, num_pages)
    state = {}

    def measure(impl):
        if not state:
            pool = max(1 + b * pages_per_slot, int(num_pages or 0))
            q = jnp.asarray(np.random.RandomState(0).randn(b, nh, dh)
                            .astype(np.float32)).astype(dtype)
            state.update(
                args=(q, *_synthetic_pools(pool, page_size, nh, dh, dtype,
                                           quant)),
                pt=jnp.asarray(1 + np.arange(b * pages_per_slot,
                                             dtype=np.int32)
                               .reshape(b, pages_per_slot)),
                # ragged mix spanning 1..pages_per_slot pages — the
                # serving shape the pallas kernel's stop is built for
                pos=jnp.asarray(((np.arange(b) % pages_per_slot) + 1)
                                * page_size - 1, dtype=jnp.int32))
        step = jax.jit(lambda q_, k_, v_, *scales: _impl_call(
            impl, q_, k_, v_, state["pt"], state["pos"], 0, *scales))
        return registry.measure(step, state["args"])

    return key, measure


def paged_attention(q, k_pages, v_pages, page_table, pos,
                    k_scale=None, v_scale=None, *, layer=None, scale=None):
    """One decode step of attention over paged K/V for B sequences.

    q          : [B, nh, dh] query for the CURRENT token of each sequence;
                 ``nh`` may be ``g`` x the pool's heads (grouped queries:
                 head ``h`` reads K/V head ``h // g``)
    k_pages    : [nl, num_pages, page_size, nh * dh] — the stored pool,
                 read at ``layer``. Without ``layer``: one layer's
                 [num_pages, page_size, nh, dh] (or merged rank 3), which
                 costs a copy of it on the chip (:func:`stored_pools`)
    v_pages    : as k_pages
    page_table : [B, pages_per_slot] int32
    pos        : [B] int32 — position of the current token (already written
                 to the cache); attends over positions 0..pos inclusive
    k_scale/v_scale : optional f32 scales of an int8 pool, [nl, num_pages,
                 page_size, nh] (per-layer form: without the leading nl)
    scale      : what multiplies the scores (None: ``1 / sqrt(dh)``)
    returns    : [B, nh, dh] in q.dtype

    Same numerics as the dense path (f32 scores, -1e30 mask, f32 softmax):
    token-identical output is the contract, not an approximation. Dispatches
    on ``FLAGS_tpu_paged_impl`` (module docstring); the selection runs at
    trace time, so the winner string is baked into each compiled program and
    the ``paged_attention.impl.*`` counters count program builds (once per
    trace of the calling code: once a GPT step program, whose block is one
    traced function), not steps.
    """
    k_pages, v_pages, k_scale, v_scale, layer = stored_pools(
        "paged_attention", k_pages, v_pages, k_scale, v_scale, layer)
    try:
        from paddle_tpu.framework.flags import flag_value
        forced = flag_value("tpu_paged_impl")
    except Exception:          # flags registry unavailable (early import)
        forced = "xla"
    # a grouped signature is not measured: the probe's pool would be a
    # second pool of the model's own size (every slot's pages are distinct)
    # with the xla arm's gathers beside it, at a size where the first fills
    # the chip. It takes the candidates' preference (`_paged_cands`)
    grouped = query_groups(q.shape[1], q.shape[2], k_pages.shape[-1]) > 1
    key, measure = (None, None) if grouped else _paged_selection(
        q.shape[0], page_table.shape[1], k_pages.shape[2], q.shape[1],
        q.shape[2], q.dtype, quant=k_scale is not None,
        num_pages=k_pages.shape[1])
    impl = registry.dispatch("paged_attention", forced=forced,
                             ctx={"grouped": grouped}, key=key,
                             measure=measure)
    return _impl_call(impl, q, k_pages, v_pages, page_table, pos, layer,
                      k_scale=k_scale, v_scale=v_scale, scale=scale)


def _xla_prefill_attention(q, k_pages, v_pages, page_table, start, valid,
                           layer, k_scale=None, v_scale=None, scale=None):
    """The gather + absolute-position-masked f32-softmax PREFILL reference
    — exactly the math `models/gpt.py::prefill_chunk_step` always ran: the
    chunk's queries attend over ALL cached positions (previous chunks AND
    the current one) via the paged gather, masked so a query at position p
    sees keys 0..p. Traffic and FLOPs scale with the slot's capacity
    (``pages_per_slot``), which is what the Pallas arm fixes.

    q : [1, C, nh, dh] chunk queries; page_table : [pages_per_slot];
    start/valid : the chunk's absolute origin and true token count.
    ``valid`` only matters to the Pallas arm's row masking — padded rows
    here compute like the real ones (their output is never consumed).
    Grouped queries and ``scale`` as :func:`_xla_paged_attention`.
    """
    _, c, nh, dh = q.shape
    g = query_groups(nh, dh, k_pages.shape[-1])
    nkv = nh // g
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    if g > 1 and k_scale is None \
            and page_table.shape[0] * k_pages.shape[2] > LONG_ROW:
        return _xla_prefill_walk(q, k_pages, v_pages, page_table, start,
                                 layer, nkv, scale)
    row = page_table[None]
    kk = gather_kv(k_pages, row, layer, nkv).astype(jnp.float32)
    vv = gather_kv(v_pages, row, layer, nkv).astype(jnp.float32)
    if k_scale is not None:
        kk = kk * gather_scales(k_scale, row, layer)[..., None]
        vv = vv * gather_scales(v_scale, row, layer)[..., None]
    lmax = kk.shape[1]
    pos = start + jnp.arange(c)
    mask = jnp.arange(lmax)[None, :] <= pos[:, None]         # [C, Lmax]
    qs = q.astype(jnp.float32) * scale
    if g > 1:
        sc = jnp.einsum("bqkgd,blkd->bkgql", qs.reshape(1, c, nkv, g, dh),
                        kk)
        sc = jnp.where(mask[None, None, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgql,blkd->bqkgd", pr, vv).reshape(
            q.shape).astype(q.dtype)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qs, kk)
    sc = jnp.where(mask[None, None], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, vv).astype(q.dtype)


LONG_ROW = 8192      # positions of a slot's page row past which a grouped
#                      chunk walks the row by blocks (read: 4,096 one-shot,
#                      17,920 by blocks)
WALK_BLOCK = 2048    # keys a turn of that walk takes


def _xla_prefill_walk(q, k_pages, v_pages, page_table, start, layer, nkv,
                      scale):
    """`_xla_prefill_attention` for grouped queries over a LONG page row:
    the same masked float32 softmax, taken `WALK_BLOCK` keys a turn with a
    running maximum, sum and output, and only as many turns as reach the
    chunk's last position. The one-shot form gathers the slot's whole row
    and holds scores ``[kv heads, group, chunk, row]`` whatever the
    context: at 64 query heads over 8, a chunk of 512 and a row of 17,920
    that is 2.35 GB and 10.2 ms a chunk for the first chunk of a prompt as
    for its last (my chip run, PR 50); by blocks a chunk pays for the keys
    it can see. A row of at most `LONG_ROW` positions keeps the one-shot
    form, whose single product wins there."""
    _, c, nh, dh = q.shape
    g = nh // nkv
    ps = k_pages.shape[2]
    bp = max(1, WALK_BLOCK // ps)                  # pages a turn
    block = bp * ps
    turns_max = -(-page_table.shape[0] // bp)
    table = jnp.pad(page_table, (0, turns_max * bp - page_table.shape[0])
                    ).reshape(turns_max, bp)       # padded with the trash page
    qs = (q[0].astype(jnp.float32) * scale).reshape(c, nkv, g, dh)
    pos = (start + jnp.arange(c)).astype(jnp.int32)
    f32 = jnp.float32

    def turn(i, carry):
        m, total, acc = carry
        pages = jax.lax.dynamic_index_in_dim(table, i, 0, keepdims=False)
        k = k_pages[layer, pages].reshape(block, nkv, dh).astype(f32)
        v = v_pages[layer, pages].reshape(block, nkv, dh).astype(f32)
        sc = jnp.einsum("qkgd,lkd->kgql", qs, k)
        seen = (i * block + jnp.arange(block, dtype=jnp.int32))[None, :] \
            <= pos[:, None]                                    # [C, block]
        sc = jnp.where(seen[None, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        pr = jnp.exp(sc - m_new[..., None])
        keep = jnp.exp(m - m_new)
        return (m_new, total * keep + pr.sum(axis=-1),
                acc * keep[..., None] + jnp.einsum("kgql,lkd->kgqd", pr, v))

    # key 0 is in every query's sight, so the first turn sets a real maximum
    init = (jnp.full((nkv, g, c), -1e30, f32), jnp.zeros((nkv, g, c), f32),
            jnp.zeros((nkv, g, c, dh), f32))
    turns = jnp.minimum((start + c - 1) // block + 1, turns_max)
    _, total, acc = jax.lax.fori_loop(0, turns.astype(jnp.int32), turn, init)
    out = acc / total[..., None]                               # [k, g, C, dh]
    return jnp.moveaxis(out, 2, 0).reshape(q.shape).astype(q.dtype)


def _prefill_impl_call(impl, q, k_pages, v_pages, page_table, start, valid,
                       layer, k_scale=None, v_scale=None, scale=None):
    """Execute one named prefill impl over the stored pool at ``layer``
    (also what the selection times)."""
    if impl == "pallas":
        from paddle_tpu.kernels.pallas.prefill_attention import (
            prefill_attention as pallas_prefill)
        return pallas_prefill(q[0], k_pages, v_pages, page_table, start,
                              valid, layer=layer, k_scale=k_scale,
                              v_scale=v_scale, scale=scale)[None]
    return _xla_prefill_attention(q, k_pages, v_pages, page_table, start,
                                  valid, layer, k_scale=k_scale,
                                  v_scale=v_scale, scale=scale)


def _prefill_selection(chunk, pages_per_slot, page_size, nh, dh, dtype, *,
                       quant=False, parity=True, num_pages=None):
    """``(key, measure)`` of one PREFILL signature for `registry.dispatch`
    — ("prefill", backend, chunk, pages_per_slot, page_size, nh, dh[,
    pool], dtype[/kv-int8][/no-parity]) — as :func:`_paged_selection`; the
    measurement runs one mid-pool chunk (a page of prior context + a full
    chunk of fresh queries, ``[1, chunk, nh, dh]``) so the length-aware
    stop is exercised. A parity-gated signature (``parity=False``,
    `_prefill_cands`) keys a DISTINCT entry, so it can't adopt an ungated
    one's pallas win."""
    key = _selection_key("prefill",
                         (chunk, pages_per_slot, page_size, nh, dh), dtype,
                         quant, num_pages, "" if parity else "/no-parity")
    state = {}

    def measure(impl):
        if not state:
            pool = max(1 + pages_per_slot, int(num_pages or 0))
            q = jnp.asarray(np.random.RandomState(0).randn(1, chunk, nh, dh)
                            .astype(np.float32)).astype(dtype)
            state.update(
                args=(q, *_synthetic_pools(pool, page_size, nh, dh, dtype,
                                           quant)),
                row=jnp.asarray(1 + np.arange(pages_per_slot,
                                              dtype=np.int32)))
        # a page of prior context where the slot has room for it: the last
        # query position must stay inside the slot (start + chunk <=
        # capacity), past it the page walk leaves the page-table row
        start = jnp.int32(max(0, min(page_size,
                                     pages_per_slot * page_size - chunk)))
        step = jax.jit(lambda q_, k_, v_, *scales: _prefill_impl_call(
            impl, q_, k_, v_, state["row"], start, jnp.int32(chunk), 0,
            *scales))
        return registry.measure(step, state["args"])

    return key, measure


def prefill_impl(chunk, pages_per_slot, page_size, nh, dh, dtype,
                 quant=False, parity=True, num_pages=None,
                 grouped=False) -> str:
    """Resolve (and COUNT) the prefill-attention impl for one program
    build — the registry is the only selector (`kernels/registry.py`;
    ``FLAGS_tpu_prefill_impl`` forces, ``auto`` measures over
    :func:`_prefill_selection`'s workload). ``parity=False`` marks a call
    whose XLA arm does NOT read the page pool (the one-shot `prefill_step`
    over a narrowing pool dtype), which drops the pallas candidate rather
    than silently changing numerics. ``num_pages`` is the pool's size,
    which the measurement reproduces. A ``grouped`` signature (more query
    heads than the pool has) has the xla arm alone: the Pallas arm takes
    one K/V head a query head."""
    try:
        from paddle_tpu.framework.flags import flag_value
        forced = flag_value("tpu_prefill_impl")
    except Exception:          # flags registry unavailable (early import)
        forced = "xla"
    key, measure = (None, None) if grouped else _prefill_selection(
        chunk, pages_per_slot, page_size, nh, dh, dtype, quant=quant,
        parity=parity, num_pages=num_pages)
    return registry.dispatch("prefill_attention", forced=forced,
                             ctx={"parity": parity, "grouped": grouped},
                             key=key, measure=measure,
                             require_viable=grouped)


def prefill_attention(q, k_pages, v_pages, page_table, start, valid,
                      k_scale=None, v_scale=None, *, layer=None,
                      scale=None):
    """One CHUNK of ragged prefill attention for ONE sequence, over pages
    the chunk's K/V were just written to — the dispatch switch the
    registry routes (`prefill_step` / `prefill_chunk_step` / the PTKS1
    streaming path all land here or on :func:`prefill_impl`):

    q          : [1, C, nh, dh] chunk queries (leading batch of 1 — the
                 step programs' native layout); ``nh`` may be ``g`` x the
                 pool's heads (grouped queries)
    k_pages    : [nl, num_pages, page_size, nh * dh] — the stored pool,
                 read at ``layer`` (without ``layer``: one layer's pool,
                 as :func:`paged_attention`)
    page_table : [pages_per_slot] int32 — this sequence's page row
    start      : scalar int32 absolute position of the chunk's first token
    valid      : scalar int32 true token count in this chunk
    scale      : what multiplies the scores (None: ``1 / sqrt(dh)``)
    returns    : [1, C, nh, dh] in q.dtype — token-identical between arms
                 (rows < valid; parity-tested in interpret mode off-TPU)
    """
    k_pages, v_pages, k_scale, v_scale, layer = stored_pools(
        "prefill_attention", k_pages, v_pages, k_scale, v_scale, layer)
    impl = prefill_impl(q.shape[1], page_table.shape[0], k_pages.shape[2],
                        q.shape[2], q.shape[3], q.dtype,
                        quant=k_scale is not None,
                        num_pages=k_pages.shape[1],
                        grouped=query_groups(q.shape[2], q.shape[3],
                                             k_pages.shape[-1]) > 1)
    return _prefill_impl_call(impl, q, k_pages, v_pages, page_table, start,
                              valid, layer, k_scale=k_scale, v_scale=v_scale,
                              scale=scale)


def token_page_coords(page_table, pos, active, page_size):
    """(page, offset) for writing token ``pos`` of each of B sequences.

    page_table : [B, pages_per_slot] int32; pos : [B] int32; active : [B]
    bool — inactive slots are routed to TRASH_PAGE, and so is any position
    past the slot's capacity (``pos >= pages_per_slot * page_size``): a
    clamped overflow would silently corrupt the LAST page's KV, which the
    engine then attends over. Returns ([B], [B]).
    """
    maxp = page_table.shape[1]
    idx = pos // page_size
    page = jnp.take_along_axis(page_table,
                               jnp.clip(idx, 0, maxp - 1)[:, None],
                               axis=1)[:, 0]
    page = jnp.where(active & (idx < maxp), page, TRASH_PAGE)
    return page, pos % page_size


def prompt_page_coords(page_table, length, seq_len, page_size):
    """(page, offset) for writing positions 0..seq_len-1 of ONE sequence.

    page_table : [pages_per_slot] int32; length : scalar int32 true prompt
    length (positions >= length — bucket padding — go to TRASH_PAGE, as do
    positions past the slot's capacity rather than corrupting the last
    page). Returns ([seq_len], [seq_len]).
    """
    maxp = page_table.shape[0]
    t = jnp.arange(seq_len)
    idx = t // page_size
    page = jnp.where((t < length) & (idx < maxp),
                     page_table[jnp.clip(idx, 0, maxp - 1)], TRASH_PAGE)
    return page, t % page_size


def chunk_page_coords(page_table, start, valid, seq_len, page_size):
    """(page, offset) for writing a prefill CHUNK — positions
    ``start .. start+seq_len-1`` of ONE sequence.

    page_table : [pages_per_slot] int32; start : scalar int32 absolute
    position of the chunk's first token; valid : scalar int32 true token
    count in this chunk (chunk-padding positions ``i >= valid`` go to
    TRASH_PAGE, as do positions past the slot's capacity). The ``start=0,
    valid=length`` case degenerates to :func:`prompt_page_coords`.
    Returns ([seq_len], [seq_len]).
    """
    maxp = page_table.shape[0]
    t = start + jnp.arange(seq_len)
    idx = t // page_size
    page = jnp.where((jnp.arange(seq_len) < valid) & (idx < maxp),
                     page_table[jnp.clip(idx, 0, maxp - 1)], TRASH_PAGE)
    return page, t % page_size


def verify_page_coords(page_table, pos, valid, page_size):
    """(page, offset) for writing a [B, W] WINDOW of tokens per sequence —
    the speculative-decode verify step's write pattern (`models/gpt.py::
    verify_step`): each slot writes its current token plus up to W-1
    drafted tokens in one step.

    page_table : [B, pages_per_slot] int32; pos : [B, W] int32 absolute
    positions; valid : [B, W] bool — padding drafts, inactive slots, and
    positions past the slot's capacity all route to TRASH_PAGE (rejected
    drafts leave garbage ONLY at positions past the rolled-back length,
    which every later step overwrites before attending). Returns
    ([B, W], [B, W]).
    """
    maxp = page_table.shape[1]
    idx = pos // page_size
    page = jnp.take_along_axis(page_table, jnp.clip(idx, 0, maxp - 1), axis=1)
    page = jnp.where(valid & (idx < maxp), page, TRASH_PAGE)
    return page, pos % page_size


def export_pages(k_pages, v_pages, page_list, num_heads, k_scales=None,
                 v_scales=None):
    """Gather the listed pages' contents out of the pool — the send side of
    the page-granular KV handoff (a prefill finished on one replica resumes
    decode on another; docs/SERVING.md). The page table makes the transfer a
    page-index gather, never a tensor-relayout.

    Wire integrity lives one layer up (docs/ROBUSTNESS.md "Wire
    integrity"): when these blobs travel as ``PTKV1``/``PTMG1`` bytes,
    `engine.KVHandoff.pack` stamps a blake2b body checksum the unpack
    side verifies BEFORE any page byte is interpreted — a truncated or
    bit-flipped transfer is a typed ``HandoffCorrupt`` refusal, so the
    scatter below only ever sees intact pages. The KV tier store
    (`inference/kv_tiers.py`) rides the same pair of primitives: a
    prefix-page spill is this gather framed as a checksummed ``PTKT1``
    blob per page, and a tier hit re-uploads through `import_pages` —
    pages and scales are immutable once full, so the round trip is
    bit-identical.

    k_pages/v_pages : [num_layers, num_pages, page_size, nh * dh]
    page_list       : [n] int page indices (a sequence's allocation,
                      in token order)
    num_heads       : nh — a blob leaves the pool as ``[..., nh, dh]``, the
                      shape every wire format states (``PTKV1``'s
                      ``pages_shape``, ``PTKT1`` frames); ``[nh, dh]`` and
                      ``nh * dh`` are the same row-major bytes, so the
                      blobs are byte-for-byte what an unmerged pool gave
    k_scales/v_scales : optional [num_layers, num_pages, page_size, nh] f32
                      (int8 pools); the listed pages' scales travel with
                      their values so the handoff stays bit-exact
    returns         : (k_blob, v_blob) each [num_layers, n, page_size, nh, dh]
                      — plus (k_s_blob, v_s_blob) when scales were given
    """
    idx = jnp.asarray(page_list, jnp.int32)

    def blob(pool):
        got = pool[:, idx]             # (n may be 0: no -1 in the shape)
        return got.reshape(*got.shape[:3], num_heads,
                           got.shape[3] // num_heads)
    if k_scales is None:
        return blob(k_pages), blob(v_pages)
    return (blob(k_pages), blob(v_pages), k_scales[:, idx], v_scales[:, idx])


def import_pages(k_pages, v_pages, k_blob, v_blob, page_list,
                 k_scales=None, v_scales=None, k_s_blob=None, v_s_blob=None):
    """Scatter exported page contents into a (different) pool at (different)
    page indices — the receive side of the KV handoff. Only the page IDS
    change across the transfer; contents (and, for int8 pools, their scales)
    land bit-identical, so decode on the importing replica matches decode
    where the prefill ran.

    k_pages/v_pages : [num_layers, num_pages, page_size, nh * dh]
    k_blob/v_blob : [num_layers, n, page_size, nh, dh] from `export_pages`
    page_list     : [n] destination page indices in THIS pool
    returns       : (k_pages, v_pages) updated — plus (k_scales, v_scales)
                    when the scale pools/blobs were given
    """
    idx = jnp.asarray(page_list, jnp.int32)
    kp = k_pages.at[:, idx].set(kv_rows(k_blob, k_pages))
    vp = v_pages.at[:, idx].set(kv_rows(v_blob, v_pages))
    if k_scales is None:
        return kp, vp
    return (kp, vp,
            k_scales.at[:, idx].set(jnp.asarray(k_s_blob, k_scales.dtype)),
            v_scales.at[:, idx].set(jnp.asarray(v_s_blob, v_scales.dtype)))


def write_token_kv(k_pages, v_pages, k, v, page_table, pos, active, layer):
    """Scatter one new K/V token per sequence into its page of ``layer``.

    k_pages/v_pages : [nl, num_pages, page_size, nh * dh]
    k, v       : [B, nh, dh] — the current token's key/value (one layer)
    page_table : [B, pages_per_slot] int32
    pos        : [B] int32 token position being written
    active     : [B] bool — inactive slots write to TRASH_PAGE
    returns    : (k_pages, v_pages) updated
    """
    page, off = token_page_coords(page_table, pos, active, k_pages.shape[2])
    return (k_pages.at[layer, page, off].set(kv_rows(k, k_pages)),
            v_pages.at[layer, page, off].set(kv_rows(v, v_pages)))


def write_prompt_kv(k_pages, v_pages, k, v, page_table, length, layer):
    """Scatter a whole prompt's K/V (one sequence, one layer) into its pages.

    k, v       : [S, nh, dh] — S is the PADDED bucket length; positions
                 >= length (prompt padding) go to TRASH_PAGE
    page_table : [pages_per_slot] int32
    length     : scalar int32, true prompt length
    returns    : (k_pages, v_pages) updated
    """
    page, off = prompt_page_coords(page_table, length, k.shape[0],
                                   k_pages.shape[2])
    return (k_pages.at[layer, page, off].set(kv_rows(k, k_pages)),
            v_pages.at[layer, page, off].set(kv_rows(v, v_pages)))
