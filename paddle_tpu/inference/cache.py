"""The device-side cache: ONE object holds every array a step program
updates in place, and only this module knows what is in it.

`DeviceCache` is a pytree of the K and V page pools (stored as the
attention kernels read them: `kernels/paged_attention.py`; empty, of zero
layers and the trash page alone, for a family that keeps nothing in pages:
`family.kv_layers` 0; for a family whose page row is not K and V,
`family.page_rows`, one pool a part of the row and no twin), the int8 pool's
scale pools or None, the family's state arrays beside the pool (window
rings, recurrent state: `inference/family.py`), and the fused sampler's
per-slot PRNG key chains or None. Every step program is

    exe(params, cache, *small) -> (*lead, cache)

with the cache as its one donated argument and its last result
(`inference/programs.py`); `DecodeEngine` holds one and replaces it whole
at every call. A new kind of state is a new field here (or one more array
in the family's ``state``): it then reaches every program, donated and
returned, with no edit to a program or a call site.

Beside it, what belongs to it: its allocation from the family and the
config, with the gauges that describe it; the translations between the
object and the two contracts the family's step functions have (a dict for
`decode_step` / `verify_step`, positional pools for the prefill steps);
page export and import (the wire's and the tiers' only way in and out);
and `PageAllocator`, the host-side free list over the pool's pages.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field, replace

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.kernels.paged_attention import (KV_DTYPES, TRASH_PAGE,
                                                export_pages, import_pages)
from paddle_tpu.observability import metrics
from paddle_tpu.testing import faults

__all__ = ["DeviceCache", "PageAllocator"]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class DeviceCache:
    k: jax.Array                    # [nl, num_pages, page_size, nh * dh]
    v: jax.Array
    k_scale: jax.Array | None       # [nl, num_pages, page_size, nh] f32:
    v_scale: jax.Array | None       # an int8 pool's, else None
    state: tuple                    # the family's arrays, in its order
    keys: jax.Array | None          # [slots + 1, 2] uint32 sampler chains
    #                                 (row ``slots``: slotless prefills)
    heads: int = field(metadata=dict(static=True))   # nh of the pools

    @classmethod
    def allocate(cls, fam, ecfg, num_pages: int, served_dtype):
        """A zeroed cache for ``fam`` under ``ecfg``: pools of
        ``ecfg.kv_dtype`` ("native" follows ``served_dtype``), scale pools
        for an int8 pool, the family's state for ``max_slots`` slots, key
        chains on a sampling engine. Publishes its size gauges."""
        dtype = served_dtype
        if ecfg.kv_dtype not in ("native", None):
            if ecfg.kv_dtype not in KV_DTYPES:
                raise ValueError(
                    f"kv_dtype={ecfg.kv_dtype!r}: expected 'native', "
                    f"{sorted(KV_DTYPES)}")
            dtype = jnp.dtype(KV_DTYPES[ecfg.kv_dtype])
        nl, nh, ps, B = (fam.kv_layers, fam.kv_heads, ecfg.page_size,
                         ecfg.max_slots)
        # the pools' shapes first, each pool made once: a pool can be a
        # quarter of the chip, and a K-and-V pair made before a family's
        # own parts replaced it did not fit beside the weights
        k_shape = v_shape = (nl, num_pages, ps, nh * fam.head_dim)
        if fam.page_rows:
            # rows that are not K and V (inference/family.py): one pool a
            # part, no twin
            if ecfg.kv_dtype == "int8":
                raise ValueError("kv_dtype='int8': the scale pools are per "
                                 "K/V head, and this family's page rows "
                                 "have none")
            widths = [int(w) for _, w in fam.page_rows]
            k_shape = (nl, num_pages, ps, widths[0])
            v_shape = (nl, num_pages, ps, widths[1]) \
                if len(widths) == 2 else (0, 1, ps, 0)
        k, v = jnp.zeros(k_shape, dtype), jnp.zeros(v_shape, dtype)
        # int8 pool: per-token-slot per-head f32 scales, written by the
        # same scatters that write the pages (docs/QUANTIZATION.md)
        ks = jnp.zeros((nl, num_pages, ps, nh), jnp.float32) \
            if ecfg.kv_dtype == "int8" else None
        specs = fam.state(B, ps, served_dtype) if fam.state else ()
        cache = cls(
            k=k, v=v, k_scale=ks,
            v_scale=None if ks is None else jnp.zeros_like(ks),
            state=tuple(jnp.zeros(shape, dt) for _, _, shape, dt in specs),
            keys=jnp.zeros((B + 1, 2), jnp.uint32) if ecfg.sampling
            else None, heads=nh)

        def nbytes(kind):
            return sum(int(a.nbytes) for a, (_, kd, _, _)
                       in zip(cache.state, specs) if kd == kind)
        metrics.gauge("engine.kv_bytes_per_token").set(cache.bytes_per_token)
        metrics.gauge("engine.cache_bytes.paged").set(
            int(k.nbytes) + int(v.nbytes)
            + (0 if ks is None else 2 * int(ks.nbytes)))
        for (name, _), pool in zip(fam.page_rows, (k, v)):
            metrics.gauge(f"engine.cache_bytes.paged.{name}").set(
                int(pool.nbytes))
        metrics.gauge("engine.cache_bytes.window").set(nbytes("window"))
        metrics.gauge("engine.cache_bytes.state").set(nbytes("recurrent"))
        metrics.gauge("engine.state_bytes_per_slot").set(
            nbytes("recurrent") // B)
        return cache

    @property
    def bytes_per_token(self) -> int:
        """Bytes each cached token costs across all layers (the values of
        both pools' rows: K + V, or a family's own parts; plus scales when
        quantized): the capacity yardstick bench_quant's
        slots-at-fixed-pool-bytes assertion is computed from."""
        return sum(
            p.shape[0] * (p.shape[3] * jnp.dtype(p.dtype).itemsize
                          + (0 if self.k_scale is None else self.heads * 4))
            for p in (self.k, self.v))

    # ---- what the family's step functions take and return (family.py) ----

    def step_view(self, page_table, lengths) -> dict:
        """The ``cache`` dict of `decode_step` / `verify_step`."""
        return dict(k_pages=self.k, v_pages=self.v, page_table=page_table,
                    lengths=lengths, **self.extras())

    def after_step(self, view: dict) -> "DeviceCache":
        return replace(self, k=view["k_pages"], v=view["v_pages"],
                       k_scale=view.get("k_scale"),
                       v_scale=view.get("v_scale"),
                       state=tuple(view.get("state", ())))

    def extras(self) -> dict:
        """What is there beside the pools, under the names both contracts
        use: entries of the step dict, keyword arguments of `prefill_step`
        / `prefill_chunk_step` (a family with state also takes ``slot=``:
        the program's, from its upload)."""
        kw = {}
        if self.k_scale is not None:
            kw.update(k_scale=self.k_scale, v_scale=self.v_scale)
        if self.state:
            kw.update(state=self.state)
        return kw

    def after_prefill(self, k, v, *more) -> "DeviceCache":
        """From what a prefill step returns after its logits: the pools,
        then the scale pools of an int8 pool, then the state arrays."""
        n = 0 if self.k_scale is None else 2
        return replace(self, k=k, v=v, k_scale=more[0] if n else None,
                       v_scale=more[1] if n else None, state=tuple(more[n:]))

    def with_keys(self, keys) -> "DeviceCache":
        return replace(self, keys=keys)

    # ---- host side: pages in and out, one slot's key chain ----

    def export_pages(self, pages):
        """The listed pages' contents off the device, as numpy ``(k, v,
        k_scales, v_scales)``: values ``[nl, n, page_size, nh, dh]`` (the
        shape every wire format states), scales ``[nl, n, page_size, nh]``
        or None off a float pool. ONE batched gather per pool."""
        out = [np.asarray(b) for b in export_pages(
            self.k, self.v, pages, self.heads,
            k_scales=self.k_scale, v_scales=self.v_scale)]
        return tuple(out) if self.k_scale is not None else (*out, None, None)

    def import_pages(self, pages, k, v, k_scales=None,
                     v_scales=None) -> "DeviceCache":
        """The cache with exported page contents scattered in at
        ``pages``, bit-identical: ``k`` / ``v`` as `export_pages` gave
        them, and the blob's scales (None off a float pool)."""
        if (k_scales is None) != (self.k_scale is None):
            raise ValueError(
                "page import: the blob "
                + ("carries scales a float pool has no place for"
                   if self.k_scale is None else "is missing the scales "
                   "an int8 pool needs"))
        k, v, *scales = import_pages(
            self.k, self.v, jnp.asarray(k), jnp.asarray(v), pages,
            k_scales=self.k_scale, v_scales=self.v_scale,
            k_s_blob=k_scales, v_s_blob=v_scales)
        return replace(self, k=k, v=v, k_scale=scales[0] if scales else None,
                       v_scale=scales[1] if scales else None)

    def key_chain(self, slot: int) -> list[int]:
        """One slot's sampler chain as advanced so far (a readback: for
        migration, never on the step loop)."""
        k0, k1 = np.asarray(self.keys)[slot]
        return [int(k0), int(k1)]

    def with_key_chain(self, slot: int, key) -> "DeviceCache":
        return replace(self, keys=self.keys.at[slot].set(
            jnp.asarray(key, jnp.uint32)))


class PageAllocator:
    """Host-side REFCOUNTED free-list over the page pool. Page 0
    (TRASH_PAGE) is never handed out — it is the spill target for masked
    writes.

    Prefix caching (docs/SERVING.md) shares pages copy-on-write across
    slots: `share` grows a page's refcount and `free` releases one owner's
    claim, reclaiming only at refcount 0. A page the engine's prefix store
    still indexes is RETAINED at refcount 0 (its contents stay valid for
    future hits) instead of returning to the free list; under pool pressure
    `alloc` reclaims retained pages through ``evict_hook`` (LRU order, the
    engine owns the policy), so eviction can never touch a live slot's
    pages — only refcount-0 ones."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            # a family with no layer in the pool gets no allocator at all
            # (`DecodeEngine`): this is the pooled families' rule
            raise ValueError(f"a page pool needs >= 2 pages (1 is reserved), "
                             f"got {num_pages}")
        self.num_pages = num_pages
        self._free = deque(range(1, num_pages))
        self._refcnt = [0] * num_pages
        self._retained: set[int] = set()
        self.retain_hook = None   # page -> bool: keep this refcount-0 page?
        self.evict_hook = None    # n -> list[page]: reclaim retained pages
        self._g_in_use = metrics.gauge("engine.pages_in_use")

    @property
    def free_pages(self) -> int:
        """Pages allocatable RIGHT NOW: the free list plus refcount-0
        cached pages (reclaimable by eviction)."""
        return len(self._free) + len(self._retained)

    def _update_gauge(self):
        self._g_in_use.set(self.num_pages - 1 - self.free_pages)

    def refcount(self, page: int) -> int:
        return self._refcnt[page]

    def alloc(self, n: int) -> list[int] | None:
        """n pages or None (caller keeps the request queued — admission
        control is 'wait', never 'partially allocate'). Evicts refcount-0
        cached pages (LRU via ``evict_hook``) when the free list alone
        cannot cover the request."""
        if faults.ENABLED and faults.fire("engine.pool_pressure"):
            return None        # injected pool pressure (testing/faults.py)
        if n > self.free_pages:
            return None
        if n > len(self._free) and self.evict_hook is not None:
            for p in self.evict_hook(n - len(self._free)):
                if p not in self._retained or self._refcnt[p] != 0:
                    raise RuntimeError(
                        f"evict hook surrendered live page {p}")
                self._retained.discard(p)
                self._free.append(p)
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._refcnt[p] = 1
        self._update_gauge()
        return pages

    def reclaim(self, pages: list[int]):
        """Return RETAINED (refcount-0 cached) pages to the free list —
        the prefix store dropping its index outside an alloc-driven
        eviction (e.g. a weight swap invalidating every cached page)."""
        for p in pages:
            if p not in self._retained or self._refcnt[p] != 0:
                raise ValueError(f"reclaiming non-retained page {p}")
        for p in pages:
            self._retained.discard(p)
            self._free.append(p)
        self._update_gauge()

    def share(self, pages: list[int]):
        """Attach cached pages to ONE more owner (a prefix-cache hit):
        refcount-0 retained pages come back to life, live shared pages just
        gain a reference."""
        for p in pages:
            if not (0 < p < self.num_pages):
                raise ValueError(f"sharing bogus page {p}")
            if self._refcnt[p] == 0 and p not in self._retained:
                raise ValueError(f"sharing unallocated page {p}")
        for p in pages:
            self._retained.discard(p)
            self._refcnt[p] += 1
        self._update_gauge()

    def free(self, pages: list[int]):
        """Release one owner's claim on each page. Fails LOUDLY — before
        mutating anything — on a double-free (refcount already 0), a
        duplicate page id within the call, an out-of-pool id, or the
        reserved trash page 0: tolerating any of these would eventually
        hand the same page to two live sequences."""
        seen = set()
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("freeing reserved trash page 0")
            if not (0 < p < self.num_pages):
                raise ValueError(f"freeing bogus page {p}")
            if p in seen:
                raise ValueError(f"duplicate page {p} in one free() call")
            seen.add(p)
            if self._refcnt[p] <= 0:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._refcnt[p] -= 1
            if self._refcnt[p] == 0:
                if self.retain_hook is not None and self.retain_hook(p):
                    self._retained.add(p)
                else:
                    self._free.append(p)
        self._update_gauge()
