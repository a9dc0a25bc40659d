"""The engine's model seam: what `DecodeEngine` takes from a model family.

`DecodeEngine` is a scheduler and a cache manager; which model it steps is
not its business. A model object hands it one :class:`ModelFamily` through
``model.engine_family()``: the pure step functions the AOT programs trace,
where the parameters are, and the kinds and shapes of state a sequence
keeps. There is no flag and no `EngineConfig` field that picks a model —
the model object decides. `models/gpt.py`, `models/phi4flash.py`,
`models/granitemoehybrid.py`, `models/brumby.py`, `models/dots3note.py`,
`models/gigachat35.py`, `models/kimi_k2.py` and `models/solar_open2.py`
each supply one; the GPT family describes exactly what the engine used to
import, so its programs trace as before.

The step functions' contracts (``steps`` is any namespace that has them):

- ``decode_step(params, ids, cache, slot_mask, *, cfg)`` -> ``(logits
  [B, V] f32, cache)``; ``cache`` holds ``k_pages``, ``v_pages``,
  ``page_table``, ``lengths`` (a slot's tokens so far: its new token's
  position), ``k_scale`` / ``v_scale`` on an int8 pool, and ``state`` (a
  tuple, in ``state(...)``'s order) for a family that keeps any;
- ``prefill_step(params, ids, length, row, k_pages, v_pages, *, cfg, ...)``
  and ``prefill_chunk_step(params, ids, start, valid, row, k_pages,
  v_pages, *, cfg, ...)`` -> ``(logits [V] f32, k_pages, v_pages, ...)``;
  a family with state also takes ``state=`` and ``slot=`` and returns the
  state arrays after the pools;

A family whose ``kv_layers`` is 0 keeps nothing in a page pool (all it
remembers of a sequence is fixed-size state): the engine then allocates no
pool and no page, ``k_pages`` / ``v_pages`` are empty (``[0, 1, page,
width]``) and ``page_table`` / ``row`` have no width; its step functions
pass the pools through untouched, its admission is bounded by slots alone,
and a sequence's length costs it no memory (docs/SERVING.md "A family
without a page pool");
- ``verify_step`` (optional): the speculative k-token step. A family
  without one cannot speculate, and the engine refuses ``speculate_k``.

State beside the page pool is described by ``state(slots, page_size,
dtype)`` -> ``((name, kind, shape, dtype), ...)`` with ``kind`` one of
``"window"`` (K and V of a bounded window: constant in sequence length) or
``"recurrent"`` (fixed-size state, read as zero by the chunk that starts a
sequence and carried from chunk to chunk). The arrays live in the
engine's `DeviceCache` (inference/cache.py: allocated there, donated into
and returned from every step program with the pools), and — because pages
alone then cannot restore a sequence — the engine refuses prefix
reuse, speculation, hand-off, migration and tier spill by typed error
(`errors.RecurrentStateUnsupported`; docs/SERVING.md "The model seam").

A page row need not be K and V. A family whose pooled layers keep
something else of a token (a latent row that every head reads, a key of a
selector beside it) says so with ``page_rows``: ``((name, values a token),
...)``, one or two parts. The first part is the row of ``k_pages``, the
second (if any) the row of ``v_pages``, each ``[kv_layers, P, page,
values]`` in the pool's type; there is no twin: with one part ``v_pages``
is empty (``[0, 1, page, 0]``). ``kv_heads`` and ``head_dim`` then describe
nothing and are left at 1 and the first part's width. The pool's sizing,
``engine.kv_bytes_per_token`` and ``engine.cache_bytes.paged`` count what
the parts hold, and each part's bytes are the gauge
``engine.cache_bytes.paged.<name>`` (inference/cache.py). The fields
compose: a family may declare ``page_rows`` of ONE part (a latent row, no
second pool), ``state`` of several ``recurrent`` arrays a layer and
``step_counts`` together (`models/gigachat35.py`); a prefill step then
returns its logits, the two pools (the second the engine's empty one,
passed through), the state arrays in ``state(...)``'s order, and the counts
last, which is the order `cache.py::after_prefill` and
`programs.py::prefill_program` take them in. The same holds WITHOUT
``page_rows``: twin K and V pools, ``state`` of several ``recurrent``
arrays a layer and ``step_counts`` (`models/solar_open2.py`, the eighth
family: K/V pages beside a delta-rule state). A family with ``page_rows``
and NO ``state`` is all pages (the seventh family): the engine's prefix
store serves it, and what would ship its pages through a wire blob (every
blob is laid out as twin K and V pools: hand-off, migration, the spill
tiers) refuses by `errors.PageLayoutUnsupported`.

A step may hand back COUNTS with its tokens. A family with ``step_counts``
= n > 0 (sparse experts: which held expert took how many tokens) has its
token chain lengthened by n int32 entries: every program gives the tail to
the step function (``cache["counts"]`` of `decode_step`, ``counts=`` of the
prefill steps), which returns it with this step's counts ADDED
(``cache["counts"]``; after the state arrays), and the program puts it back
behind the tokens. So the tail is a running total that reaches the host on
the one readback a step's tokens already make; `DecodeEngine` hands what it
grew by since the last readback to ``on_counts`` (a numpy int64 vector),
where the family turns it into counters. A family without counts pays
nothing: its chain and its programs are as they were.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

__all__ = ["ModelFamily", "family_of"]


@dataclass(frozen=True)
class ModelFamily:
    name: str
    steps: Any                      # namespace of the step functions
    params: Callable[[Any], dict]   # model -> {leaf name: device array, or
    #                                 a tuple of them, one a layer}
    table_key: str                  # the leaf whose dtype is the served one
    kv_layers: int                  # layers that own rows of the page pool
    #                                 (0: no pool at all)
    kv_heads: int
    head_dim: int
    max_positions: int              # longest sequence the model can see
    state: Callable | None = None   # (slots, page, dtype) -> specs, or None
    window_tokens: int = 0          # window of the ``window`` state, if any
    quantize: Callable | None = None  # (params, weight_dtype) -> params
    step_counts: int = 0            # int32 entries a step adds to (above)
    page_rows: tuple = ()           # ((name, values), ...): a page row that
    #                                 is not K and V heads (above); () = K, V
    on_counts: Callable | None = None  # (grown: np.ndarray) -> None


def family_of(model) -> ModelFamily:
    """The family ``model`` declares; a model that declares none cannot be
    served by `DecodeEngine`."""
    get = getattr(model, "engine_family", None)
    if get is None:
        raise TypeError(
            f"{type(model).__name__} has no engine_family(): DecodeEngine "
            "serves models that supply their step functions and state "
            "description (inference/family.py)")
    return get()
