"""Fused on-device sampling (registry op ``fused_sampling``) — the kernel
that kills the last per-step host<->device logits round-trip (ROADMAP
item 4; the Gemma-on-TPU serving study, arxiv 2605.25645, identifies it
as the tok/s ceiling once attention is fast).

Before this module the serving engine was GREEDY-ONLY: sampled generation
would have required reading each step's ``[B, V]`` f32 logits back to the
host, sampling there, and uploading the chosen tokens — one d2h + h2d
round trip per decode step, serializing the de-synchronized loop PR 3
built. This module moves the whole sampler into the fixed-shape step
programs:

- **temperature / top-k mask / categorical draw** run on the logits where
  they already live; per-slot (temperature, top_k) ride the packed int32
  state upload (temperature as bitcast f32), so one compiled program
  serves every request's sampling params with ZERO recompiles;
- **per-slot PRNG key chains** live on device, exactly the
  `models/gpt.py::verify_step` keys discipline: one ``jax.random.split``
  per SAMPLED token, no split for greedy slots — bit-identical to
  `fast_generate`'s host sampler for the same seed (parity-tested);
- **the spec-decode accept test** (:func:`accept_drafts`) is the ONE
  implementation of the longest-matching-prefix acceptance both the
  greedy and sampled verify paths use;
- the engine's decode/verify steps emit ACCEPTED TOKENS only —
  ``engine.d2h_transfers`` stays token-harvest-only and
  ``engine.logits_readback`` pins to 0 (docs/OBSERVABILITY.md).

The math mirrors `models/gpt.py::_make_sampler` exactly for any fixed
(temperature, top_k): temperature scales BEFORE the top-k mask (the
kth-logit cutoff applies on the tempered distribution), the k-th-largest
cutoff comes from a full descending sort (equal to ``lax.top_k``'s k-th
value, but dynamic in k so it can ride the state upload), and greedy
(t == 1, k == 0) is a pure argmax of the UNSCALED logits with no key
advance. Selection goes through `kernels/registry.py` — "xla" is the one
impl today; a Mosaic top-k candidate lands as a registry drop-in, not a
new dispatch branch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry

__all__ = ["sample_one", "fused_sample", "accept_drafts"]

registry.register_op("fused_sampling", impls=("xla",))

NEG_INF = -1e30


def sample_one(logits, key, temperature, top_k):
    """One slot's sampler: ``[V]`` f32 logits + ``[2]`` uint32 key +
    scalar f32 temperature + scalar int32 top_k ->
    ``(token int32, new key)``.

    Bit-identical to `_make_sampler` for the matching static params: the
    categorical draw happens on a ``[1, V]`` row (the B=1 host shape and
    the per-slot discipline `verify_step`'s sampled path established), and
    the key chain advances by exactly one split per SAMPLED token — a
    greedy slot's chain never moves.
    """
    v = logits.shape[-1]
    sampled = (top_k > 0) | (temperature != 1.0)
    lt = logits / temperature          # t==1 divides by 1.0: bit-exact
    desc = -jnp.sort(-lt)              # descending; desc[k-1] == the
    kth = desc[jnp.clip(top_k - 1, 0, v - 1)]   # lax.top_k kth value
    masked = jnp.where((top_k > 0) & (lt < kth), NEG_INF, lt)
    next_key, sub = jax.random.split(key)
    cat = jax.random.categorical(sub, masked[None], axis=-1)[0]
    tok = jnp.where(sampled, cat, jnp.argmax(logits))
    new_key = jnp.where(sampled, next_key, key)
    return tok.astype(jnp.int32), new_key


def _xla_fused_sample(logits, keys, temperatures, top_ks):
    return jax.vmap(sample_one)(logits, keys, temperatures, top_ks)


_IMPLS = {"xla": _xla_fused_sample}


def fused_sample(logits, keys, temperatures, top_ks):
    """Batched fused sampler: ``[B, V]`` f32 logits + ``[B, 2]`` uint32
    keys + ``[B]`` f32 temperatures + ``[B]`` int32 top-ks ->
    ``(tokens [B] int32, new_keys [B, 2])``. Registry-dispatched
    (``kernel.dispatch.fused_sampling.*`` counts program builds — the
    selection runs at trace time like every kernel op)."""
    impl = registry.dispatch("fused_sampling")
    return _IMPLS[impl](logits, keys, temperatures, top_ks)


def accept_drafts(drafts, out, draft_len, slot_mask):
    """The spec-decode accept test — the ONE implementation
    (`models/gpt.py::verify_step`, both greedy and sampled arms).

    drafts    : [B, K] int32 drafted continuations (columns past
                ``draft_len`` are padding)
    out       : [B, K+1] int32 — the model's own emission at every
                position (column i conditions on drafts 1..i)
    draft_len : [B] int32 true drafted tokens per slot
    slot_mask : [B] bool — inactive slots emit 0
    returns   : n_emitted [B] int32 in 0..K+1 — the longest draft prefix
                matching the model's own choices, plus ONE corrected
                token (contiguous-prefix acceptance: the first mismatch
                rejects the rest). Acceptance is EXACT: emitted tokens
                are precisely what the non-speculative loop would
                produce.
    """
    b, k = drafts.shape
    if k > 0:
        match = (drafts == out[:, :-1]) \
            & (jnp.arange(k)[None] < draft_len[:, None])
        n_acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    else:
        n_acc = jnp.zeros(b, jnp.int32)
    return jnp.where(slot_mask, n_acc + 1, 0).astype(jnp.int32)
