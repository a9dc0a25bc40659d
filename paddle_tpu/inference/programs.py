"""The engine's step programs, as pure module-level functions, and the
packed int32 uploads they read.

ONE calling convention: every program is

    program(params, cache, *small) -> (*lead, cache)

where ``cache`` is the `inference/cache.py::DeviceCache` (the one donated
argument, returned whole as the last result) and ``small`` is the
on-device token chain (never donated; every program returns the next one
among ``lead``) and one packed upload. For a family whose steps hand back
counts (``counts`` > 0: inference/family.py) the chain is the slots' tokens
followed by that many running totals, which every program passes through
its step function and puts back behind the tokens. `DecodeEngine` compiles them ahead of time
(``jax.jit(program, donate_argnums=(1,)).lower(...).compile()``); nothing
here needs an engine, so a program lowers from `jax.ShapeDtypeStruct`s
alone (tests/test_tpu_compile.py).

Each upload is laid out ONCE, by an `Upload` that the host packer writes
through and the traced program reads through. Whether a program samples
is decided when it is built: a sampling engine's uploads carry the
sampler's fields, and its cache carries the key chains.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["Upload", "step_upload", "prefill_upload", "decode_program",
           "verify_program", "prefill_program", "FLAG_ACTIVE", "FLAG_FRESH"]

FLAG_ACTIVE, FLAG_FRESH = 1, 2     # bits of a step upload's ``flags``


class Upload:
    """One packed int32 host->device transfer: named fields side by side
    along the last axis. A field is a name (one int: ``up[name]`` is its
    index) or ``(name, width)`` (``up[name]`` is its slice); ``shape`` is
    ``rows + (total width,)``. Both sides index ``packed[..., up[name]]``,
    so the layout exists in one place."""

    def __init__(self, rows: tuple, *fields):
        self._at, n = {}, 0
        for f in fields:
            name, width = (f, None) if isinstance(f, str) else f
            self._at[name] = n if width is None else slice(n, n + width)
            n += 1 if width is None else width
        self.shape = (*rows, n)

    def __getitem__(self, name):
        return self._at[name]

    def __contains__(self, name):
        return name in self._at

    def spec(self, **kw):
        return jax.ShapeDtypeStruct(self.shape, jnp.int32, **kw)


def step_upload(slots: int, pages_per_slot: int, sampling: bool,
                k: int = 0) -> Upload:
    """The batched steps' slot state, one row a slot: the host's token
    (read where ``flags`` says fresh; else the on-device chain's), length,
    flags, page-table row; a verify step (``k`` drafted tokens) adds the
    draft's length and tokens; a sampling engine the slot's temperature
    (float32 bits) and top-k."""
    return Upload(
        (slots,), "token", "length", "flags",
        *(("draft_len", ("drafts", k)) if k else ()),
        ("table", pages_per_slot),
        *(("temp", "top_k") if sampling else ()))


def prefill_upload(tokens: int, pages_per_slot: int, sampling: bool,
                   chunk: bool) -> Upload:
    """One prefill launch: ``tokens`` ids (a bucket, or a chunk), the
    prompt's true length (one-shot) or the chunk's start, valid count and
    whether it is the FINAL one (only that one's token counts: it alone
    enters the token chain and advances a key chain), the slot's page row
    and the slot itself (its entry of the token chain takes the sampled
    token; a family with state fills that slot's state); on a sampling
    engine the key-chain row to write (row ``slots`` = scratch, for
    slotless prefills), the request's seed key, temperature bits and
    top-k."""
    return Upload(
        (), ("ids", tokens),
        *(("start", "valid", "final") if chunk else ("length",)),
        ("row", pages_per_slot), "slot",
        *(("key_slot", ("seed", 2), "temp", "top_k") if sampling else ()))


def _f32(bits):
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _slot_tokens(up, slot_state, tokens):
    """(active mask, this step's input token a slot): the previous step's
    on-device output, overridden for slots the host admitted since."""
    flags = slot_state[:, up["flags"]]
    active = (flags & FLAG_ACTIVE) != 0
    fresh = (flags & FLAG_FRESH) != 0
    return active, jnp.where(fresh, slot_state[:, up["token"]], tokens)


def _split(chain, counts: int):
    """(the slots' tokens, the running counts behind them or None)."""
    return (chain[:-counts], chain[-counts:]) if counts else (chain, None)


def _join(tokens, tail):
    return tokens if tail is None else jnp.concatenate([tokens, tail])


def decode_program(steps, cfg, up: Upload, counts: int = 0):
    """``(params, cache, tokens, slot_state) -> (next tokens, cache)``:
    the family's `decode_step` on all slots and the next token a slot
    (argmax, or the fused sampler with each active slot's chain advanced
    once). Tokens and key chains stay on device step to step."""
    sampling = "temp" in up

    def program(params, cache, chain, slot_state):
        tokens, tail = _split(chain, counts)
        active, toks = _slot_tokens(up, slot_state, tokens)
        view = cache.step_view(slot_state[:, up["table"]],
                               slot_state[:, up["length"]])
        if counts:
            view["counts"] = tail
        logits, view = steps.decode_step(params, toks, view, active, cfg=cfg)
        cache = cache.after_step(view)
        tail = view["counts"] if counts else None
        if not sampling:
            nxt = jnp.argmax(logits, axis=-1).astype(toks.dtype)
            return _join(jnp.where(active, nxt, toks), tail), cache
        from paddle_tpu.kernels.sampling import fused_sample
        B, keys = tokens.shape[0], cache.keys
        nxt, new_keys = fused_sample(logits, keys[:B],
                                     _f32(slot_state[:, up["temp"]]),
                                     slot_state[:, up["top_k"]])
        nxt = jnp.where(active, nxt.astype(toks.dtype), toks)
        return _join(nxt, tail), cache.with_keys(keys.at[:B].set(
            jnp.where(active[:, None], new_keys, keys[:B])))

    return program


def verify_program(steps, cfg, up: Upload, counts: int = 0):
    """``(params, cache, tokens, slot_state) -> (emitted [B, k+1],
    n_emitted, next tokens, cache)``: the speculative k-token step. Draft
    contents and lengths ride the upload, never a shape; on a sampling
    engine `verify_step` advances each slot's chain by exactly its
    ``n_emitted`` splits. Running counts pass through as they came: a
    verify step adds none."""
    sampling = "temp" in up

    def program(params, cache, chain, slot_state):
        tokens, tail = _split(chain, counts)
        active, tok0 = _slot_tokens(up, slot_state, tokens)
        draft_len = slot_state[:, up["draft_len"]]
        tok_seq = jnp.concatenate(
            [tok0[:, None], slot_state[:, up["drafts"]]], axis=1)
        view = cache.step_view(slot_state[:, up["table"]],
                               slot_state[:, up["length"]])
        if sampling:
            B, keys = tokens.shape[0], cache.keys
            emitted, n_emitted, view, new_keys = steps.verify_step(
                params, tok_seq, draft_len, view, active, cfg=cfg,
                sample_state=(keys[:B], _f32(slot_state[:, up["temp"]]),
                              slot_state[:, up["top_k"]]))
            cache = cache.with_keys(keys.at[:B].set(new_keys))
        else:
            emitted, n_emitted, view = steps.verify_step(
                params, tok_seq, draft_len, view, active, cfg=cfg)
        nxt = jnp.take_along_axis(
            emitted, jnp.maximum(n_emitted - 1, 0)[:, None], axis=1)[:, 0]
        return (emitted, n_emitted,
                _join(jnp.where(active, nxt, tok0), tail),
                cache.after_step(view))

    return program


def prefill_program(steps, cfg, up: Upload, counts: int = 0):
    """``(params, cache, tokens, packed) -> (tokens, cache)``: fill one
    slot's pages from ``packed``'s ids, and set that slot's entry of the
    token chain to the first token, so that the next decode step reads it
    where it reads every other token and no host waits for it. A one-shot
    upload runs the family's `prefill_step` over a whole bucketed prompt;
    a chunk upload (it has ``start``) runs `prefill_chunk_step` over a
    window that starts at an absolute position: decode-priority chunks and
    prefix-cache tails alike, and a chunk that is not the FINAL one
    returns the chain as it came. The token is the argmax, or on a
    sampling engine the fused sampler's from the request's seed key, the
    advanced chain landing in the cache at ``key_slot`` with no
    readback."""
    chunk, sampling = "start" in up, "seed" in up

    def program(params, cache, chain, packed):
        tokens, tail = _split(chain, counts)
        ids = packed[up["ids"]]
        where = (packed[up["start"]], packed[up["valid"]]) if chunk \
            else (packed[up["length"]],)
        slot = packed[up["slot"]]
        kw = cache.extras()
        if cache.state:
            kw["slot"] = slot
        if counts:
            kw["counts"] = tail
        step = steps.prefill_chunk_step if chunk else steps.prefill_step
        logits, *pools = step(params, ids, *where, packed[up["row"]],
                              cache.k, cache.v, cfg=cfg, **kw)
        if counts:
            *pools, tail = pools
        cache = cache.after_prefill(*pools)
        final = packed[up["final"]] != 0 if chunk else True
        if sampling:
            from paddle_tpu.kernels.sampling import sample_one
            tok, new_key = sample_one(
                logits, jax.lax.bitcast_convert_type(packed[up["seed"]],
                                                     jnp.uint32),
                _f32(packed[up["temp"]]), packed[up["top_k"]])
            row, keys = packed[up["key_slot"]], cache.keys
            cache = cache.with_keys(keys.at[row].set(
                jnp.where(final, new_key, keys[row])))
        else:
            tok = jnp.argmax(logits, axis=-1)
        return _join(tokens.at[slot].set(
            jnp.where(final, tok.astype(tokens.dtype), tokens[slot])),
            tail), cache

    return program
