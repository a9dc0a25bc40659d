"""Drop-free routed experts for the serving engine's fixed-shape programs:
one chip's SHARE of an expert-parallel layer.

A chip of an expert-parallel deployment holds the routed experts ``[lo,
hi)`` of a layer and a copy of the router. :func:`routed_experts` is that
layer as this chip runs it: it routes every token over ALL the router's
experts with the published gates (float32 logits, the ``top_k`` largest, a
softmax over those ``top_k`` logits and no renormalisation over the held
ones), and adds up the terms of the experts it HOLDS. What the absent
experts would have added is left out (their chips add it, through an
exchange this file does not have), and a token none of whose experts is
held gets zero. No capacity, no padding to one, no dropped token: every
assignment to a held expert is computed, which `incubate/moe.py`'s
capacity-padded dispatch does not promise and a served model needs.

An expert is gated: ``[u | v] = x W1_e`` (the FIRST half is activated),
``(silu(u) * v) W2_e``; with ``limit`` (a published ``swiglu_limit``) the
halves are clamped first, ``u`` from above and ``v`` both ways. Weights arrive as the held experts' stacks, ``w1
[hi - lo, d, 2 f]`` and ``w2 [hi - lo, f, d]``.

Two arms (registered as ``moe_experts``; counted per program build in
``kernel.dispatch.moe_experts.<arm>``). ``dense``: every token through every held
expert, the gate (zero where the expert was not chosen) folded in before the
second product, whose contraction runs over experts and expert width at
once. It reads each held expert once and computes ``held / top_k`` times the
needed FLOPs: right where the step is bound by reading the experts anyway (a
decode step of 64 tokens hits every one of 36 held experts). On the chip at
Granite-4.0-H's sizes it also beat a sorted `jax.lax.ragged_dot` product at
every token count tried, 64 to 1,024 (PERF.md section 6, PR 31), because the
ragged product copies a layer's experts out of the stack first.

``grouped``: the assignments that landed on a held expert, sorted by
expert into groups of unequal size (an expert with no row is a group of
none; no row is dropped, the buffer holds every assignment a call can
make), and only those rows multiplied: `jax.lax.ragged_dot` twice, the gate
between. A token that is not ``valid`` (a dead slot of a decode step, a
chunk's padding) has no row, gets zero and reads no expert. It makes the
needed FLOPs and reads the experts that were HIT; it
wants a layer's experts as arrays of their own (a family hands them as a
tuple of leaves, one a layer: a slice of a stack is copied first, which is
what lost PR 31's trial). The registry takes it first where the dense arm
would make ``experts / top_k`` >= `GROUPED_FROM` times the needed FLOPs (32
at 256 experts and 8 a token) AND a call has at most `GROUPED_UP_TO` tokens:
on the chip, a layer of 32 held experts of 256 took 1.42 ms grouped against
2.15 dense at 24 tokens (a decode step hits a third of the experts), and
5.64 against 4.59 at 512 (a chunk hits them all, and the sorted rows' gather
and the ragged product's own overhead outweigh the FLOPs saved); Granite's
36 of 72 at 10 a token read 1.51 against 1.06 at 64 tokens and 3.35 against
2.13 at 512, so it stays dense (my chip run, PR 40; PERF.md section 6).
Both thresholds lie BETWEEN measured points: ``experts / top_k`` was read
at 7.2 and 32 and nowhere between, tokens a call at 24, 64 (Granite's ratio
alone) and 512. A configuration that falls between them wants a reading of
its own before it trusts the order here.
One such reading (my chip run, PR 42): 16 held of 256 at 8 a token and a
width of 2,048 over a hidden size of 7,168, a layer, ``grouped`` against
``dense``: 1.15 / 1.95 ms at 24 tokens, 4.01 / 1.95 at 64, 2.99 / 1.97 at
96, 4.65 / 1.97 at 128 (a decode step of that family: ``dense`` is taken,
and is right), 5.33 / 4.24 at 512. ``dense`` reads its 16 experts once
(1.41 GB, 1.72 ms at the memory's rate) whatever the tokens up to 128;
``grouped`` sorts 8 rows a token and its ragged products walk every row
of the buffer, so it loses from 64 tokens on: for THIS share the cut lies
under 64, between 24 and 64, and no cell runs there.
A second (my chip run, PR 50): 40 held of 320 at 8 a token (``experts /
top_k`` 40) and a width of 1,280 over a hidden size of 4,096, a layer
alone, ``grouped`` against ``dense``: 1.17 / 1.76 ms at 24 tokens, 1.60 /
1.77 at 48 (a decode step of that family: ``grouped`` is taken, and is
right by a tenth), 3.52 / 1.76 at 64, 2.73 / 1.78 at 96, 4.08 / 1.78 at
128, 4.29 / 2.00 at 256, 4.60 / 3.80 at 512 (a chunk: ``dense`` is taken,
and is right; it is bound by its FLOPs from some 240 tokens on, 0.64 TFLOP
at 512), 5.46 / 7.32 at 1,024. In that family's cell, one run each, the
rule's choice read 952-972 tokens/s at 47.1 ms a token, ``grouped`` in
step and chunk 905 at 50.8, ``dense`` in both 940 at 48.2. So the cut in
tokens lies between 48 and 64 for both shares read there, and
`GROUPED_UP_TO` stands at 48, the largest call at which ``grouped`` has
won (it stood at 64, where it has lost twice; no cell makes a call of 49
to 64 tokens for a router that wasteful, so no cell's arm moved).

A router scores with a softmax over the chosen logits (``scoring``
``"softmax"``) or with a sigmoid (``"sigmoid"``: scores ``sigmoid(logits)``,
the ``top_k`` largest of ``score + bias`` chosen, the chosen SCORES divided
by their sum and times ``scale``: DeepSeek-V3's ``noaux_tc`` with one
group).

With ``counts`` (int32 ``[hi - lo + 1]``) the call also returns the vector
with this call's routing added: one entry a held expert (assignments of
``valid`` tokens that landed on it) and, last, all assignments of valid
tokens (``top_k`` each). The step programs carry that vector beside their
tokens (inference/programs.py), so it costs no readback of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry

__all__ = ["routed_experts", "route"]

GROUPED_FROM = 16      # ``experts / top_k`` from which (read: 7.2, 32, 40),
GROUPED_UP_TO = 48     # and tokens a call up to which (won at 24, 32 and 48;
#                        lost at 64 with 16 and with 40 held: docstring),
#                        ``grouped`` is first


def _candidates(ctx):
    wasteful = ctx.get("experts", 0) >= GROUPED_FROM * ctx.get("top_k", 1)
    few = ctx.get("tokens", GROUPED_UP_TO + 1) <= GROUPED_UP_TO
    return ["grouped", "dense"] if wasteful and few else ["dense", "grouped"]


registry.register_op("moe_experts", impls=("dense", "grouped"),
                     candidates=_candidates)

_HI = jax.lax.Precision.HIGHEST


def route(x, w_router, top_k, scoring="softmax", bias=None, scale=1.0):
    """(expert ids [T, top_k] int32, gates [T, top_k] f32): float32 logits
    over every expert of the router. ``"softmax"``: the ``top_k`` largest, a
    softmax over those logits alone. ``"sigmoid"``: the ``top_k`` largest of
    ``sigmoid(logits) + bias``, the chosen sigmoids over their sum, times
    ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=_HI)
    if scoring == "softmax":
        top, idx = jax.lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    if scoring != "sigmoid":
        raise ValueError(f"scoring {scoring!r}: 'softmax' or 'sigmoid'")
    sc = jax.nn.sigmoid(logits)
    by = sc if bias is None else sc + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(by, top_k)
    top = jnp.take_along_axis(sc, idx, axis=-1)
    return idx.astype(jnp.int32), \
        top / jnp.sum(top, axis=-1, keepdims=True) * scale


def _gated(h, gate, limit=None):
    """[u | v] in f32 -> silu(u) * v, times the assignment's gate; with
    ``limit`` the activated half is clamped from above and the linear half
    both ways first."""
    u, v = jnp.split(h, 2, axis=-1)
    if limit is not None:
        u, v = jnp.minimum(u, limit), jnp.clip(v, -limit, limit)
    return u * jax.nn.sigmoid(u) * v * gate


def _dense(x, w1, w2, idx, gates, lo, limit=None):
    held = w1.shape[0]
    chosen = idx[:, :, None] == lo + jnp.arange(held)          # [T, K, E]
    gate = jnp.sum(jnp.where(chosen, gates[:, :, None], 0.0), axis=1)
    h = jnp.einsum("td,edf->tef", x, w1,
                   preferred_element_type=jnp.float32)         # [T, E, 2f]
    act = _gated(h, gate[:, :, None], limit).astype(x.dtype)
    return jnp.einsum("tef,efd->td", act, w2,
                      preferred_element_type=jnp.float32)


def _grouped(x, w1, w2, idx, gates, lo, valid=None, limit=None):
    t, k = idx.shape
    held = w1.shape[0]
    e = idx.reshape(-1) - lo
    on = (e >= 0) & (e < held)
    if valid is not None:         # a dead slot's row reads no expert
        on &= jnp.repeat(valid, k)
    key = jnp.where(on, e, held)                  # the others' rows last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    gate = jnp.where(on, gates.reshape(-1), 0.0)[order]
    h = jax.lax.ragged_dot(x[order // k], w1, sizes,
                           preferred_element_type=jnp.float32)
    act = _gated(h, gate[:, None], limit).astype(x.dtype)
    y = jax.lax.ragged_dot(act, w2, sizes,
                           preferred_element_type=jnp.float32)
    # a row past the last group is no expert's: whatever the product left
    # there is not added
    y = jnp.where((gate != 0.0)[:, None], y, 0.0).astype(x.dtype)
    back = jnp.zeros(t * k, jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    return jnp.sum(y[back].reshape(t, k, -1), axis=1, dtype=jnp.float32)


def routed_experts(x, w_router, w1, w2, *, top_k, held, counts=None,
                   valid=None, scoring="softmax", bias=None, scale=1.0,
                   limit=None, impl=None):
    """This chip's part of a routed-expert layer for tokens ``x`` [T, d].

    w_router : [d, E] over ALL experts; w1 : [hi - lo, d, 2 f]; w2 :
    [hi - lo, f, d] of the held experts ``held`` = (lo, hi); top_k : experts
    a token; valid : [T] bool, the tokens ``counts`` counts (None: all; what
    the others get is unspecified: zero from ``grouped``);
    scoring, bias [E], scale : the router's gates (`route`); limit : the
    gated MLP's clamp (``swiglu_limit``: ``min(u, limit)``, ``clip(v, -limit,
    limit)``; None: none); impl : an arm
    by name (None: the registry's). Returns ``y`` [T, d] in ``x``'s type, or
    ``(y, counts)`` when ``counts`` came.
    """
    lo, hi = held
    if w1.shape[0] != hi - lo or w2.shape[0] != hi - lo:
        raise ValueError(f"held experts {held} but {w1.shape[0]} stacked")
    if not 0 <= lo < hi <= w_router.shape[1]:
        raise ValueError(f"held experts {held} of a router over "
                         f"{w_router.shape[1]}")
    arm = registry.dispatch("moe_experts", forced=impl, ctx=dict(
        experts=w_router.shape[1], top_k=top_k, held=hi - lo,
        tokens=x.shape[0]))
    idx, gates = route(x, w_router, top_k, scoring, bias, scale)
    y = _dense(x, w1, w2, idx, gates, lo, limit) if arm == "dense" \
        else _grouped(x, w1, w2, idx, gates, lo, valid, limit)
    y = y.astype(x.dtype)
    if counts is None:
        return y
    ok = jnp.ones(x.shape[0], bool) if valid is None else valid
    hit = (idx[:, :, None] == lo + jnp.arange(hi - lo)) & ok[:, None, None]
    add = jnp.concatenate([
        jnp.sum(hit, axis=(0, 1), dtype=jnp.int32),
        (jnp.sum(ok, dtype=jnp.int32) * top_k)[None]])
    return y, counts + add.astype(counts.dtype)
