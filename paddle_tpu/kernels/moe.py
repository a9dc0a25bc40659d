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
halves are clamped first, ``u`` from above and ``v`` both ways. Weights arrive
as the held experts' stacks, ``w1 [hi - lo, d, 2 f]`` and ``w2 [hi - lo, f,
d]``.

Three arms (registered as ``moe_experts``; counted per program build in
``kernel.dispatch.moe_experts.<arm>``). ``dense``: every token through every
held expert, the gate (zero where the expert was not chosen) folded in before
the second product, whose contraction runs over experts and expert width at
once. It reads each held expert once and computes ``experts / top_k`` times
the needed FLOPs: right where a call hits every held expert anyway and its
FLOPs hide under the read, which on a v5e (197 TFLOP/s over 819 GB/s) is up
to some 240 tokens a call. XLA fuses the slice of a stack of layers into it,
so it is also the one arm that reads a layer's experts out of a stack without
copying them first.

``grouped``: the assignments that landed on a held expert, sorted by expert
into groups of unequal size (`_sorted_rows`: an expert with no row is a group
of none; no row is dropped, the buffer holds every assignment a call can
make), and only those rows multiplied: `jax.lax.ragged_dot` twice, the gate
between. A token that is not ``valid`` (a dead slot of a decode step, a
chunk's padding) has no row, gets zero and reads no expert. It is the arm of
a CPU, of a mesh and of widths that are no whole lane tiles, and the oracle of
the third arm's tests; on one TPU it is first nowhere, because the chip's
ragged product walks every row tile of the buffer, ran at half of what
reading the hit experts takes in a decode step and lost to ``dense`` in every
chunk read (PRs 31-50), and its custom calls carry no scope into a trace.

``pallas`` (kernels/pallas/grouped_experts.py): the same plan, the two
products as the repo's own Mosaic kernels. Only the (row tile, expert) pairs
that share a row are visited, an unhit expert is not read, a hit one is read
once, the activation and the gate are applied on the float32 result in VMEM:
the roundings of ``grouped``, bit for bit on the chip at every size read. It
wants a layer's experts as arrays of their own (a family hands them as leaves,
one a layer; a slice of a stack would be copied first).

The rule (`_candidates`, from the call's shapes alone): on one TPU with
widths in whole lane tiles and a router of `WASTEFUL_FROM` experts a chosen
one or more, ``pallas`` first where ``dense`` would be bound by its FLOPs
(`FLOP_BOUND_FROM` tokens a call) or where a call is expected to hit at most
`HIT_UP_TO` of the held experts, ``1 - (1 - top_k / experts) ** tokens``;
``dense`` first in between and for a router that wastes little. The readings
behind it, ms a layer alone, ``pallas`` / ``dense`` / ``grouped`` (my chip
run, PR 51; held of experts at 8 a token, [hidden, width]):

- 40 of 320, [4096, 1280]: 48 tokens (70% hit) 1.56 / 1.79 / 2.06; 512
  2.24 / 3.72 / 4.69 (reading the 40 once takes 1.54);
- 32 of 256, [5120, 1536]: 24 tokens (53%) 1.14 / 2.09 / 1.27; 512 2.62 /
  4.49 / 5.39 (1.85);
- 12 of 384, [7168, 2048]: 32 tokens (49%) 0.95 / 1.51 / 1.37; 512 2.04 /
  3.48 / 4.22 (1.29);
- 16 of 256, [7168, 2048]: 128 tokens (98%) 2.25 / 1.99 / 4.65, so ``dense``
  keeps that step; 512 2.64 / 4.23 / 5.33 (1.72);
- 36 of 72 at 10 a token, [4096, 768], the experts as leaves of their own:
  64 tokens 1.15 / 0.99 / 1.45; 512 1.77 / 2.02 / 3.15. That family hands a
  layer's experts as slices of a stack (0.68 GB copied a call before any arm
  but ``dense`` starts), so `WASTEFUL_FROM` keeps it on ``dense`` at both
  sizes until the kernel takes a layer index into the stack (ROADMAP.md
  Speed 1); between 7.2 and 32 experts a chosen one nothing was read.

The cut in the hit share lies between 0.70 (won) and 0.98 (lost), the cut in
tokens where the FLOPs of ``dense`` pass its read. Between the cuts the two
shares read there disagree: 40 of 320 read 1.55 / 1.80 (``pallas`` /
``dense``) at 64 tokens and 2.02 / 2.38 at 256, where 16 of 256 read 2.25 /
1.99 at 128; the one call a cell makes there is the second, so ``dense``
stands, and a configuration that falls there wants a reading of its own
before it trusts the order here. Past them ``pallas`` pays for a larger
call: 40 of 320 read 2.61 / 7.32 at 1,024 tokens.

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

WASTEFUL_FROM = 16      # ``experts / top_k`` from which ``dense`` yields
#                         (read: 7.2 over a stack of layers, then 32 to 48)
FLOP_BOUND_FROM = 240   # tokens a call from which ``dense`` is bound by its
#                         FLOPs: a v5e's 197 TFLOP/s over its 819 GB/s
HIT_UP_TO = 0.8         # expected share of the held experts a call hits up
#                         to which reading the hit ones alone pays (0.70
#                         won, 0.98 lost)
_LANES = 128


def _candidates(ctx):
    experts, top_k = ctx.get("experts", 0), ctx.get("top_k", 1)
    tokens = ctx.get("tokens", 0)
    wasteful = experts >= WASTEFUL_FROM * top_k
    few_hit = wasteful and \
        1 - (1 - top_k / experts) ** tokens <= HIT_UP_TO
    if not (registry.on_one_tpu() and all(
            ctx.get(w, 1) % _LANES == 0 for w in ("hidden", "width"))):
        return ["grouped", "dense"] if few_hit else ["dense", "grouped"]
    if few_hit or (wasteful and tokens >= FLOP_BOUND_FROM):
        return ["pallas", "dense", "grouped"]
    return ["dense", "pallas", "grouped"]


registry.register_op("moe_experts", impls=("dense", "grouped", "pallas"),
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


def _gated(u, v, gate, limit=None):
    """The halves ``[u | v]`` of a first product in f32 -> silu(u) * v,
    times the assignment's gate; with ``limit`` the activated half is
    clamped from above and the linear half both ways first. (Also the body
    of the ``pallas`` arm's first kernel, on a block in VMEM.)"""
    if limit is not None:
        u, v = jnp.minimum(u, limit), jnp.clip(v, -limit, limit)
    return u * jax.nn.sigmoid(u) * v * gate


def _dense(x, w1, w2, idx, gates, lo, limit=None):
    held = w1.shape[0]
    chosen = idx[:, :, None] == lo + jnp.arange(held)          # [T, K, E]
    gate = jnp.sum(jnp.where(chosen, gates[:, :, None], 0.0), axis=1)
    h = jnp.einsum("td,edf->tef", x, w1,
                   preferred_element_type=jnp.float32)         # [T, E, 2f]
    act = _gated(*jnp.split(h, 2, axis=-1), gate[:, :, None],
                 limit).astype(x.dtype)
    return jnp.einsum("tef,efd->td", act, w2,
                      preferred_element_type=jnp.float32)


def _sorted_rows(idx, gates, lo, held, valid):
    """The assignments that landed on a held expert, sorted by expert:
    (order [T k] the assignments' places in turn, the rows of no group last;
    sizes [held] int32 rows an expert; gate [T k] f32 in that order, zero
    for a row in no group)."""
    k = idx.shape[1]
    e = idx.reshape(-1) - lo
    on = (e >= 0) & (e < held)
    if valid is not None:         # a dead slot's row reads no expert
        on &= jnp.repeat(valid, k)
    key = jnp.where(on, e, held)                  # the others' rows last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    return order, sizes, jnp.where(on, gates.reshape(-1), 0.0)[order]


def _summed(y, gate, order, t, k):
    """The sorted rows' results ``y`` back at their tokens, the ``top_k``
    of a token added in float32. A row past the last group is no expert's:
    whatever the product left there is not added."""
    back = jnp.zeros(t * k, jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    y = jnp.where((gate != 0.0)[back, None], y[back], 0.0)
    return jnp.sum(y.reshape(t, k, -1), axis=1, dtype=jnp.float32)


def _grouped(x, w1, w2, idx, gates, lo, valid=None, limit=None):
    t, k = idx.shape
    order, sizes, gate = _sorted_rows(idx, gates, lo, w1.shape[0], valid)
    h = jax.lax.ragged_dot(x[order // k], w1, sizes,
                           preferred_element_type=jnp.float32)
    act = _gated(*jnp.split(h, 2, axis=-1), gate[:, None],
                 limit).astype(x.dtype)
    y = jax.lax.ragged_dot(act, w2, sizes,
                           preferred_element_type=jnp.float32)
    return _summed(y.astype(x.dtype), gate, order, t, k)


def _pallas(x, w1, w2, idx, gates, lo, valid=None, limit=None):
    from paddle_tpu.kernels.pallas import _compat, grouped_experts as ge
    t, k = idx.shape
    order, sizes, gate = _sorted_rows(idx, gates, lo, w1.shape[0], valid)
    tiles = ge.plan(t * k, x.shape[1], w2.shape[1], x.dtype.itemsize)
    pad = -(t * k) % tiles.tm                      # whole row tiles
    y = ge.grouped_experts(
        x[jnp.pad(order, (0, pad)) // k], jnp.pad(gate, (0, pad)), w1, w2,
        sizes, plan=tiles, limit=limit,
        interpret=_compat.default_interpret())
    return _summed(y, gate, order, t, k)


def routed_experts(x, w_router, w1, w2, *, top_k, held, counts=None,
                   valid=None, scoring="softmax", bias=None, scale=1.0,
                   limit=None, impl=None):
    """This chip's part of a routed-expert layer for tokens ``x`` [T, d].

    w_router : [d, E] over ALL experts; w1 : [hi - lo, d, 2 f]; w2 :
    [hi - lo, f, d] of the held experts ``held`` = (lo, hi); top_k : experts
    a token; valid : [T] bool, the tokens ``counts`` counts (None: all; what
    the others get is unspecified: zero from ``grouped`` and ``pallas``);
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
        tokens=x.shape[0], hidden=x.shape[1], width=w2.shape[1]))
    idx, gates = route(x, w_router, top_k, scoring, bias, scale)
    if arm == "dense":
        y = _dense(x, w1, w2, idx, gates, lo, limit)
    else:
        y = (_pallas if arm == "pallas" else _grouped)(
            x, w1, w2, idx, gates, lo, valid, limit)
    y = y.astype(x.dtype)
    if counts is None:
        return y
    ok = jnp.ones(x.shape[0], bool) if valid is None else valid
    hit = (idx[:, :, None] == lo + jnp.arange(hi - lo)) & ok[:, None, None]
    add = jnp.concatenate([
        jnp.sum(hit, axis=(0, 1), dtype=jnp.int32),
        (jnp.sum(ok, dtype=jnp.int32) * top_k)[None]])
    return y, counts + add.astype(counts.dtype)
