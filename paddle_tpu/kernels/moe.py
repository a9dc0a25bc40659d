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
``(silu(u) * v) W2_e``. Weights arrive as the held experts' stacks, ``w1
[hi - lo, d, 2 f]`` and ``w2 [hi - lo, f, d]``.

One arm (registered as ``moe_experts``; counted per program build in
``kernel.dispatch.moe_experts.dense``): every token through every held
expert, the gate (zero where the expert was not chosen) folded in before the
second product, whose contraction runs over experts and expert width at
once. It reads each held expert once and computes ``held / top_k`` times the
needed FLOPs: right where the step is bound by reading the experts anyway (a
decode step of 64 tokens hits every one of 36 held experts). On the chip at
Granite-4.0-H's sizes it also beat a sorted `jax.lax.ragged_dot` product at
every token count tried, 64 to 1,024 (PERF.md section 6, PR 31), because the
ragged product copies a layer's experts out of the stack first. A grouped
product that reads the stack in place is a Pallas kernel this file does not
have (ROADMAP Reach A2); it comes as an arm of its own with the chip reading
that shows it winning.

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

registry.register_op("moe_experts", impls=("dense",))

_HI = jax.lax.Precision.HIGHEST


def route(x, w_router, top_k):
    """(expert ids [T, top_k] int32, gates [T, top_k] f32): float32 logits
    over every expert of the router, the ``top_k`` largest, a softmax over
    those logits alone."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=_HI)
    top, idx = jax.lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _gated(h, gate):
    """[u | v] in f32 -> silu(u) * v, times the assignment's gate."""
    u, v = jnp.split(h, 2, axis=-1)
    return u * jax.nn.sigmoid(u) * v * gate


def _dense(x, w1, w2, idx, gates, lo):
    held = w1.shape[0]
    chosen = idx[:, :, None] == lo + jnp.arange(held)          # [T, K, E]
    gate = jnp.sum(jnp.where(chosen, gates[:, :, None], 0.0), axis=1)
    h = jnp.einsum("td,edf->tef", x, w1,
                   preferred_element_type=jnp.float32)         # [T, E, 2f]
    act = _gated(h, gate[:, :, None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", act, w2,
                      preferred_element_type=jnp.float32)


def routed_experts(x, w_router, w1, w2, *, top_k, held, counts=None,
                   valid=None):
    """This chip's part of a routed-expert layer for tokens ``x`` [T, d].

    w_router : [d, E] over ALL experts; w1 : [hi - lo, d, 2 f]; w2 :
    [hi - lo, f, d] of the held experts ``held`` = (lo, hi); top_k : experts
    a token; valid : [T] bool, the tokens ``counts`` counts (None: all). Returns
    ``y`` [T, d] in ``x``'s type, or ``(y, counts)`` when ``counts`` came.
    """
    lo, hi = held
    if w1.shape[0] != hi - lo or w2.shape[0] != hi - lo:
        raise ValueError(f"held experts {held} but {w1.shape[0]} stacked")
    if not 0 <= lo < hi <= w_router.shape[1]:
        raise ValueError(f"held experts {held} of a router over "
                         f"{w_router.shape[1]}")
    registry.dispatch("moe_experts")
    idx, gates = route(x, w_router, top_k)
    y = _dense(x, w1, w2, idx, gates, lo).astype(x.dtype)
    if counts is None:
        return y
    ok = jnp.ones(x.shape[0], bool) if valid is None else valid
    hit = (idx[:, :, None] == lo + jnp.arange(hi - lo)) & ok[:, None, None]
    add = jnp.concatenate([
        jnp.sum(hit, axis=(0, 1), dtype=jnp.int32),
        (jnp.sum(ok, dtype=jnp.int32) * top_k)[None]])
    return y, counts + add.astype(counts.dtype)
