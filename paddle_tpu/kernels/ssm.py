"""Mamba-1 state ops over the serving engine's recurrent state.

Per Mamba layer and engine slot a sequence keeps two fixed-size arrays
(inference/engine.py "Three kinds of state"):

- the convolution state: the last ``d_conv - 1`` inputs of the depthwise
  causal convolution, ``[n_layers, slots, (d_conv - 1) * d_inner]`` in the
  served type (the taps merged into the lane axis: a second-minor axis of
  3 would be padded to a tile of 8 and relaid at every program's edge);
- the SSM state ``S``, ``[n_layers, slots, d_state, d_inner]`` in float32 —
  ``d_inner`` in the lane axis, so the 16 states of a channel sit in one
  (8, 128) tile column and every op below is lane-dense.

Both are STORED stacks addressed with ``layer=`` (a traced index is fine),
read and written in place: a decode step rewrites one layer's ``[slots,
...]`` slab, a prefill chunk one slot's row of it, and a donated step
program therefore copies no state-sized array (tests/test_tpu_compile.py).
Without ``layer`` an op takes ONE layer's state — the form tests and probes
call — which inside a program would be a slice and an update of the stack,
so each such call counts in the trace-time ``kernel.state_relayout.{op}``
(the twin of ``kernel.pool_relayout.{op}``; 0 for every engine program).

The recurrence (Gu & Dao 2023; ``A = -exp(A_log)``)::

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (outer) B_t
    y_t = S_t C_t + D * x_t

``ssm_update`` advances every slot by one token (decode), ``ssm_scan`` one
slot by a chunk with its carried-in state (prefill). Both are the plainest
correct XLA (registered with the single impl ``xla``); a Pallas arm belongs
here once a traced run shows the family among a cell's largest (PERF.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry
from paddle_tpu.observability import metrics

__all__ = ["conv_update", "conv_scan", "ssm_update", "ssm_scan"]

registry.register_op("ssm_update", impls=("xla",))
registry.register_op("ssm_scan", impls=("xla",))


def _stored(op, state, layer):
    """(stack, layer, was_stack): the state as the stored stack. One layer's
    state handed without ``layer`` is viewed as a stack of one and
    counted."""
    registry.count(op, "xla")
    if layer is not None:
        return state, layer, True
    metrics.counter(f"kernel.state_relayout.{op}").inc()
    return state[None], 0, False


def _give(stack, was_stack):
    return stack if was_stack else stack[0]


def conv_update(conv, x, w, b, active, *, layer=None):
    """One token of the depthwise causal convolution for every slot.

    conv : [nl, B, (K-1) * di] (or one layer's [B, (K-1) * di]); x :
    [B, di] the new inputs; w : [K, di] taps, the LAST multiplying the
    current token;
    b : [di]; active : [B] bool — an inactive slot's state is left alone
    (it may be mid-prefill). Returns (conv output [B, di] f32 before the
    activation, conv updated)."""
    conv, layer, was = _stored("ssm_update", conv, layer)
    k, di = w.shape
    old = conv[layer]                                     # [B, (K-1)*di]
    win = jnp.concatenate([old, x.astype(old.dtype)], axis=1)
    out = sum(w[j].astype(jnp.float32)
              * win[:, j * di:(j + 1) * di].astype(jnp.float32)
              for j in range(k)) + b.astype(jnp.float32)
    new = jnp.where(active[:, None], win[:, di:], old)
    return out, _give(conv.at[layer].set(new), was)


def conv_scan(conv, x, w, b, slot, fresh, valid, *, layer=None):
    """A chunk of the convolution for ONE slot, with its carried-in state.

    conv : [nl, B, (K-1) * di]; x : [T, di]; slot : scalar int32; fresh :
    scalar bool — the sequence starts here, so its state reads as zero
    whatever the slot held; valid : scalar int32 true token count (the
    state keeps the last K-1 inputs BEFORE position ``valid``). Returns
    (conv output [T, di] f32, conv updated)."""
    conv, layer, was = _stored("ssm_scan", conv, layer)
    k, di = w.shape
    t = x.shape[0]
    old = jnp.where(fresh, 0, conv[layer, slot]).reshape(k - 1, di)
    xp = jnp.concatenate([old, x.astype(old.dtype)], axis=0)
    out = sum(w[j].astype(jnp.float32) * xp[j:j + t].astype(jnp.float32)
              for j in range(k)) + b.astype(jnp.float32)
    new = jax.lax.dynamic_slice_in_dim(xp, valid, k - 1, axis=0)
    return out, _give(conv.at[layer, slot].set(new.reshape(-1)), was)


def ssm_update(ssm, dt, x, bm, cm, a_t, d_skip, active, *, layer=None):
    """The Mamba decode update: one token for every slot, state read and
    written once.

    ssm : [nl, B, ds, di] f32 (or one layer's [B, ds, di]); dt, x :
    [B, di] f32; bm, cm : [B, ds] f32; a_t : [ds, di] f32, ``A``
    transposed; d_skip : [di]; active : [B] bool. Returns (y [B, di] f32,
    ssm updated)."""
    ssm, layer, was = _stored("ssm_update", ssm, layer)
    old = ssm[layer]                                      # [B, ds, di]
    new = jnp.exp(dt[:, None, :] * a_t[None]) * old.astype(jnp.float32) \
        + (dt * x)[:, None, :] * bm[:, :, None]
    new = new.astype(ssm.dtype)        # float32 as the engine keeps it
    y = jnp.einsum("bnd,bn->bd", new.astype(jnp.float32), cm) \
        + d_skip.astype(jnp.float32) * x
    new = jnp.where(active[:, None, None], new, old)
    return y, _give(ssm.at[layer].set(new), was)


def ssm_scan(ssm, dt, x, bm, cm, a_t, d_skip, slot, fresh, *, layer=None,
             unroll=8):
    """The Mamba prefill scan: a chunk of T tokens for ONE slot from its
    carried-in state (zero when ``fresh``), the final state written back.

    dt, x : [T, di] f32 — a padded token carries ``dt = 0`` and so leaves
    the state alone; bm, cm : [T, ds] f32. Returns (y [T, di] f32, ssm
    updated)."""
    ssm, layer, was = _stored("ssm_scan", ssm, layer)
    s0 = jnp.where(fresh, 0, ssm[layer, slot])            # [ds, di]

    def step(s, inp):
        dt_t, x_t, b_t, c_t = inp
        s = jnp.exp(dt_t[None, :] * a_t) * s.astype(jnp.float32) \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        s = s.astype(ssm.dtype)        # float32 as the engine keeps it
        return s, (s.astype(jnp.float32) * c_t[:, None]).sum(0)

    s_end, y = jax.lax.scan(step, s0, (dt, x, bm, cm), unroll=unroll)
    y = y + d_skip.astype(jnp.float32) * x
    return y, _give(ssm.at[layer, slot].set(s_end), was)
