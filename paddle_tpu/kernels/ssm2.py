"""Mamba-2 state ops over the serving engine's recurrent state (the
state-space dual form, Dao & Gu 2024), beside `kernels/ssm.py`'s Mamba-1
ops, whose convolution ops (`conv_update`, `conv_scan`) serve both.

A Mamba-2 layer keeps, per engine slot, ``H`` heads of a ``[P, N]`` state
(head width ``P``, state size ``N``); one scalar decay a head and step, and
``B`` / ``C`` shared by the heads of a group (one group here). The stored
stack is ``[n_layers, slots, N, H * P]`` float32: the state size on
sublanes and every head's ``P`` values side by side in the lane axis (8,192
lanes at the published size), as `kernels/ssm.py` keeps Mamba-1's ``[d_state,
d_inner]``. What a step multiplies a state by is one number a (head, p)
column: a lane vector, broadcast down the sublanes for nothing; ``B`` and
``C`` are one number a row; and ``y = S C`` is a sum down the sublanes that
comes out lane-dense. Heads and head width are ONE axis of the stored array
on purpose: as two, the chip's compiler swapped them inside the prefill
program and relaid the whole stack on the way in and out of every launch,
2.4 GB each way at the published size (tests/test_tpu_compile.py holds the
programs to no such copy). The stack is addressed with ``layer=`` and
rewritten in place exactly as `kernels/ssm.py` sets out; a call without
``layer`` takes one layer's state and counts in
``kernel.state_relayout.{op}`` (0 for every engine program).

The recurrence (``A = -exp(A_log)``, one scalar a head)::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

``ssm2_update`` advances every slot one token (decode), two arms under one
contract (``kernel.dispatch.ssm2_update.{xla|pallas}``):

- **xla** — the plainest form. As the chip's compiler builds it, one fusion
  writes the layer's new slab out beside the stack (with ``y``) and a
  second copies it in: four passes over the slab where two would do, each
  at half the memory's rate (PERF.md section 6, PR 31: 2.5 ms a layer for
  268 MB, 71% of a decode step);
- **pallas** — `_update_kernel`: the stack stays in HBM and is ALIASED to
  the result; a grid cell reads one slot's ``[N, block]`` piece of the
  layer's slab, updates it and writes it back where it lay, ``y`` beside
  it: the slab is read once and written once. Taken on a TPU; off it the
  interpreter runs it for parity tests only.

``ssm2_scan`` advances ONE slot by a chunk of a prompt in the chunked dual
form: inside a block of ``chunk`` tokens (the published 256) the outputs
are a masked matrix product over the block (``(C B^T * decay) (dt x)``),
and only the block's closing state walks on to the next block, so a
512-token prefill chunk is two steps of a loop and not 512. A padded token
carries ``dt = 0``: decay 1, input 0, the state passes it unchanged. Plain
XLA (one arm); every product inside is float32 at ``HIGHEST``: they are
small beside a layer's projections, and the state is what the layer's
memory of 4,000 tokens rests on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry
from paddle_tpu.kernels.ssm import _give, _stored
from paddle_tpu.observability import metrics

__all__ = ["ssm2_update", "ssm2_scan", "CHUNK"]


registry.register_op("ssm2_update", impls=("xla", "pallas"),
                     candidates=registry.tpu_first)
registry.register_op("ssm2_scan", impls=("xla",))

CHUNK = 256      # tokens a block of the dual form takes (mamba_chunk_size)
LANES = 2048     # lanes of a slot's slab a cell of the update kernel takes

_HI = jax.lax.Precision.HIGHEST


def _rows(v, p):
    """A number a head -> a number a (head, p) column: [B, H] -> [B, H*P]."""
    return jnp.repeat(v, p, axis=-1)


def _update_kernel(layer_ref, active_ref, s_ref, dec_ref, x_ref, b_ref,
                   c_ref, o_ref, y_ref):
    # one grid cell per (slot b, block j of the lane axis): s_ref / o_ref
    # the [N, lanes] piece of the layer's slab (the same HBM, aliased);
    # dec_ref / x_ref [1, lanes] the decay and dt * x of its columns;
    # b_ref / c_ref [N, 1] the slot's B and C; layer and the active mask
    # scalar-prefetched. y = S_new C sums down the sublanes: lane-dense.
    from jax.experimental import pallas as pl
    del layer_ref
    s = s_ref[...].astype(jnp.float32)
    new = dec_ref[...] * s + b_ref[...] * x_ref[...]
    new = new.astype(o_ref.dtype)
    y_ref[...] = jnp.sum(new.astype(jnp.float32) * c_ref[...], axis=0,
                         keepdims=True)
    live = active_ref[pl.program_id(0)] != 0
    o_ref[...] = jnp.where(live, new, s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_update(ssm, layer, active, dec, xdt, bm, cm, *, interpret):
    """(y [B, HP] f32, ssm): the kernel over the stored stack at a traced
    layer; every Mamba layer of a program is the same call of it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.core.autograd import x64_off_scope
    _, b, n, hp = ssm.shape
    lanes = LANES if hp % LANES == 0 else hp
    slab = pl.BlockSpec((None, None, n, lanes),
                        lambda i, j, lyr, act: (lyr[0], i, 0, j))
    row = pl.BlockSpec((None, 1, lanes), lambda i, j, *_: (i, 0, j))
    col = pl.BlockSpec((None, n, 1), lambda i, j, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, hp // lanes),
        in_specs=[slab, row, row, col, col], out_specs=[slab, row])
    with x64_off_scope():
        new, y = pl.pallas_call(
            _update_kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                       jax.ShapeDtypeStruct((b, 1, hp), jnp.float32)],
            # operand 2 (after the two prefetched scalars) is the stack
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(layer.reshape(1), active.astype(jnp.int32), ssm,
          *(v.astype(jnp.float32) for v in (
              dec[:, None], xdt[:, None], bm[..., None], cm[..., None])))
    return y[:, 0], new


def ssm2_update(ssm, dt, x, bm, cm, a, d_skip, active, *, layer=None,
                impl=None, interpret=None):
    """The Mamba-2 decode update: one token for every slot.

    ssm : [nl, B, N, H * P] (or one layer's [B, N, H * P]); dt : [B, H]
    f32 (after the softplus); x : [B, H, P] f32; bm, cm : [B, N] f32; a :
    [H] f32 (negative); d_skip : [H]; active : [B] bool — an inactive
    slot's state is left alone; impl : ``xla`` / ``pallas`` / None (pallas
    on a TPU). Returns (y [B, H, P] f32, ssm updated)."""
    stacked = layer is not None
    if not stacked:
        metrics.counter("kernel.state_relayout.ssm2_update").inc()
        ssm, layer = ssm[None], 0
    impl = registry.dispatch("ssm2_update", forced=impl)
    b, h, p = x.shape
    dec = _rows(jnp.exp(dt * a), p)                       # [B, H * P]
    xdt = _rows(dt, p) * x.reshape(b, h * p)
    if impl == "pallas":
        if interpret is None:
            from paddle_tpu.kernels.pallas._compat import default_interpret
            interpret = default_interpret()
        y, ssm = _pallas_update(ssm, jnp.asarray(layer, jnp.int32), active,
                                dec, xdt, bm, cm, interpret=bool(interpret))
    else:
        old = ssm[layer]                                  # [B, N, H * P]
        new = dec[:, None, :] * old.astype(jnp.float32) \
            + bm[:, :, None] * xdt[:, None, :]
        new = new.astype(ssm.dtype)    # float32 as the engine keeps it
        y = jnp.sum(new.astype(jnp.float32) * cm[:, :, None], axis=1)
        ssm = ssm.at[layer].set(jnp.where(active[:, None, None], new, old))
    y = y.reshape(b, h, p) + d_skip.astype(jnp.float32)[None, :, None] * x
    return y, (ssm if stacked else ssm[0])


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def ssm2_scan(ssm, dt, x, bm, cm, a, d_skip, slot, fresh, *, layer=None,
              chunk=CHUNK):
    """The Mamba-2 prefill scan: T tokens of ONE slot from its carried-in
    state (zero when ``fresh``), the closing state written back.

    dt : [T, H] f32, 0 on padded tokens; x : [T, H, P] f32; bm, cm :
    [T, N] f32; a : [H]; d_skip : [H]. Returns (y [T, H, P] f32, ssm
    updated)."""
    ssm, layer, was = _stored("ssm2_scan", ssm, layer)
    t, h, p = x.shape
    n = bm.shape[-1]
    q = min(int(chunk), t)
    pad = -t % q
    if pad:                      # whole blocks: a padded token is inert
        dt, x, bm, cm = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                         for v in (dt, x, bm, cm))
    nc = (t + pad) // q
    s = jnp.where(fresh, 0, ssm[layer, slot]).astype(jnp.float32)  # [N, HP]
    # log decay up to and including each token, inside its block: [c, H, Q]
    cum = jnp.cumsum((dt * a).reshape(nc, q, h), axis=1).transpose(0, 2, 1)
    xdt = (x * dt[..., None]).reshape(nc, q, h, p)
    bq, cq = bm.reshape(nc, q, n), cm.reshape(nc, q, n)
    # inside a block: y_i += sum_{j <= i} (C_i . B_j) decay(j -> i) dt_j x_j
    causal = jnp.tril(jnp.ones((q, q), bool))
    seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    mix = _mm("cin,cjn->cij", cq, bq)[:, None] * jnp.exp(seg)  # [c, H, Q, Q]
    y = _mm("chij,cjhp->cihp", mix, xdt)
    # what each block adds to the state by its end: [c, N, H * P]
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 2, 1)   # [c, Q, H]
    add = _mm("cjn,cjr->cnr", bq,
              (xdt * to_end[..., None]).reshape(nc, q, h * p))
    total = _rows(jnp.exp(cum[..., -1]), p)                    # [c, H * P]
    from_start = jnp.exp(cum).transpose(0, 2, 1)               # [c, Q, H]
    carried = []
    for c in range(nc):          # the blocks' states walk on, one a block
        carried.append(_mm("in,nr->ir", cq[c], s).reshape(q, h, p)
                       * from_start[c][..., None])
        s = total[c][None, :] * s + add[c]
    y = (y + jnp.stack(carried)).reshape(nc * q, h, p)[:t]
    y = y + d_skip.astype(jnp.float32)[None, :, None] * x[:t]
    return y, _give(ssm.at[layer, slot].set(s.astype(ssm.dtype)), was)
