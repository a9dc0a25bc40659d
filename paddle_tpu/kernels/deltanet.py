"""Gated delta-rule state ops over the serving engine's recurrent state
(Gated DeltaNet, arXiv:2412.06464).

The repo's other recurrences (kernels/ssm.py, ssm2.py, retention.py) ADD an
outer product to a decayed state. The delta rule first READS the state with
the new key and writes back only the error. Per value head, with ``S`` a
``dk x dv`` float32 matrix (keys on the sublanes, values on the lanes)::

    S <- exp(g) S                     g <= 0 the token's log decay
    u  = beta (v - S^T k)             beta in (0, 1) the write strength
    S <- S + k u^T
    o  = S^T q                        the state AFTER the token

so a token reads the state twice where the others read it once, and the
tokens of a chunk are coupled: token ``i``'s ``u`` depends on every earlier
``u`` of the chunk through the keys' products.

    S : [layers, slots, value heads, dk, dv]  float32

The stack is addressed with ``layer=`` (a traced index is fine) and
rewritten in place, as `kernels/ssm2.py` sets out. Keys and queries arrive
per VALUE head (the caller repeats a key head over the value heads it
serves) and already normalised and scaled.

``deltanet_update`` advances every slot one token (decode), two arms under
one contract (``kernel.dispatch.deltanet_update.{xla|pallas}``):

- **xla**: the equations above on the layer's slab; the parity reference on
  the CPU;
- **pallas**: `_update_kernel`: the stack stays in HBM, ALIASED to the
  result; a grid cell takes `HEADS` heads of one slot through VMEM, makes
  both reads and the rank-one write there and rewrites the block where it
  lay: the state crosses HBM once each way however often the rule reads
  it. A dead slot's block comes back the same bits (it is still moved: a
  block that a grid cell owns is written back; skipping the move takes
  hand-made DMA and is left for the reading that shows dead slots cost).
  Taken on a TPU.

``deltanet_chunk`` advances ONE slot by a chunk of a prompt (arm ``xla``),
in sub-chunks of `SUB` tokens that carry ``S`` between them. Inside a
sub-chunk of ``C`` tokens with cumulative decay ``G`` (``G_i`` the sum of
``g`` up to and including token ``i``) the ``u`` of all tokens solve a UNIT
LOWER TRIANGULAR system::

    (I + A) U = beta . (V - exp(G) K S_0),   A = tril(beta_i (K K^T)_ij
                                                      exp(G_i - G_j), -1)
    O   = exp(G) Q S_0 + tril((Q K^T) . exp(G_i - G_j)) U
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U

``A`` is strictly lower triangular, so ``A^C = 0`` and the inverse is the
finite DOUBLING PRODUCT ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...``:
``log2 C`` squarings and as many products of ``C x C`` matrices, all matrix
products (no substitution loop, which the chip would run a row at a time).
At ``C`` = 64 that is 12 products of 64^3 a head and sub-chunk, 6.3 MFLOP
beside the 10.5 MFLOP of the sub-chunk's other products; 64 heads, 8
sub-chunks of a 512-token launch: 8.6 GFLOP a layer, float32 operands at
``highest`` (six bfloat16 passes: some 0.26 ms of a v5e's peak). Every
product accumulates in float32. A padded token carries ``g = 0``, ``beta =
0`` and ``k = 0``: the state passes it unchanged.

**The convolution before it** (`conv_update`, `conv_chunk`): depthwise,
causal, no bias, over the last ``K - 1`` inputs carried per sequence,
``[slots, (K - 1) * width]`` with the taps side by side on the lanes
(`kernels/ssm.py` says why), ONE ARRAY A LAYER and not a stack over layers.
A decode step's new state is the old one SHIFTED by a tap, so it depends on
what it replaces; written as an in-place update of a layer's slab of a
donated stack (`ssm.conv_update` with ``layer=``), the v5e compiler, short
of memory at 128 slots, rematerialized the update with its read of the
slab, ran both copies in place and shifted layer 0's state twice (my chip
run, PR 42: every token after a sequence's first decode step was wrong at
128 slots and right at 64 and 96). With an array of its own the new state
is a new buffer and nothing is updated in place; a chunk writes one slot's
row, whose value does not depend on the buffer once it is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry

__all__ = ["deltanet_update", "deltanet_chunk", "conv_update", "conv_chunk",
           "state_shape", "HEADS", "SUB"]


registry.register_op("deltanet_update", impls=("xla", "pallas"),
                     candidates=registry.tpu_first)
registry.register_op("deltanet_chunk", impls=("xla",))

_HI = jax.lax.Precision.HIGHEST
HEADS = 8            # value heads a grid cell of the update kernel takes
ROWS = 8             # sublanes of the update kernel's small operand
SUB = 64             # tokens of a sub-chunk of `deltanet_chunk`


def state_shape(layers: int, slots: int, heads: int, dk: int, dv: int):
    return (layers, slots, heads, dk, dv)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ decode

def _update_kernel(layer_ref, active_ref, aux_ref, s_ref, so_ref, y_ref):
    # one grid cell per (slot, block of HEADS value heads): s_ref / so_ref
    # the [HEADS, dk, dv] block of the layer's slab (the same HBM,
    # aliased); aux_ref [HEADS, ROWS, d]: rows k, q, v, exp(g) and beta (on
    # every lane); y_ref [HEADS, dv]. A key is wanted down the sublanes:
    # its row is broadcast and transposed (a [d, 1] operand would be a
    # tile a number).
    from jax.experimental import pallas as pl
    del layer_ref
    live = active_ref[pl.program_id(0)] != 0
    n, dk, dv = s_ref.shape
    outs = []
    for h in range(n):
        rows = aux_ref[h]
        kcol = jnp.broadcast_to(rows[0:1, :dk], (dv, dk)).T      # [dk, dv]
        qcol = jnp.broadcast_to(rows[1:2, :dk], (dv, dk)).T
        old = s_ref[h]
        s = rows[3:4, :dv] * old
        read = jnp.sum(s * kcol, axis=0, keepdims=True)          # [1, dv]
        u = rows[4:5, :dv] * (rows[2:3, :dv] - read)
        s = s + kcol * u
        so_ref[h] = jnp.where(live, s, old)
        outs.append(jnp.sum(s * qcol, axis=0, keepdims=True))
    y_ref[...] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_update(state, layer, active, aux, *, interpret):
    """(y [B, H, dv], state): the kernel over the stored stack at a traced
    layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.core.autograd import x64_off_scope
    _, b, h, dk, dv = state.shape
    n = HEADS if h % HEADS == 0 else h
    slab = pl.BlockSpec((None, None, n, dk, dv),
                        lambda i, j, lyr, act: (lyr[0], i, j, 0, 0))
    small = pl.BlockSpec((None, n, ROWS, aux.shape[-1]),
                         lambda i, j, *_: (i, j, 0, 0))
    out = pl.BlockSpec((None, n, dv), lambda i, j, *_: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, h // n),
        in_specs=[small, slab], out_specs=[slab, out])
    # a block in and out, each double-buffered, beside the temporaries
    vmem = 4 * n * dk * dv * 4 + (16 << 20)
    with x64_off_scope():
        new, y = pl.pallas_call(
            _update_kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((b, h, dv), jnp.float32)],
            # operand 3 (after the two prefetched scalars and the small
            # operand) is the stack
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(vmem, 32 << 20)),
            interpret=interpret,
        )(layer.reshape(1), active.astype(jnp.int32), aux, state)
    return y, new


def deltanet_update(state, log_g, beta, q, k, v, active, *, layer, impl=None,
                    interpret=None):
    """The delta-rule decode update: one token for every slot.

    state : the stored stack [layers, B, H, dk, dv] float32; log_g, beta :
    [B, H] f32 (``log_g <= 0``); q, k : [B, H, dk] (per VALUE head,
    normalised and scaled by the caller); v : [B, H, dv]; active : [B] bool:
    an inactive slot's state is left as it was (what it reads is
    unspecified); impl : ``xla`` / ``pallas`` / None (pallas on a TPU).
    Returns (o [B, H, dv] f32, state): the read-out of the state AFTER this
    token."""
    impl = registry.dispatch("deltanet_update", forced=impl)
    f32 = jnp.float32
    b, h, dk = k.shape
    dv = v.shape[-1]
    decay = jnp.exp(log_g.astype(f32))
    qf, kf, vf, bf = (x.astype(f32) for x in (q, k, v, beta))
    if impl == "pallas" and state.dtype == f32 and dk % 128 == 0 \
            and dv % 128 == 0:
        if interpret is None:
            from paddle_tpu.kernels.pallas._compat import default_interpret
            interpret = default_interpret()
        d = max(dk, dv)

        def row(x):                               # [B, H, n] -> [B, H, 1, d]
            return jnp.pad(x, ((0, 0), (0, 0), (0, d - x.shape[-1])))[:, :,
                                                                      None]

        def lanes(x):
            return jnp.broadcast_to(x[..., None, None], (b, h, 1, d))

        aux = jnp.concatenate(
            [row(kf), row(qf), row(vf), lanes(decay), lanes(bf),
             jnp.zeros((b, h, ROWS - 5, d), f32)], axis=2)
        return _pallas_update(state, jnp.asarray(layer, jnp.int32), active,
                              aux, interpret=bool(interpret))
    old = state[layer].astype(f32)                          # [B, H, dk, dv]
    s = decay[..., None, None] * old
    u = bf[..., None] * (vf - jnp.einsum("bhkv,bhk->bhv", s, kf,
                                         precision=_HI))
    s = s + kf[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, qf, precision=_HI)
    new = jnp.where(active[:, None, None, None], s.astype(state.dtype),
                    state[layer])
    return o, state.at[layer].set(new)


# ----------------------------------------------------------------- prefill

def _solve_unit_lower(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular, by
    the doubling product (module docstring)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv, power = eye - a, a
    span = 2                     # powers of ``a`` below ``span`` are in
    while span < c:
        power = _mm("...ij,...jk->...ik", power, power)
        inv = _mm("...ij,...jk->...ik", inv, eye + power)
        span *= 2
    return inv


def _sub_chunk(s0, x):
    """One sub-chunk of one head: s0 [dk, dv]; x = (g [C], beta [C], q, k
    [C, dk], v [C, dv]). Returns (state after, o [C, dv])."""
    g, beta, q, k, v = x
    c = g.shape[0]
    cum = jnp.cumsum(g)
    tril = jnp.tril(jnp.ones((c, c), bool))
    # exp(G_i - G_j) for j <= i (<= 1); the rest is never read
    decay = jnp.exp(jnp.where(tril, cum[:, None] - cum[None, :], -jnp.inf))
    kk = _mm("id,jd->ij", k, k)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  beta[:, None] * kk * decay, 0.0)
    up = jnp.exp(cum)[:, None]
    rhs = beta[:, None] * (v - up * _mm("id,dv->iv", k, s0))
    u = _mm("ij,jv->iv", _solve_unit_lower(a), rhs)
    o = up * _mm("id,dv->iv", q, s0) \
        + _mm("ij,jv->iv", _mm("id,jd->ij", q, k) * decay, u)
    to_end = jnp.exp(cum[-1] - cum)[:, None]
    s1 = jnp.exp(cum[-1]) * s0 + _mm("id,iv->dv", to_end * k, u)
    return s1, o


def deltanet_chunk(state, log_g, beta, q, k, v, slot, fresh, valid, *, layer,
                   sub=SUB):
    """The delta-rule prefill: T tokens of ONE slot from its carried-in
    state (zero when ``fresh``), the closing state written back.

    log_g, beta : [T, H] f32; q, k : [T, H, dk] (per VALUE head, normalised
    and scaled); v : [T, H, dv]; tokens from ``valid`` on are padding and
    leave the state alone; sub : tokens of a sub-chunk (T is cut into
    ``T / sub`` of them, or taken whole when ``sub`` does not divide it).
    Returns (o [T, H, dv] f32, state)."""
    registry.count("deltanet_chunk", "xla")
    f32 = jnp.float32
    t, h, dk = k.shape
    dv = v.shape[-1]
    live = (jnp.arange(t) < valid)[:, None]
    g = jnp.where(live, log_g.astype(f32), 0.0)
    bf = jnp.where(live, beta.astype(f32), 0.0)
    kf = jnp.where(live[..., None], k.astype(f32), 0.0)
    c = sub if t % sub == 0 else t

    def cut(x):                    # [T, H, ...] -> [T / c, H, c, ...]
        return jnp.moveaxis(x.reshape(t // c, c, *x.shape[1:]), 2, 1)

    s0 = jnp.where(fresh, 0, state[layer, slot]).astype(f32)
    s1, o = jax.lax.scan(
        lambda s, x: jax.vmap(_sub_chunk)(s, x), s0,
        (cut(g), cut(bf), cut(q.astype(f32)), cut(kf), cut(v.astype(f32))))
    o = jnp.moveaxis(o, 1, 2).reshape(t, h, dv)
    return o, state.at[layer, slot].set(s1.astype(state.dtype))


# ------------------------------------------------------------- convolution

def conv_update(conv, x, w, active):
    """One token of the depthwise causal convolution for every slot.

    conv : [B, (K - 1) * width] ONE layer's last inputs, oldest first; x :
    [B, width] the new inputs; w : [K, width] taps, the LAST multiplying
    the current token; active : [B] bool: an inactive slot's state is left
    alone (it may be mid-prefill). Returns (output [B, width] f32 before
    the activation, the layer's new state: a new array, module
    docstring)."""
    k, width = w.shape
    win = jnp.concatenate([conv, x.astype(conv.dtype)], axis=1)
    out = sum(w[j].astype(jnp.float32)
              * win[:, j * width:(j + 1) * width].astype(jnp.float32)
              for j in range(k))
    return out, jnp.where(active[:, None], win[:, width:], conv)


def conv_chunk(conv, x, w, slot, fresh, valid):
    """A chunk of the convolution for ONE slot, with its carried-in state.

    conv : [B, (K - 1) * width] one layer's; x : [T, width]; slot : scalar
    int32; fresh : scalar bool: the sequence starts here, so its state
    reads as zero whatever the slot held; valid : scalar int32 true token
    count (the state keeps the last ``K - 1`` inputs BEFORE position
    ``valid``). Returns (output [T, width] f32, the layer's state with the
    slot's row rewritten)."""
    k, width = w.shape
    t = x.shape[0]
    old = jnp.where(fresh, 0, conv[slot]).reshape(k - 1, width)
    xp = jnp.concatenate([old, x.astype(old.dtype)], axis=0)
    out = sum(w[j].astype(jnp.float32) * xp[j:j + t].astype(jnp.float32)
              for j in range(k))
    new = jax.lax.dynamic_slice_in_dim(xp, valid, k - 1, axis=0)
    return out, conv.at[slot].set(new.reshape(-1))
