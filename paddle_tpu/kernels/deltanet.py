"""Gated delta-rule state ops over the serving engine's recurrent state
(Gated DeltaNet, arXiv:2412.06464; with a decay per key CHANNEL, Kimi Delta
Attention, arXiv:2510.26692).

The repo's other recurrences (kernels/ssm.py, ssm2.py, retention.py) ADD an
outer product to a decayed state. The delta rule first READS the state with
the new key and writes back only the error. Per value head, with ``S`` a
``dk x dv`` float32 matrix (keys on the sublanes, values on the lanes)::

    S <- exp(g) S                     g <= 0 the token's log decay
    u  = beta (v - S^T k)             beta in (0, 2) the write strength
    S <- S + k u^T
    o  = S^T q                        the state AFTER the token

so a token reads the state twice where the others read it once, and the
tokens of a chunk are coupled: token ``i``'s ``u`` depends on every earlier
``u`` of the chunk through the keys' products.

**One contract, two decays.** ``log_g`` is ``[.., H]``, ONE log decay a
head (``exp(g)`` a scalar: Gated DeltaNet, `models/gigachat35.py`), or
``[.., H, dk]``, one a KEY CHANNEL (``exp(g)`` scales the state's ROWS:
``S <- Diag(exp(g)) S``, Kimi Delta Attention, `models/solar_open2.py`).
``beta`` is any value in (0, 2): under 1 from a plain sigmoid, up to 2 where
a model lets ``I - beta k k^T`` have a negative eigenvalue; nothing here
depends on which. The scalar form's programs are what they were before the
per-channel form came (`tests/test_tpu_compile.py` holds their op
families); the per-channel form is registered and counted apart
(``kernel.dispatch.kda_update.{xla|pallas}``, ``kda_chunk.xla``).

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
  it. A dead slot's block comes back the same bits, and it crosses HBM
  like a live one's: a block that a grid cell owns is read and written
  back (skipping the move takes hand-made DMA and waits for a reading that
  shows dead slots cost). Per channel the decay arrives as a row of the
  small operand like the key and is turned down the sublanes with it.
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

**The chunk form per channel** (`_sub_chunk_channels`). With ``G_i`` now a
vector over the key channels the decay no longer factors out of the keys'
products: ``A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)``, likewise ``Q
K^T``. They stay matrix products by splitting ``exp(G_i - G_j) = exp(G_i -
R) exp(R - G_j)`` around a REFERENCE POINT ``R`` and folding one factor
into each operand. A sub-chunk's `SUB` tokens are cut into blocks of
`BLOCK` = 16 rows; for the rows ``i`` of block ``I`` the reference is
``R_I``, the cumulative decay just BEFORE the block's first token. Then
``exp(G_i - R_I) <= 1`` for every row, ``exp(R_I - G_j) <= 1`` for every
column ``j`` of an earlier block, and only the columns of the SAME block
take a positive exponent, what the block's own at most 16 tokens lose; the
columns of later blocks are never read and get a factor of 0. That positive
exponent is held to `MAX_EXP` = 80 (``e^80`` = 5.5e34, inside float32 with
keys of norm 1): NO POSITIVE NUMBER LARGER THAN 80 IS EVER EXPONENTIATED,
and every term of a product is at most 1 in size. The form is exact while
a channel loses at most 80 nats inside one block of 16 tokens (5 nats a
token sustained, a retention of 0.7% a token; the family's published
initial ranges reach 1.6). Past that, the pairs ``j < i`` of that channel
inside that block are read LOW, by the factor ``exp(80 - loss)``: nothing
where the channel keeps losing that fast (their weight ``exp(G_i - G_j)``
is then under ``e^-5`` a token apart), a pair's whole weight where ONE
token wiped the channel and both tokens of the pair come after it. A
token's read of its own write (``i = j``, weight 1) is the plain ``q . k``
and takes no reference point. The other factors are ``exp(G_i)`` and
``exp(G_C - G_j)`` (from the sub-chunk's start, to its end: never
positive). ``S`` is carried between sub-chunks and a padded token passes as
in the scalar form. The triangular system is solved BY BLOCKS
(`_invert_unit_lower`), not by the doubling product: with ``beta`` up to 2
the powers of ``A`` grow before they cancel.

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
import numpy as np

from paddle_tpu.kernels import registry

__all__ = ["deltanet_update", "deltanet_chunk", "conv_update", "conv_chunk",
           "state_shape", "HEADS", "SUB", "BLOCK", "MAX_EXP"]


registry.register_op("deltanet_update", impls=("xla", "pallas"),
                     candidates=registry.tpu_first)
registry.register_op("deltanet_chunk", impls=("xla",))
# the same ops with a decay per key channel (module docstring)
registry.register_op("kda_update", impls=("xla", "pallas"),
                     candidates=registry.tpu_first)
registry.register_op("kda_chunk", impls=("xla",))

_HI = jax.lax.Precision.HIGHEST
HEADS = 8            # value heads a grid cell of the update kernel takes
ROWS = 8             # sublanes of the update kernel's small operand
SUB = 64             # tokens of a sub-chunk of `deltanet_chunk`
BLOCK = 16           # rows that share a reference point (per channel)
MAX_EXP = 80.0       # the largest positive number ever exponentiated


def state_shape(layers: int, slots: int, heads: int, dk: int, dv: int):
    return (layers, slots, heads, dk, dv)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ decode

def _update_kernel(layer_ref, active_ref, aux_ref, s_ref, so_ref, y_ref, *,
                   channels):
    # one grid cell per (slot, block of HEADS value heads): s_ref / so_ref
    # the [HEADS, dk, dv] block of the layer's slab (the same HBM,
    # aliased); aux_ref [HEADS, ROWS, d]: rows k, q, v, exp(g) and beta (on
    # every lane; with ``channels`` exp(g) is a row over dk like the key);
    # y_ref [HEADS, dv]. A key is wanted down the sublanes: its row is
    # broadcast and transposed (a [d, 1] operand would be a tile a number).
    from jax.experimental import pallas as pl
    del layer_ref
    live = active_ref[pl.program_id(0)] != 0
    n, dk, dv = s_ref.shape
    outs = []
    for h in range(n):
        rows = aux_ref[h]

        def down(r, rows=rows):                                  # [dk, dv]
            return jnp.broadcast_to(rows[r:r + 1, :dk], (dv, dk)).T

        kcol, qcol = down(0), down(1)
        old = s_ref[h]
        s = (down(3) if channels else rows[3:4, :dv]) * old
        read = jnp.sum(s * kcol, axis=0, keepdims=True)          # [1, dv]
        u = rows[4:5, :dv] * (rows[2:3, :dv] - read)
        s = s + kcol * u
        so_ref[h] = jnp.where(live, s, old)
        outs.append(jnp.sum(s * qcol, axis=0, keepdims=True))
    y_ref[...] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret", "channels"))
def _pallas_update(state, layer, active, aux, *, interpret, channels=False):
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
            functools.partial(_update_kernel, channels=channels),
            grid_spec=grid_spec,
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

    state : the stored stack [layers, B, H, dk, dv] float32; log_g : [B, H]
    f32, one log decay a head, or [B, H, dk], one a key channel (``log_g <=
    0``); beta : [B, H] f32 in (0, 2); q, k : [B, H, dk] (per VALUE head,
    normalised and scaled by the caller); v : [B, H, dv]; active : [B] bool:
    an inactive slot's state is left as it was (what it reads is
    unspecified); impl : ``xla`` / ``pallas`` / None (pallas on a TPU).
    Returns (o [B, H, dv] f32, state): the read-out of the state AFTER this
    token."""
    channels = log_g.ndim == 3
    impl = registry.dispatch("kda_update" if channels else "deltanet_update",
                             forced=impl)
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
            [row(kf), row(qf), row(vf),
             row(decay) if channels else lanes(decay), lanes(bf),
             jnp.zeros((b, h, ROWS - 5, d), f32)], axis=2)
        return _pallas_update(state, jnp.asarray(layer, jnp.int32), active,
                              aux, interpret=bool(interpret),
                              channels=channels)
    old = state[layer].astype(f32)                          # [B, H, dk, dv]
    s = (decay[..., None] if channels else decay[..., None, None]) * old
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


def _invert_unit_lower(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular, BY
    BLOCKS: the inverses of the diagonal blocks of 1, 2, 4, ... rows are
    merged in pairs, ``[[L11, 0], [L21, L22]]^-1 = [[L11^-1, 0], [-L22^-1
    L21 L11^-1, L22^-1]]``, two products of ``C x C`` a level (as many as
    `_solve_unit_lower` makes) and no power of ``a``: with ``beta`` up to 2
    and keys that lie close to one another the entries of ``a`` reach 1 and
    its powers grow by orders before they cancel, which float32 does not
    survive (64 tokens of neighbouring keys with ``beta`` near 2: the
    doubling product read 2e-3 off the token recurrence, this form 6e-7)."""
    c = a.shape[-1]
    i = np.arange(c)             # the masks are constants of the program
    inv = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    size = 1
    while size < c:
        # the lower-left block of every pair of neighbouring blocks
        half, pair = i // size % 2, i // (2 * size)
        l21 = (half[:, None] == 1) & (half[None, :] == 0) \
            & (pair[:, None] == pair[None, :])
        inv = inv - _mm("...ij,...jk->...ik", inv, _mm(
            "...ij,...jk->...ik", jnp.where(l21, a, 0.0), inv))
        size *= 2
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


def _sub_chunk_channels(s0, x):
    """`_sub_chunk` with a log decay per key channel: g [C, dk]. The keys'
    products around one reference point a block of `BLOCK` rows (module
    docstring)."""
    g, beta, q, k, v = x
    c, dk = g.shape
    r = BLOCK if c % BLOCK == 0 else c
    n = c // r
    cum = jnp.cumsum(g, axis=0)                                  # [C, dk]
    ref = (cum - g).reshape(n, r, dk)[:, 0]      # before a block's first row
    rows = jnp.exp(cum.reshape(n, r, dk) - ref[:, None])         # <= 1
    # columns seen from block I: earlier blocks <= 1, its own up to what
    # the block loses (held to MAX_EXP), later blocks never read
    seen = np.arange(c)[None, :] // r <= np.arange(n)[:, None]    # [n, C]
    cols = jnp.where(seen[..., None], jnp.exp(jnp.minimum(
        ref[:, None] - cum[None], MAX_EXP)), 0.0) * k[None]      # [n, C, dk]
    kk = _mm("nrd,njd->nrj", rows * k.reshape(n, r, dk), cols).reshape(c, c)
    qk = _mm("nrd,njd->nrj", rows * q.reshape(n, r, dk), cols).reshape(c, c)
    below = np.tril(np.ones((c, c), bool), -1)
    a = jnp.where(below, beta[:, None] * kk, 0.0)
    up = jnp.exp(cum)
    rhs = beta[:, None] * (v - _mm("id,dv->iv", up * k, s0))
    u = _mm("ij,jv->iv", _invert_unit_lower(a), rhs)
    # a token's own write is read undecayed: the diagonal is q . k itself
    qk = jnp.where(below, qk, jnp.where(
        np.eye(c, dtype=bool), jnp.sum(q * k, axis=-1)[:, None], 0.0))
    o = _mm("id,dv->iv", up * q, s0) + _mm("ij,jv->iv", qk, u)
    to_end = jnp.exp(cum[-1] - cum)
    s1 = jnp.exp(cum[-1])[:, None] * s0 + _mm("id,iv->dv", to_end * k, u)
    return s1, o


def deltanet_chunk(state, log_g, beta, q, k, v, slot, fresh, valid, *, layer,
                   sub=SUB):
    """The delta-rule prefill: T tokens of ONE slot from its carried-in
    state (zero when ``fresh``), the closing state written back.

    log_g : [T, H] f32, one log decay a head, or [T, H, dk], one a key
    channel; beta : [T, H] f32 in (0, 2); q, k : [T, H, dk] (per VALUE
    head, normalised and scaled); v : [T, H, dv]; tokens from ``valid`` on
    are padding and leave the state alone; sub : tokens of a sub-chunk (T
    is cut into ``T / sub`` of them, or taken whole when ``sub`` does not
    divide it). Returns (o [T, H, dv] f32, state)."""
    channels = log_g.ndim == 3
    registry.count("kda_chunk" if channels else "deltanet_chunk", "xla")
    f32 = jnp.float32
    t, h, dk = k.shape
    dv = v.shape[-1]
    live = (jnp.arange(t) < valid)[:, None]
    g = jnp.where(live[..., None] if channels else live,
                  log_g.astype(f32), 0.0)
    bf = jnp.where(live, beta.astype(f32), 0.0)
    kf = jnp.where(live[..., None], k.astype(f32), 0.0)
    c = sub if t % sub == 0 else t

    def cut(x):                    # [T, H, ...] -> [T / c, H, c, ...]
        return jnp.moveaxis(x.reshape(t // c, c, *x.shape[1:]), 2, 1)

    s0 = jnp.where(fresh, 0, state[layer, slot]).astype(f32)
    s1, o = jax.lax.scan(
        lambda s, x: jax.vmap(_sub_chunk_channels if channels
                              else _sub_chunk)(s, x), s0,
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
