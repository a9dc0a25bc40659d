"""Power-retention state ops over the serving engine's recurrent state: a
gated linear attention of degree 2 (Manifest AI's power attention,
arXiv:2507.04239), and the rotary positions its queries and keys take.

A retention layer keeps, per engine slot and key-value head, a matrix state
``S`` and a normaliser ``z`` over the degree-2 feature map ``phi`` of the
keys (``hd`` the head width)::

    phi(u) . phi(w) = (u . w)^2
    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

``phi(u)`` holds the distinct products ``u_a u_b``: ``hd (hd + 1) / 2`` of
them. **The stored layout** is by cyclic diagonals: row ``d`` (``d = 0 ..
hd / 2``) is the lane vector ``c_d * u * roll(u, d)``, the products of every
element with the one ``d`` places before it. Each unordered pair at cyclic
distance ``1 .. hd / 2 - 1`` appears once (``c_d = sqrt 2``), each at
distance ``hd / 2`` twice (``c = 1`` each), the squares once (``c_0 = 1``):
``(hd / 2 + 1) * hd`` stored terms (8,320 at 128, 0.8% over the 8,256
distinct ones; blocks of the upper triangle would store 8,704 or 9,216),
every row a whole lane vector that a kernel forms from the 128-lane ``u``
with one lane rotation, never gathered and never written to HBM. So::

    S : [layers, slots, kv_heads, hd / 2 + 1, hd (v), hd (a)]  float32
    z : [layers, slots, kv_heads, (hd / 2 + 1) * hd]           float32

with the key axis ``a`` on lanes and the value axis ``v`` on sublanes:
``phi(k)`` and ``phi(q)`` are then lane vectors broadcast down the
sublanes, and ``v`` is one number a sublane. The stacks are addressed with
``layer=`` (a traced index inside a loop over layers) and rewritten in
place, as `kernels/ssm2.py` sets out.

``retention_update`` advances every slot one token (decode), two arms under
one contract (``kernel.dispatch.retention_update.{xla|pallas}``):

- **xla** — the plainest form, ``phi`` written out; the parity reference on
  the CPU;
- **pallas** — `_update_kernel`: the stacks stay in HBM, ALIASED to the
  results; a grid cell takes one (slot, key-value head)'s diagonals tile by
  tile through VMEM, forms ``phi(k)`` and the ``phi(q)`` of the query
  heads that read this head there, rewrites the tile where it lay and adds
  its part of the read-out. Taken on a TPU.

``retention_chunk`` advances ONE slot by a chunk of a prompt: power
attention inside the chunk (``(q . k)^2`` with the gates between the two
tokens: a masked matrix product), the carried state's part from the state,
and the chunk's closing state written back. A padded token carries ``log g
= 0`` and ``k = 0``: the state passes it unchanged.

``rotary`` is the half-rotation rotary embedding at absolute positions
(``kernel.dispatch.rotary.{xla|pallas}``; the pallas arm is
`kernels/pallas/rotary.py`). ``rotary_pairs`` is the INTERLEAVED form
(element ``2 i`` pairs with ``2 i + 1``) at given frequencies, and
``yarn_inv_freq`` / ``yarn_mscale`` are YaRN's per-dimension frequencies
and attention scale (arXiv:2309.00071, as DeepSeek-V3's public modelling
code computes them).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import registry

__all__ = ["retention_update", "retention_chunk", "rotary", "rotary_pairs",
           "yarn_inv_freq", "yarn_mscale", "phi", "diagonals",
           "state_shapes"]


registry.register_op("retention_update", impls=("xla", "pallas"),
                     candidates=registry.tpu_first)
registry.register_op("retention_chunk", impls=("xla",))
registry.register_op("rotary", impls=("xla",))

_HI = jax.lax.Precision.HIGHEST
# the chunk's products with the carried state (the read-out of phi(q) and
# the addition of phi(k) v^T: 6.5 GFLOP a key-value head and 512 tokens,
# what the chunk op's time is made of) take three bf16 passes, not six: on
# a v5e 16.6 ms a launch of 8 layers against 23.5, y and the state within
# 2e-5 of the six-pass ones, where y is rounded to bf16 (4e-3) next
_CHUNK = jax.lax.Precision.HIGH
ROWS = 8             # sublanes of the update kernel's small operand


def diagonals(hd: int) -> int:
    """Cyclic diagonals stored for a head width ``hd`` (even)."""
    if hd % 2:
        raise ValueError(f"head width {hd}: the diagonal layout needs an "
                         "even one")
    return hd // 2 + 1


def state_shapes(layers: int, slots: int, kv_heads: int, hd: int):
    """(S shape, z shape) of the stored stacks."""
    nd = diagonals(hd)
    return ((layers, slots, kv_heads, nd, hd, hd),
            (layers, slots, kv_heads, nd * hd))


def _weights(hd: int):
    nd = diagonals(hd)
    return [1.0 if d in (0, nd - 1) else math.sqrt(2.0) for d in range(nd)]


def phi(u):
    """``[..., hd] -> [..., hd / 2 + 1, hd]`` float32, the stored layout:
    ``phi(u) . phi(w) = (u . w)^2`` summed over both trailing axes."""
    u = u.astype(jnp.float32)
    c = _weights(u.shape[-1])
    return jnp.stack([cd * u * jnp.roll(u, d, axis=-1)
                      for d, cd in enumerate(c)], axis=-2)


def _mm(eq, a, b, precision=_HI):
    return jnp.einsum(eq, a, b, precision=precision,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ decode

def _update_kernel(layer_ref, active_ref, aux_ref, s_ref, z_ref, so_ref,
                   zo_ref, y_ref, den_ref, phi_ref, vcol_ref, acc_ref, *,
                   groups, tile, weights):
    # one grid cell per (slot, kv head, tile of diagonals): s_ref / so_ref
    # the [tile, hd (v), hd (a)] piece of the layer's slab (the same HBM,
    # aliased); aux_ref [ROWS, hd]: k, g (on every lane), v, then the
    # query heads of this kv head; z_ref / zo_ref the slot's [H, nd * hd]
    # (the diagonals side by side on the lanes). phi of the rows is formed once
    # a (slot, head) into phi_ref [nd, ROWS, hd]; the read-out adds up in
    # acc_ref [groups, hd (v), hd (a)] and is summed over the lanes at the
    # last tile. An inactive slot's k is 0 and its g 1; what it writes
    # back is selected besides, so that its state is the same bits.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    del layer_ref
    h, j = pl.program_id(1), pl.program_id(2)
    live = active_ref[pl.program_id(0)] != 0
    hd = s_ref.shape[-1]
    nd = phi_ref.shape[0]

    @pl.when(j == 0)
    def _():
        rows = aux_ref[...]
        for d in range(nd):
            rolled = rows if d == 0 else pltpu.roll(rows, d, 1)
            phi_ref[d] = weights[d] * rows * rolled
        g = rows[1:2, :]

        @pl.when(h == 0)
        def _():
            # the slot's z block stays put while its heads pass: the
            # result starts as what came in, and each head rewrites its row
            zo_ref[...] = z_ref[...]

        # every head's row of a diagonal is one tile: this head's row is
        # picked by a mask (a row of its own cannot be addressed)
        n_h = z_ref.shape[0]
        mine = (jax.lax.broadcasted_iota(jnp.int32, (n_h, hd), 0) == h) \
            & live
        dens = [jnp.zeros((n_h, hd), jnp.float32)] * groups
        for d in range(nd):
            at = (slice(None), pl.ds(d * hd, hd))
            z_old = zo_ref[at]
            z_new = jnp.where(mine, g * z_old + phi_ref[d, 0:1, :], z_old)
            zo_ref[at] = z_new
            mask = jnp.where(mine, z_new, 0.0)
            dens = [a + mask * phi_ref[d, 3 + i:4 + i, :]
                    for i, a in enumerate(dens)]
        den_ref[...] = jnp.zeros_like(den_ref)
        for i in range(groups):
            den_ref[i:i + 1, :] = jnp.sum(dens[i], axis=0, keepdims=True)
        vcol_ref[...] = jnp.broadcast_to(rows[2:3, :], (hd, hd)).T
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = aux_ref[1:2, :]

    def rows(r, carry):
        at = pl.ds(pl.multiple_of(r * 8, 8), 8)
        vcol = vcol_ref[at, :]
        accs = [jnp.zeros((8, hd), jnp.float32)] * groups
        for d in range(tile):
            ph = phi_ref[j * tile + d]
            old = s_ref[d, at, :]
            new = jnp.where(live, g * old + vcol * ph[0:1, :], old)
            so_ref[d, at, :] = new
            accs = [a + new * ph[3 + i:4 + i, :] for i, a in enumerate(accs)]
        for i in range(groups):
            acc_ref[i, at, :] += accs[i]
        return carry

    jax.lax.fori_loop(0, hd // 8, rows, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)
        for i in range(groups):
            y_ref[i:i + 1, :] = jnp.sum(acc_ref[i].T, axis=0, keepdims=True)


def _tile(nd: int) -> int:
    """Diagonals a grid cell takes: the largest divisor of ``nd`` whose
    tile stays under 1 MiB at a head width of 128."""
    return max(t for t in range(1, 17) if nd % t == 0)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _pallas_update(state, z, layer, active, aux, *, groups, interpret):
    """(num [B, H, ROWS, hd], den parts [B, H, ROWS, hd], state, z): the
    kernel over the stored stacks at a traced layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.core.autograd import x64_off_scope
    _, b, h, nd, hd, _ = state.shape
    tile = _tile(nd)
    # a tile in and out, each double-buffered, beside the scratch
    vmem = 4 * tile * hd * hd * 4 + (nd * ROWS + (groups + 1) * hd) * hd * 4
    slab = pl.BlockSpec((None, None, None, tile, hd, hd),
                        lambda i, k, j, lyr, act: (lyr[0], i, k, j, 0, 0))
    zs = pl.BlockSpec((None, None, h, nd * hd),
                      lambda i, k, j, lyr, act: (lyr[0], i, 0, 0))
    small = pl.BlockSpec((None, None, ROWS, hd),
                         lambda i, k, j, *_: (i, k, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, h, nd // tile),
        in_specs=[small, slab, zs], out_specs=[slab, zs, small, small],
        scratch_shapes=[pltpu.VMEM((nd, ROWS, hd), jnp.float32),
                        pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((groups, hd, hd), jnp.float32)])
    small_out = jax.ShapeDtypeStruct((b, h, ROWS, hd), jnp.float32)
    with x64_off_scope():
        new, z_new, num, den = pl.pallas_call(
            functools.partial(_update_kernel, groups=groups, tile=tile,
                              weights=_weights(hd)),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(z.shape, z.dtype),
                       small_out, small_out],
            # operands 3 and 4 (after the two prefetched scalars and the
            # small operand) are the stacks
            input_output_aliases={3: 0, 4: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=max(vmem + (8 << 20), 32 << 20)),
            interpret=interpret,
        )(layer.reshape(1), active.astype(jnp.int32), aux, state, z)
    return num, den, new, z_new


def retention_update(state, z, log_g, q, k, v, active, *, layer, eps=1e-6,
                     impl=None, interpret=None):
    """The retention decode update: one token for every slot.

    state, z : the stored stacks (module docstring); log_g : [B, H] f32
    (<= 0); q : [B, Hq, hd] (query head i reads kv head i // (Hq / H));
    k, v : [B, H, hd]; active : [B] bool — an inactive slot's state is left
    as it was; impl : ``xla`` / ``pallas`` / None (pallas on a TPU).
    Returns (y [B, Hq, hd] f32, state, z): the read-out is of the state
    AFTER this token, scaled by 1 / hd on the query side."""
    impl = registry.dispatch("retention_update", forced=impl)
    b, hq, hd = q.shape
    h = k.shape[1]
    grp = hq // h
    f32 = jnp.float32
    live = active[:, None]
    g = jnp.where(live, jnp.exp(log_g.astype(f32)), 1.0)        # [B, H]
    kf = jnp.where(live[..., None], k.astype(f32), 0.0)
    qf = q.astype(f32).reshape(b, h, grp, hd) / hd
    vf = v.astype(f32)
    if impl == "pallas" and grp + 3 <= ROWS and state.dtype == f32:
        if interpret is None:
            from paddle_tpu.kernels.pallas._compat import default_interpret
            interpret = default_interpret()
        aux = jnp.concatenate(
            [kf[:, :, None], jnp.broadcast_to(g[..., None, None],
                                              (b, h, 1, hd)),
             vf[:, :, None], qf,
             jnp.zeros((b, h, ROWS - 3 - grp, hd), f32)], axis=2)
        num, den, state, z = _pallas_update(
            state, z, jnp.asarray(layer, jnp.int32), active, aux, groups=grp,
            interpret=bool(interpret))
        num, den = num[:, :, :grp], den[:, :, :grp].sum(-1)
    else:
        pk, pq = phi(kf), phi(qf)              # [B,H,nd,hd], [B,H,G,nd,hd]
        old = state[layer].astype(f32)
        new = g[..., None, None, None] * old \
            + pk[..., None, :] * vf[..., None, :, None]
        z_new = g[..., None] * z[layer].astype(f32) \
            + pk.reshape(b, h, -1)
        keep = active[:, None, None]
        new = jnp.where(keep[..., None, None], new.astype(state.dtype),
                        state[layer])
        z_new = jnp.where(keep, z_new.astype(z.dtype), z[layer])
        num = _mm("bhgda,bhdva->bhgv", pq, new.astype(f32))
        den = _mm("bhgda,bhda->bhg", pq,
                  z_new.astype(f32).reshape(pk.shape))
        state = state.at[layer].set(new)
        z = z.at[layer].set(z_new)
    y = num / (den[..., None] + eps)
    return y.reshape(b, hq, hd), state, z


# ----------------------------------------------------------------- prefill

def retention_chunk(state, z, log_g, q, k, v, slot, fresh, valid, *, layer,
                    eps=1e-6):
    """The retention prefill: T tokens of ONE slot from its carried-in
    state (zero when ``fresh``), the closing state written back.

    log_g : [T, H] f32; q : [T, Hq, hd]; k, v : [T, H, hd]; tokens from
    ``valid`` on are padding and leave the state alone. Returns (y [T, Hq,
    hd] f32, state, z)."""
    registry.count("retention_chunk", "xla")
    t, hq, hd = q.shape
    h = k.shape[1]
    grp = hq // h
    f32 = jnp.float32
    live = (jnp.arange(t) < valid)[:, None]
    lg = jnp.where(live, log_g.astype(f32), 0.0)
    kf = jnp.where(live[..., None], k.astype(f32), 0.0)
    vf = v.astype(f32)
    qf = q.astype(f32).reshape(t, h, grp, hd) / hd
    cum = jnp.cumsum(lg, axis=0)                                # [T, H]
    # inside the chunk: a[t, s] = (q_t . k_s)^2 exp(cum_t - cum_s), s <= t
    causal = jnp.tril(jnp.ones((t, t), bool))
    seg = jnp.where(causal, cum.T[:, :, None] - cum.T[:, None, :], -jnp.inf)
    a = _mm("thgd,shd->hgts", qf, kf) ** 2 * jnp.exp(seg)[:, None]
    num = _mm("hgts,shv->thgv", a, vf)
    den = a.sum(-1).transpose(2, 0, 1)                          # [T, H, G]
    s0 = jnp.where(fresh, 0, state[layer, slot]).astype(f32)
    z0 = jnp.where(fresh, 0, z[layer, slot]).astype(f32).reshape(
        s0.shape[:2] + (hd,))
    from_start = jnp.exp(cum)                                   # [T, H]
    to_end = jnp.exp(cum[-1][None] - cum)

    def head(x):
        # one kv head at a time: phi of a chunk's queries is [T, G, nd, hd]
        # (85 MB at 512 x 5 x 65 x 128), of all heads' at once 0.68 GB
        qh, kh, vh, s_h, z_h, up, down = x
        pq, pk = phi(qh), phi(kh)
        carried = _mm("tgda,dva->tgv", pq, s_h, _CHUNK) * up[:, None, None]
        norm = _mm("tgda,da->tg", pq, z_h, _CHUNK) * up[:, None]
        s_h = up[-1] * s_h + _mm("tv,tda->dva", vh * down[:, None], pk,
                                 _CHUNK)
        z_h = up[-1] * z_h + _mm("t,tda->da", down, pk, _CHUNK)
        return carried, norm, s_h, z_h

    carried, norm, s1, z1 = jax.lax.map(head, (
        qf.swapaxes(0, 1), kf.swapaxes(0, 1), vf.swapaxes(0, 1), s0, z0,
        from_start.T, to_end.T))
    num = num + carried.transpose(1, 0, 2, 3)
    den = den + norm.transpose(1, 0, 2)
    y = num / (den[..., None] + eps)
    state = state.at[layer, slot].set(s1.astype(state.dtype))
    z = z.at[layer, slot].set(z1.reshape(h, -1).astype(z.dtype))
    return y.reshape(t, hq, hd), state, z


# ------------------------------------------------------------------ rotary

def rotary(x, positions, theta):
    """Half-rotation rotary embedding: ``x`` [T, heads, hd] at absolute
    ``positions`` [T]; the pair of element ``i < hd / 2`` is ``i + hd / 2``.
    Returns float32."""
    registry.count("rotary", "xla")
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]    # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow, original_max):
    """YaRN's ``dim / 2`` rotary frequencies: a dimension that turns more
    than ``beta_fast`` times over the original context keeps its frequency,
    one that turns fewer than ``beta_slow`` times has it divided by
    ``factor``, a linear ramp between. A numpy float32 vector: a constant
    of the program."""
    import numpy as np

    def turns_dim(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention scale ``0.1 mscale ln(factor) + 1`` (1 at a factor
    of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_pairs(x, positions, inv_freq):
    """Interleaved rotary embedding: ``x`` [T, heads, hd] at absolute
    ``positions`` [T]; elements ``2 i`` and ``2 i + 1`` turn by
    ``positions * inv_freq[i]``. Returns float32, the pairs where they
    were."""
    registry.count("rotary", "xla")
    t, n, hd = x.shape
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]          # [T, 1, hd/2]
    x = x.astype(jnp.float32).reshape(t, n, hd // 2, 2)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(t, n, hd)
