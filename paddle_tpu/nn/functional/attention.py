"""Attention functionals.

`scaled_dot_product_attention` routes to the Pallas flash-attention kernel on TPU
when shapes allow (ref counterpart: `paddle/fluid/operators/fused/fused_attention_op.cu`
which uses non-flash fmha_ref.h — flash here is strictly better), with an XLA
fallback that fuses fine for short sequences.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.autograd import apply
from paddle_tpu.kernels import registry
from paddle_tpu.ops.common import ensure_tensor


def _sp_candidates(ctx):
    cands = ["ring"]
    if ctx.get("heads", 1) % max(ctx.get("sp", 1), 1) == 0:
        cands.append("ulysses")
    return cands


registry.register_op("sp_attention", impls=("ring", "ulysses"),
                     candidates=_sp_candidates)


def _sdpa_xla(q, k, v, mask, dropout_p, is_causal, scale, rng_key=None):
    # q,k,v: [B, S, H, D] (paddle convention)
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
    if is_causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and rng_key is not None:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros_like(probs))
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def sequence_parallel_attention(query, key, value, is_causal=True, scale=None,
                                impl="ring", dropout_p=0.0, training=True,
                                name=None):
    """Context-parallel attention over the 'sp' mesh axis — the ONE
    authoritative gate for ring/Ulysses dispatch (beyond-reference feature,
    SURVEY §5.7). [B, S, H, D] layout. Falls back to
    scaled_dot_product_attention when no sp axis is active; RAISES on
    configurations that would silently degrade (attention dropout in training,
    non-divisible seq/heads) instead of quietly gathering full K/V."""
    from paddle_tpu.distributed.mesh import get_mesh
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    mesh = get_mesh()
    sp = (mesh.shape.get("sp", 1) if mesh is not None
          and "sp" in mesh.axis_names else 1)
    if sp <= 1 or impl in (None, "none"):
        return scaled_dot_product_attention(
            query, key, value, dropout_p=dropout_p, is_causal=is_causal,
            scale=scale, training=training)
    if dropout_p > 0.0 and training:
        raise RuntimeError(
            "sequence-parallel attention does not support attention dropout "
            "(set attention_dropout=0, or sp_attention='none'); refusing to "
            "silently fall back to full-K/V attention")
    S, H = query.shape[1], query.shape[2]
    if S % sp:
        raise ValueError(f"sequence length {S} not divisible by sp={sp}")
    if impl == "ulysses" and H % sp:
        raise ValueError(f"ulysses needs heads ({H}) divisible by sp ({sp})")
    if impl not in ("ring", "ulysses", "auto"):
        raise ValueError(
            f"unknown sequence-parallel attention impl {impl!r}; "
            "choose 'ring', 'ulysses', or 'none'")
    from paddle_tpu.kernels.ring_attention import (
        ring_attention, ulysses_attention)
    # registry-routed (kernels/registry.py): the op validates viability
    # (ulysses needs heads % sp == 0) and counts
    # kernel.dispatch.sp_attention.{ring|ulysses}; "auto" picks the first
    # viable candidate (ring — correct for every shape)
    impl = registry.dispatch("sp_attention", forced=impl,
                             ctx={"heads": H, "sp": sp})
    kern = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]

    def prim(qa, ka, va):
        qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (qa, ka, va))
        o = kern(qt, kt, vt, is_causal, scale, mesh)
        return jnp.swapaxes(o, 1, 2)

    return apply(prim, query, key, value, op_name=f"{impl}_attention")


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, scale=None, training=True,
                                 name=None):
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    use_flash = attn_mask is None and dropout_p == 0.0
    if use_flash:
        # no blanket except here: a broken kernel must surface, not silently
        # fall back to O(S^2)-materializing attention (cost a whole round once)
        from paddle_tpu.kernels.flash_attention import flash_attention_fn
        fn = flash_attention_fn(causal=is_causal, scale=scale)
        return apply(fn, query, key, value, op_name="flash_attention",
                     x64_off=True)
    ts = [query, key, value]
    has_mask = attn_mask is not None
    if has_mask:
        ts.append(ensure_tensor(attn_mask))
    use_drop = dropout_p > 0.0 and training
    if use_drop:
        # rng key split OUTSIDE the prim (the stateful generator advance must
        # happen at the framework level so capture threads it as state)
        from paddle_tpu.ops.random import default_generator
        from paddle_tpu.core.tensor import Tensor
        ts.append(Tensor(default_generator().next_key(), _internal=True))

    def prim(q, k, v, *rest):
        rest = list(rest)
        rkey = rest.pop() if use_drop else None
        m = rest[0] if rest else None
        return _sdpa_xla(q, k, v, m, dropout_p if use_drop else 0.0,
                         is_causal, scale, rng_key=rkey)

    return apply(prim, *ts, op_name="scaled_dot_product_attention")


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    x = ensure_tensor(x)
    ml = int(maxlen) if maxlen is not None else int(jnp.max(x._data))
    from paddle_tpu.core import dtype as dtype_mod
    d = dtype_mod.convert_dtype(dtype)
    return apply(lambda a: (jnp.arange(ml) < a[..., None]).astype(d), x,
                 op_name="sequence_mask")
