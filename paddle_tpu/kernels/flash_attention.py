"""Flash attention for TPU.

Counterpart of `paddle/fluid/operators/fused/fused_attention_op.cu` — which is
non-flash (`fmha_ref.h`), so this is strictly beyond reference parity (SURVEY.md
§5.7 requires it). Strategy:

1. Pallas TPU flash kernel (jax.experimental.pallas.ops.tpu.flash_attention) when
   shapes are TPU-tileable (seq multiple of block, head_dim aligned);
2. otherwise a blockwise online-softmax attention in pure lax (still O(S) memory
   via jax.checkpoint-friendly scan), which XLA fuses well.

Layout note: paddle uses [B, S, H, D]; the pallas op uses [B, H, S, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.autograd import x64_off_scope

_PALLAS_OK = None


def _try_pallas():
    global _PALLAS_OK, _fa_mod
    if _PALLAS_OK is None:
        from jax.experimental.pallas.ops.tpu import flash_attention as _m
        _fa_mod = _m
        _PALLAS_OK = jax.default_backend() == "tpu"
    return _PALLAS_OK


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_flash(q, k, v, causal, sm_scale):
    out, _ = _pallas_flash_fwd(q, k, v, causal, sm_scale)
    return out


def _pallas_flash_fwd(q, k, v, causal, sm_scale):
    _try_pallas()
    bs = _fa_mod.BlockSizes.get_default(
        q.shape[0], q.shape[1], q.shape[2], k.shape[2], q.shape[3])
    with x64_off_scope():
        o, res = _fa_mod._flash_attention_fwd(
            q, k, v, None, None, False, causal, sm_scale, bs, False)
    return o, res


def _pallas_flash_bwd(causal, sm_scale, res, do):
    _try_pallas()
    q, k = res[0], res[1]
    bs = _fa_mod.BlockSizes.get_default(
        q.shape[0], q.shape[1], q.shape[2], k.shape[2], q.shape[3])
    with x64_off_scope():
        dq, dk, dv, _, _ = _fa_mod._flash_attention_bwd(
            False, causal, sm_scale, bs, False, res, do)
    return dq, dk, dv


_pallas_flash.defvjp(_pallas_flash_fwd, _pallas_flash_bwd)


def _blockwise_attention(q, k, v, causal, scale, block_k=512):
    """Online-softmax attention scanning over K blocks (lax fallback)."""
    # q,k,v: [B, H, S, D]
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    q = q * s
    nblocks = max((Sk + block_k - 1) // block_k, 1)
    pad = nblocks * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, nblocks, block_k, D)
    vb = v.reshape(B, H, nblocks, block_k, D)
    q_idx = jnp.arange(Sq)

    def body(carry, blk):
        m_prev, l_prev, acc = carry
        kk, vv, base = blk
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                            preferred_element_type=jnp.float32)
        kpos = base + jnp.arange(block_k)
        valid = kpos < Sk
        if causal:
            valid = valid[None, :] & (kpos[None, :] <= (
                q_idx + (Sk - Sq))[:, None])
            logits = jnp.where(valid[None, None], logits, -jnp.inf)
        else:
            logits = jnp.where(valid[None, None, None], logits, -jnp.inf)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    bases = jnp.arange(nblocks) * block_k
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), bases))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


_SPLASH_CACHE: dict = {}


def _splash_kernel(n_heads, S, causal):
    """Cached Splash (Pallas) MHA kernel — the production TPU flash attention.
    Created under ensure_compile_time_eval so the precomputed mask-info arrays
    stay concrete even when first touched inside an abstract capture probe."""
    key = (n_heads, S, causal)
    if key not in _SPLASH_CACHE:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as sm)
        with jax.ensure_compile_time_eval(), x64_off_scope():
            mask = sm.MultiHeadMask(
                [sm.CausalMask((S, S)) if causal else sm.FullMask((S, S))
                 for _ in range(n_heads)])
            _SPLASH_CACHE[key] = sk.make_splash_mha(
                mask, head_shards=1, q_seq_shards=1)
    return _SPLASH_CACHE[key]


def _splash_attention(q, k, v, causal, scale):
    """q,k,v: [B,H,S,D]; caller must hold an x64-off scope across fwd+bwd
    traces (see autograd.apply(x64_off=True))."""
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    kern = _splash_kernel(q.shape[1], q.shape[2], causal)
    return jax.vmap(kern)((q * s).astype(q.dtype), k, v)


def _qblocks(S):
    """Static q-block size (unrolled python loop — lax.scan variants hit
    pathological compile paths on the current TPU toolchain).

    256 measured best on v5e (round-4 sweep, GPT-2s B16/S1024, fwd+bwd
    per-12-layers: bq=1024 74.6 ms, 512 54.7, 256 48.5, 128 50.3): small
    blocks make the causal ``kend`` truncation real — with bq == S the whole
    [S, S] logits block is computed then half masked away, while bq=256 skips
    the upper-triangular blocks' FLOPs and HBM traffic entirely. Whole-step
    effect: 101.0k -> 120.7k tok/s (MFU 0.383 -> 0.458). Above 4k the block
    size grows back to 1024 to bound the unrolled block count (compile
    time)."""
    return min(256, S) if S <= 4096 else 1024


# bwd may use a different q-block than fwd: each bwd block pays a padded
# dk/dv accumulation over the FULL K length, so fewer/larger blocks trade
# upper-triangular logit FLOPs for less accumulator traffic. Swept r5
# (GPT-2s B16/S1024 whole step): bwd 512 -> 149.2 ms, 128 -> 152.7 ms vs
# 130.5 ms at the shared 256 — the split LOSES both ways; 256 is a sharp
# joint optimum. None = same as fwd (kept as an experiment hook).
_QBLOCKS_BWD = None


def _qblocks_bwd(S):
    return _QBLOCKS_BWD if _QBLOCKS_BWD else _qblocks(S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _xla_flash(q, k, v, causal, scale):
    out, _ = _xla_flash_fwd(q, k, v, causal, scale)
    return out


def _block_logits(qb, k, scale):
    # [B,H,Bq,D] x [B,H,Sk,D] -> [B,H,Bq,Sk]; bf16 inputs materialize bf16
    # logits (halves the S^2 HBM traffic, reductions still accumulate f32)
    acc = jnp.bfloat16 if qb.dtype == jnp.bfloat16 else jnp.float32
    return jax.lax.dot_general(
        qb * scale, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=acc)


def _causal_mask(bq, kend, q0, sq_total, sk_total):
    """kend: K prefix length kept for this q block (absolute positions 0..kend);
    the causal offset is measured against the FULL k length (decode caches make
    Sk > Sq)."""
    qpos = q0 + jnp.arange(bq)
    kpos = jnp.arange(kend)
    return kpos[None, :] <= (qpos[:, None] + (sk_total - sq_total))


def _xla_flash_fwd(q, k, v, causal, scale):
    """Flash-style attention in pure XLA: the [S,S] probability matrix exists
    only transiently inside each q-block; residuals are (q, k, v, out, lse).
    Counterpart of the reference's fused_attention fmha path, but online-safe
    (ref `operators/fused/fused_attention_op.cu` is non-flash)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = _qblocks(Sq)
    outs, lses = [], []
    for q0 in range(0, Sq, bq):
        qb = q[:, :, q0:q0 + bq]
        # causal: later K positions can't be attended by this q block — slice
        # them off entirely (real FLOP/traffic saving, not just masking).
        # Clamp to >= 1: Sq > Sk causal rows with no visible key keep the
        # degenerate single-block behavior (all-masked -> uniform weights)
        kend = min(max(q0 + bq + (Sk - Sq), 1), Sk) if causal else Sk
        kb, vb = k[:, :, :kend], v[:, :, :kend]
        logits = _block_logits(qb, kb, s)                   # bf16 [B,H,Bq,kend]
        if causal:
            m = _causal_mask(qb.shape[2], kend, q0, Sq, Sk)
            logits = jnp.where(m[None, None], logits,
                               jnp.asarray(-1e30, logits.dtype))
        mx = jnp.max(logits, axis=-1, keepdims=True)        # exact in bf16
        z = logits.astype(jnp.float32) - mx.astype(jnp.float32)
        l = jnp.sum(jnp.exp(z), axis=-1, keepdims=True)     # f32 accumulation
        p = jnp.exp(z).astype(v.dtype)                      # bf16 for the MXU
        acc = jax.lax.dot_general(
            p, vb, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        outs.append((acc / l).astype(q.dtype))              # normalize post-dot
        lses.append((mx.astype(jnp.float32) + jnp.log(l))[..., 0])
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    lse = lses[0] if len(lses) == 1 else jnp.concatenate(lses, axis=2)
    return out, (q, k, v, out, lse)


def _xla_flash_bwd(causal, scale, res, do):
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = _qblocks_bwd(Sq)
    dqs = []
    dk = jnp.zeros((B, H, Sk, D), jnp.float32)
    dv = jnp.zeros((B, H, Sk, D), jnp.float32)
    for q0 in range(0, Sq, bq):
        qb = q[:, :, q0:q0 + bq]
        dob = do[:, :, q0:q0 + bq]
        ob = out[:, :, q0:q0 + bq]
        lseb = lse[:, :, q0:q0 + bq]
        kend = min(max(q0 + bq + (Sk - Sq), 1), Sk) if causal else Sk
        kb, vb = k[:, :, :kend], v[:, :, :kend]
        logits = _block_logits(qb, kb, s)
        if causal:
            m = _causal_mask(qb.shape[2], kend, q0, Sq, Sk)
            logits = jnp.where(m[None, None], logits,
                               jnp.asarray(-1e30, logits.dtype))
        # p recomputed from lse: [B,H,Bq,kend] bf16, never a residual
        p = jnp.exp(logits.astype(jnp.float32) -
                    lseb[..., None]).astype(v.dtype)
        # dv += p^T do ; dp = do v^T ; ds = p*(dp - di) ; dq = ds k ; dk += ds^T q
        dvc = jax.lax.dot_general(
            p, dob, (((2,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            dob, vb, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=(jnp.bfloat16 if v.dtype == jnp.bfloat16
                                    else jnp.float32))
        di = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                     axis=-1, keepdims=True)
        ds = (p.astype(jnp.float32) *
              (dp.astype(jnp.float32) - di)).astype(q.dtype)
        dqs.append(jax.lax.dot_general(
            ds, kb, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * s)
        dkc = jax.lax.dot_general(
            ds, qb, (((2,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * s
        if kend == Sk:
            dk = dk + dkc
            dv = dv + dvc
        else:
            pad = ((0, 0), (0, 0), (0, Sk - kend), (0, 0))
            dk = dk + jnp.pad(dkc, pad)
            dv = dv + jnp.pad(dvc, pad)
    dq = dqs[0] if len(dqs) == 1 else jnp.concatenate(dqs, axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_xla_flash.defvjp(_xla_flash_fwd, _xla_flash_bwd)


def _dense_attention(q, k, v, causal, scale):
    """Full-materialization SDPA: the [B, H, Sq, Sk] scores exist in HBM
    (bf16 when inputs are bf16) and XLA autodiffs it. At moderate S the
    S^2 tensor fits easily and the single fused softmax beats chunked
    flash's loop overhead — the autotuner decides per shape."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    acc = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    logits = jax.lax.dot_general(
        q * s, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=acc)
    if causal:
        qpos = jnp.arange(Sq)
        kpos = jnp.arange(Sk)
        mask = kpos[None, :] <= (qpos[:, None] + (Sk - Sq))
        logits = jnp.where(mask[None, None], logits,
                           jnp.asarray(-1e30, logits.dtype))
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jax.lax.dot_general(
        p, v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=v.dtype)


def _impl_call(impl, qt, kt, vt, causal, scale, tileable):
    """Execute one named implementation on [B, H, S, D] arrays."""
    if impl == "dense":
        return _dense_attention(qt, kt, vt, causal, scale)
    if impl == "splash" and tileable:
        return _splash_attention(qt, kt, vt, causal, scale)
    if impl == "mosaic" and tileable:
        sm = scale if scale is not None else 1.0 / math.sqrt(qt.shape[-1])
        return _pallas_flash(qt, kt, vt, causal, sm)
    if impl == "authored":
        # the in-repo Pallas kernels (kernels/pallas/flash_attention.py),
        # forward AND backward. They read [B, S, H * D]: the swap of axes
        # that brought these arrays here and the one they make cancel
        from paddle_tpu.kernels.pallas import flash_attention as _authored
        return _authored(qt, kt, vt, causal=causal, sm_scale=scale)
    return _xla_flash(qt, kt, vt, causal, scale)


def flash_attention_fn(causal=False, scale=None):
    """Returns a pure fn(q, k, v) on paddle-layout [B, S, H, D] tensors."""

    def fn(q, k, v):
        from paddle_tpu.distributed.mesh import get_mesh
        from paddle_tpu.framework.flags import flag_value
        from paddle_tpu.kernels import registry
        # -> [B, H, S, D]
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        S, D = qt.shape[2], qt.shape[3]
        tileable = (_try_pallas() and S % 128 == 0 and D % 64 == 0
                    and S == kt.shape[2]
                    and qt.dtype in (jnp.float32, jnp.bfloat16))
        # under an installed multi-device mesh this trace becomes a program
        # GSPMD partitions, which the Pallas arms cannot join
        mesh = get_mesh()
        partitioned = mesh is not None and mesh.size > 1

        def winner():
            # measured selection, cached per (backend, shape, dtype,
            # causal) — ref phi/kernels/autotune. Runs eagerly at trace
            # time; the winner string is baked into this trace (the
            # program cache keys on the flag + shapes, so retunes key new
            # programs).
            from paddle_tpu.kernels.autotune import flash_winner
            return flash_winner(
                tuple(qt.shape), tuple(kt.shape), qt.dtype, causal,
                tileable,
                lambda i, q_, k_, v_: _impl_call(i, q_, k_, v_, causal,
                                                 scale, tileable),
                partitioned=partitioned)

        impl = registry.dispatch(
            "flash_attention", forced=flag_value("tpu_flash_impl"),
            ctx={"tileable": tileable, "shape_q": tuple(qt.shape),
                 "shape_k": tuple(kt.shape), "partitioned": partitioned},
            winner=winner)
        out = _impl_call(impl, qt, kt, vt, causal, scale, tileable)
        return jnp.swapaxes(out, 1, 2)

    return fn
