"""Flash attention for TPU.

Counterpart of `paddle/fluid/operators/fused/fused_attention_op.cu` — which is
non-flash (`fmha_ref.h`), so this is strictly beyond reference parity (SURVEY.md
§5.7 requires it). The arms of op ``flash_attention``, chosen per signature
by `kernels/registry.py` under ``FLAGS_tpu_flash_impl``:

- **authored** — the in-repo Pallas kernels, forward and backward
  (`kernels/pallas/flash_attention.py`): a block's scores live in VMEM only.
  Offered on a TPU outside a partitioned program, at every shape;
- **xla** — blockwise attention in plain XLA under a custom VJP (`_xla_flash`):
  the [S, S] probabilities exist only transiently inside each q-block. The
  one arm off-TPU and in a partitioned program, where nothing is measured.

Layout note: paddle uses [B, S, H, D]; the arms take [B, H, S, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import registry


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


def _impl_call(impl, qt, kt, vt, causal, scale):
    """Execute one named implementation on [B, H, S, D] arrays."""
    if impl == "authored":
        # the in-repo Pallas kernels (kernels/pallas/flash_attention.py),
        # forward AND backward. They read [B, S, H * D]: the swap of axes
        # that brought these arrays here and the one they make cancel
        from paddle_tpu.kernels.pallas import flash_attention as _authored
        return _authored(qt, kt, vt, causal=causal, sm_scale=scale)
    return _xla_flash(qt, kt, vt, causal, scale)


def _candidates(ctx):
    """Impl names viable for one call (by name, never by execution).
    ``partitioned``: the call is traced into a program GSPMD splits over
    a multi-device mesh. A Mosaic kernel cannot be partitioned
    automatically (jax refuses to lower it outside a ``shard_map``), so
    only the XLA arm is viable there."""
    if ctx.get("backend", registry.backend()) == "tpu" \
            and not ctx.get("partitioned", False):
        return ["xla", "authored"]
    return ["xla"]


registry.register_op("flash_attention", impls=("xla", "authored"),
                     candidates=_candidates)


def _selection(shape_q, shape_k, dtype, causal, scale, partitioned):
    """``(key, measure)`` of one signature for `registry.dispatch`: its key
    in the winner table — ("flash", backend, shape_q, shape_k, dtype,
    causal[, "partitioned"]), shapes [B, H, S, D] — and ``measure(impl) ->
    seconds`` of the named arm's forward and backward over seeded arrays.
    A partitioned call has narrower candidates and keys its own entry."""
    key = ("flash", registry.backend(), tuple(shape_q), tuple(shape_k),
           str(dtype), bool(causal)) \
        + (("partitioned",) if partitioned else ())
    state = {}

    def measure(impl):
        if not state:
            rng = np.random.RandomState(0)

            def seq_major(shape):
                b, h, s, d = shape
                return jnp.asarray(rng.randn(b, s, h, d)
                                   .astype(np.float32)).astype(dtype)
            state["args"] = (seq_major(shape_q), seq_major(shape_k),
                             seq_major(shape_k))
        # the call site's arrays are [B, S, H, D] and reach the impl
        # through a swap of axes (`flash_attention_fn`): the measured step
        # does the same, so an arm is timed with the relayouts it would
        # cost there, or save
        step = jax.jit(jax.grad(
            lambda q_, k_, v_: (
                _impl_call(impl, *(jnp.swapaxes(x, 1, 2)
                                   for x in (q_, k_, v_)), causal, scale)
                .astype(jnp.float32) ** 2).sum(), argnums=(0, 1, 2)))
        # a layer of a step program runs at the device's pace. A single
        # launch's wall time is a third the host's at the training cell's
        # shape (a millisecond of 3 to 4), and a busy host once read three
        # launches in a row 1.8 ms slow and hid an arm 1.5x faster
        return registry.measure(step, state["args"], calls=8)

    return key, measure


def flash_attention_fn(causal=False, scale=None):
    """Returns a pure fn(q, k, v) on paddle-layout [B, S, H, D] tensors."""

    def fn(q, k, v):
        from paddle_tpu.distributed.mesh import get_mesh
        from paddle_tpu.framework.flags import flag_value
        # -> [B, H, S, D]
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        # under an installed multi-device mesh this trace becomes a program
        # GSPMD partitions, which the Pallas arm cannot join
        mesh = get_mesh()
        partitioned = mesh is not None and mesh.size > 1
        # measured selection, cached per (backend, shape, dtype, causal) —
        # ref phi's AutoTuneCache. Runs eagerly at trace time; the winner
        # string is baked into this trace (the program cache keys on the
        # flag + shapes, so retunes key new programs)
        key, measure = _selection(qt.shape, kt.shape, qt.dtype, causal,
                                  scale, partitioned)
        impl = registry.dispatch(
            "flash_attention", forced=flag_value("tpu_flash_impl"),
            ctx={"partitioned": partitioned}, key=key, measure=measure)
        out = _impl_call(impl, qt, kt, vt, causal, scale)
        return jnp.swapaxes(out, 1, 2)

    return fn
