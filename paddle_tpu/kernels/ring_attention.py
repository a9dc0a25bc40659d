"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

BEYOND the reference (SURVEY §5.7: the snapshot has no sequence parallelism at
all — its long-sequence story is recompute + recompute_hybrid). Two schemes,
both over the 'sp' mesh axis:

- **Ring attention** (`ring_attention`): Q stays put, K/V blocks circulate the
  ring with `jax.lax.ppermute` while each rank accumulates its online-softmax
  partials — attention memory per rank stays O(S/P * S/P) per step and no rank
  ever materializes the full K/V, so max sequence length scales linearly with
  the ring size. Known inefficiency: under causal masking the contiguous
  block-to-rank assignment leaves early ranks computing fully-masked steps
  (~2x causal FLOPs); a zigzag/striped token permutation (balanced early+late
  positions per rank) would fix the imbalance but requires a global reorder of
  the sequence around the attention call — future work.
- **Ulysses** (`ulysses_attention`): `lax.all_to_all` reshards sequence->heads,
  runs dense flash attention on full sequences of H/P heads per rank, and
  reshards back — cheaper collectives when H >= P.

Both are pure-XLA (partial-manual shard_map), composable with dp/mp axes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def _online_update(m, l, acc, logits, vb):
    """One online-softmax accumulation step (f32 stats)."""
    m_c = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_c)
    # renormalize previous partials; fully-masked rows keep m=-inf safely
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - safe_m[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention(q, k, v, causal, scale, mesh, axis="sp"):
    """q,k,v: [B, H, S, D] with S sharded over `axis`. Returns [B, H, S, D]
    with the same sharding. Custom VJP: the backward pass is a SECOND ring that
    recomputes each block's probabilities from the saved logsumexp and
    circulates dK/dV accumulators with the K/V blocks — per-rank residuals are
    O(S/P), never the per-step probability matrices a plain jax.vjp of the
    unrolled loop would save."""
    out, _ = _ring_fwd(q, k, v, causal, scale, mesh, axis)
    return out


def _ring_fwd(q, k, v, causal, scale, mesh, axis):
    n = mesh.shape[axis]
    S = q.shape[2]
    s_local = S // n
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]

    def per_rank(qb, kb, vb):
        r = jax.lax.axis_index(axis)
        B, H, sl, D = qb.shape
        qpos = r * s_local + jnp.arange(sl)
        m = jnp.full((B, H, sl), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, H, sl), jnp.float32)
        acc = jnp.zeros((B, H, sl, D), jnp.float32)
        kc, vc = kb, vb
        for step in range(n):
            blk = (r - step) % n                     # block id currently held
            logits = jax.lax.dot_general(
                qb * s, kc, (((3,), (3,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)  # [B,H,sl,sl]
            if causal:
                kpos = blk * s_local + jnp.arange(sl)
                mask = kpos[None, :] <= qpos[:, None]
                logits = jnp.where(mask[None, None], logits, -jnp.inf)
            m, l, acc = _online_update(m, l, acc, logits, vc)
            if step < n - 1:
                kc = jax.lax.ppermute(kc, axis, perm)
                vc = jax.lax.ppermute(vc, axis, perm)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return out.astype(qb.dtype), lse

    spec = P(None, None, axis, None)
    spec3 = P(None, None, axis)
    f = jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=(spec, spec3), axis_names={axis},
                      check_vma=True)
    out, lse = f(q, k, v)
    return out, (q, k, v, out, lse)


def _ring_bwd(causal, scale, mesh, axis, res, do):
    q, k, v, out, lse = res
    n = mesh.shape[axis]
    S = q.shape[2]
    s_local = S // n
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]

    def per_rank(qb, kb, vb, ob, lseb, dob):
        r = jax.lax.axis_index(axis)
        B, H, sl, D = qb.shape
        qpos = r * s_local + jnp.arange(sl)
        di = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                     axis=-1, keepdims=True)         # [B,H,sl,1]
        dq = jnp.zeros((B, H, sl, D), jnp.float32)
        kc, vc = kb, vb
        dkc = jnp.zeros((B, H, sl, D), jnp.float32)
        dvc = jnp.zeros((B, H, sl, D), jnp.float32)
        for step in range(n):
            blk = (r - step) % n
            logits = jax.lax.dot_general(
                qb * s, kc, (((3,), (3,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)
            if causal:
                kpos = blk * s_local + jnp.arange(sl)
                mask = kpos[None, :] <= qpos[:, None]
                logits = jnp.where(mask[None, None], logits, -jnp.inf)
            p = jnp.exp(logits - lseb[..., None])    # masked lanes -> 0
            dvc = dvc + jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((2,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                dob, vc, (((3,), (3,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32)
            ds = p * (dp - di)                       # [B,H,sl,sl]
            dq = dq + jax.lax.dot_general(
                ds.astype(qb.dtype), kc, (((3,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32) * s
            dkc = dkc + jax.lax.dot_general(
                ds.astype(qb.dtype), qb, (((2,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32) * s
            # rotate blocks AND their grad accumulators; after n rotations the
            # accumulated dK/dV are home again
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            dkc = jax.lax.ppermute(dkc, axis, perm)
            dvc = jax.lax.ppermute(dvc, axis, perm)
        return (dq.astype(qb.dtype), dkc.astype(kb.dtype),
                dvc.astype(vb.dtype))

    spec = P(None, None, axis, None)
    spec3 = P(None, None, axis)
    f = jax.shard_map(
        per_rank, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec3, spec),
        out_specs=(spec, spec, spec), axis_names={axis}, check_vma=True)
    return f(q, k, v, out, lse, do)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ulysses_attention(q, k, v, causal, scale, mesh, axis="sp"):
    """Head<->sequence all-to-all (DeepSpeed-Ulysses scheme): reshard
    [B, H, S/P, D] -> [B, H/P, S, D], dense attention locally, reshard back.
    q,k,v: [B, H, S, D] with S sharded over `axis`; H % axis size == 0."""
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by sp ({n})")
    from paddle_tpu.kernels.flash_attention import _xla_flash

    def per_rank(qb, kb, vb):
        # local [B, H, sl, D] -> [B, H/n, S, D]
        def seq2head(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def head2seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = seq2head(qb), seq2head(kb), seq2head(vb)
        out = _xla_flash(qh, kh, vh, causal, scale)
        return head2seq(out)

    spec = P(None, None, axis, None)
    f = jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, axis_names={axis}, check_vma=True)
    return f(q, k, v)
