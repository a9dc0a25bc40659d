"""Fused LM-head + softmax cross-entropy (TPU memory/bandwidth kernel).

Counterpart of the reference's fused ``c_softmax_with_cross_entropy`` idea
(`paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cc`):
the loss of ``h @ w.T`` against integer labels as ONE op with a custom VJP,
so autodiff never sees the ``[N, V]`` logits (8 x 1024 x 50304: 1.6 GB of
float32) and nothing of a softmax over them is saved for the backward.

One algorithm, a forward with two bodies; which one runs follows from what
the code can see (`_pallas_plan`: the backend, the shapes, whether the
program is one GSPMD partitions), no flag:

- the Pallas body (`kernels/pallas/fused_ce.py`; a TPU, no multi-device
  mesh, ``hidden`` and ``V`` multiples of 128, ``N`` a multiple of the row
  block): one kernel makes the
  product and takes the row maximum, ``sum(exp)`` and the label's logit
  from each tile while it is in VMEM. It writes the float32 logits ONCE, as
  ``[V, N]``, and they are HELD from forward to backward beside the rows'
  logsumexp: the backward forms ``dlogits`` in bf16 from them straight into
  its two products (XLA fuses it into their operand). It does not make the
  product again: the forward is a custom call, XLA has nothing to merge a
  recompute with, and a real fourth product (6.4 ms at GPT-2 small's
  16 x 1024) costs more than the pass over the logits that the kernel
  removes;
- the XLA body (anything else): the vocabulary in up to four slices, each
  a product with its own maximum and ``sum(exp)``, merged; the backward
  recomputes a slice's logits. Inside one compiled program XLA merges that
  recompute with the forward's product, so there too the logits are held
  in HBM from forward to backward (3.3 GB of temporaries in the captured
  GPT-2 small step): "never materialized" is true of autodiff's residuals
  and of the eager path, not of a compiled step.

Which body a trace took: counters ``kernel.fused_ce.forward.{pallas,xla}``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.mesh import get_mesh
from paddle_tpu.kernels import registry
from paddle_tpu.observability import metrics


def _candidates(ctx):
    # the fused chunked-vocab CE assumes the full [V, H] head on every
    # rank; under mp the vocab is sharded and only the dense parallel CE
    # is correct
    return ["fused", "dense"] if ctx.get("mp", 1) == 1 else ["dense"]


# dispatched where the model chooses its loss (`models/gpt.py::
# _fused_ce_impl`)
registry.register_op("fused_ce", impls=("fused", "dense"),
                     candidates=_candidates)


def _pick_chunks(v: int) -> int:
    """Chunk count <= 4 that divides the (padded) vocab. Chunks are UNROLLED
    (python loop) so the per-chunk matmuls stay independent in the graph —
    lax.scan would serialize them behind the cheap online-logsumexp carry.
    The XLA body's forward and backward are split by it; the Pallas forward
    tiles the vocabulary itself and its backward takes the held logits
    whole."""
    for nc in (4, 3, 2):
        if v % nc == 0 and v // nc >= 4096:
            return nc
    return 1


def _pallas_plan(n, hid, v):
    """The Pallas forward's plan where it fits the call (on a TPU; in the
    interpreter where a test steers the backend's name), else None: the
    XLA body runs. Under an installed multi-device mesh
    the trace becomes a program GSPMD partitions, which a Mosaic kernel
    cannot join."""
    mesh = get_mesh()
    if registry.backend() != "tpu" or (mesh is not None and mesh.size > 1):
        return None
    from paddle_tpu.kernels.pallas import fused_ce as kernel
    return kernel._plan(n, hid, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def fused_linear_cross_entropy(h, w, labels):
    loss, _ = _flce_fwd(h, w, labels)
    return loss


def _chunk_logits(h, w_c):
    """[N,H] x [vc,H] -> [N,vc] in bf16 with f32 accumulation (MXU-friendly)."""
    return jax.lax.dot_general(
        h.astype(jnp.bfloat16), w_c.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _xla_stats(h, w, labels):
    """(lse, picked logit) a row, the vocabulary in `_pick_chunks` slices."""
    v = w.shape[0]
    nc = _pick_chunks(v)
    vc = v // nc
    # independent per-chunk (max, sumexp-at-own-max, picked-logit) ...
    ms, ls, picks = [], [], []
    for c in range(nc):
        logits = _chunk_logits(h, w[c * vc:(c + 1) * vc])   # [N, vc] f32
        m_c = jnp.max(logits, axis=-1)
        l_c = jnp.sum(jnp.exp(logits - m_c[:, None]), axis=-1)
        idx = labels - c * vc
        in_chunk = (idx >= 0) & (idx < vc)
        safe = jnp.where(in_chunk, idx, 0)
        got = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
        ms.append(m_c)
        ls.append(l_c)
        picks.append(jnp.where(in_chunk, got, -jnp.inf))
    # ... then a cheap tree-merge into the global logsumexp
    m = ms[0]
    for m_c in ms[1:]:
        m = jnp.maximum(m, m_c)
    l = ls[0] * jnp.exp(ms[0] - m)
    for m_c, l_c in zip(ms[1:], ls[1:]):
        l = l + l_c * jnp.exp(m_c - m)
    picked = picks[0]
    for pk in picks[1:]:
        picked = jnp.maximum(picked, pk)
    return m + jnp.log(l), picked


def _flce_fwd(h, w, labels):
    n, hid = h.shape
    v = w.shape[0]
    labels = labels.astype(jnp.int32)
    plan = _pallas_plan(n, hid, v)
    metrics.counter(
        f"kernel.fused_ce.forward.{'pallas' if plan else 'xla'}").inc()
    if plan:
        from paddle_tpu.kernels.pallas import _compat, fused_ce as kernel
        held, lse, picked = kernel.forward(
            h, w, labels, plan=plan, interpret=_compat.default_interpret())
    else:
        held = None
        lse, picked = _xla_stats(h, w, labels)
    # out-of-range labels (e.g. the conventional -100 padding / ignore_index)
    # contribute zero loss and zero gradient, matching F.cross_entropy
    valid = (labels >= 0) & (labels < v)
    loss = jnp.where(valid, lse - picked, 0.0)
    return loss, (h, w, labels, lse, held)


def _flce_bwd(res, dloss):
    # held: the Pallas forward's float32 logits [V, N], or None (the XLA
    # body: a slice's logits are made again, which a compiled program
    # merges with the forward's product)
    h, w, labels, lse, held = res
    n, hid = h.shape
    v = w.shape[0]
    # held logits leave nothing to keep independent: one slice (at GPT-2
    # small's 16 x 1024 no slower than four, and the step's temporaries
    # are 0.13 GB less: the partial sums of dh have no freed slice to lie in)
    nc = _pick_chunks(v) if held is None else 1
    vc = v // nc
    valid = (labels >= 0) & (labels < v)
    dl = dloss.astype(jnp.float32) * valid.astype(jnp.float32)

    dh = jnp.zeros((n, hid), jnp.float32)
    dws = []
    for c in range(nc):
        w_c = w[c * vc:(c + 1) * vc]
        logits = (_chunk_logits(h, w_c) if held is None
                  else held[c * vc:(c + 1) * vc].T)         # [N, vc]
        p = jnp.exp(logits - lse[:, None])                  # softmax chunk
        idx = labels - c * vc
        in_chunk = (idx >= 0) & (idx < vc)
        onehot = (jnp.arange(vc, dtype=jnp.int32)[None, :] ==
                  idx[:, None]) & in_chunk[:, None]
        dlogits = ((p - onehot.astype(jnp.float32)) *
                   dl[:, None]).astype(jnp.bfloat16)        # [N, vc]
        dh = dh + jax.lax.dot_general(
            dlogits, w_c.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dws.append(jax.lax.dot_general(
            dlogits, h.astype(jnp.bfloat16),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32))
    dw = jnp.concatenate(dws, axis=0).astype(w.dtype)
    return dh.astype(h.dtype), dw, None


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)
