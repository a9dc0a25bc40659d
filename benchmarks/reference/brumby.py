"""Plain Brumby (``brumby``): the forward pass of a stack of power-retention
layers, and nothing else.

The yardstick's reference for the ``brumby`` family. Straightforward
``jax.numpy`` in float32 with matrix multiplications at ``highest``
precision; power retention is computed in its ATTENTION form, the ``[T, T]``
weights of each head under a causal mask, so there is no state, no chunk,
no cache and no kernel here, while the program under test keeps a state and
never forms a weight between two chunks: the two share only the equations.
It imports nothing of ``paddle_tpu`` and is handed only the weights the
benchmark made from the seed (``harness/brumby_weights.py``).

A layer, from the published ``config.json`` keys (Hugging Face
``manifestai/Brumby-14B-Base``), the published modelling code and Manifest
AI's power-attention paper (arXiv:2507.04239); ``hd`` = ``head_dim``, query
head i reads key-value head i // (heads / kv heads)::

    h0 = embed[ids]
    a  = RMSNorm(h)                          (``rms_norm_eps``)
    q_i = RoPE(RMSNorm_head(a Wq)_i)   k_j = RoPE(RMSNorm_head(a Wk)_j)
    v_j = (a Wv)_j                           (no biases)
         RMSNorm_head: over the hd values of a head, times a learned
         hd-vector (one for q, one for k, shared by the heads)
         RoPE: the half-rotation form (element i < hd/2 pairs with i +
         hd/2) at the token's absolute position, ``rope_theta``, no scaling
    log g_t,j = logsigmoid(a_t Wg + bg)_j    one gate a kv head and token
    B_t = sum_{r <= t} log g_r
    w_{t,s} = (q_t . k_s / hd)^p exp(B_t - B_s)   s <= t, p = 2
    y_t = sum_s w_{t,s} v_s / (sum_s w_{t,s} + eps)
    h  = h + concat_i(y_i) Wo
    b  = RMSNorm(h);  h = h + (silu(b W_gate) * (b W_up)) W_down
    logits = RMSNorm(h) W_head               (untied)

What was taken where the published key set leaves a choice (``assumed`` in
the configuration's file; each the program's too): the degree p = 2; q and k
normalised per head and rotated; the gate's form and its bias ``bg`` (this
repo's own: see ``harness/brumby_weights.py``); the output normalised by
the sum of its weights with ``eps``; and, free of consequence under seeded
weights: which half RoPE pairs (above), Q, K and V stored as one matrix
``l.qkv.w`` (columns q | k | v), the MLP's gate and up as one matrix
``l.mlp.w1`` (columns gate | up: the FIRST half is activated). The
published code's switch to a K/V form below some sequence length computes
the same numbers and is not a choice here.

Weights are a flat dict of arrays (names in ``leaf_shapes``): ``embed``,
``head``, ``norm_f.w`` and ``l.*`` stacked over the layers. Linear weights
are ``[in, out]``. The arrays may be held in bfloat16: a layer's leaves are
widened to float32 as that layer runs (exact), so the model is never held
whole in float32.

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first. ``"state_bf16"`` is the
control of the STATE's type: the retention runs as the recurrence with
``S`` and ``z`` rounded to bfloat16 after every token (what a program that
kept its state in the served type would compute), everything else as the
reference.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn,
          "state_bf16": None}


class Sizes(NamedTuple):
    d: int
    n: int
    vocab: int
    nq: int
    nkv: int
    hd: int
    f: int
    eps: float
    theta: float
    power: int
    ret_eps: float


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs: the published ``config.json``
    names, and ``assumed`` for what the key set does not give."""
    a = cfg["assumed"]
    return Sizes(d=cfg["hidden_size"], n=cfg["num_hidden_layers"],
                 vocab=cfg["vocab_size"], nq=cfg["num_attention_heads"],
                 nkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                 f=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
                 theta=float(cfg["rope_theta"]), power=int(a["power"]),
                 ret_eps=float(a["retention_eps"]))


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    layer = {"norm1.w": (s.d,), "qkv.w": (s.d, qw + 2 * kvw),
             "q_norm.w": (s.hd,), "k_norm.w": (s.hd,),
             "gate.w": (s.d, s.nkv), "gate.b": (s.nkv,), "o.w": (qw, s.d),
             "norm2.w": (s.d,), "mlp.w1": (s.d, 2 * s.f),
             "mlp.w2": (s.f, s.d)}
    out = {"embed": (s.vocab, s.d), "head": (s.d, s.vocab),
           "norm_f.w": (s.d,)}
    out.update({f"l.{k}": (s.n,) + v for k, v in layer.items()})
    return out


def param_count(cfg: dict) -> int:
    return sum(math.prod(v) for v in leaf_shapes(cfg).values())


def _mm(eq, a, b, precision):
    to = _ROUND[precision]
    if to is not None:
        a = a.astype(to).astype(jnp.float32)
        b = b.astype(to).astype(jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Half rotation of ``x`` [T, heads, hd] at positions 0 .. T - 1."""
    t, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _recurrent_bf16(qg, kg, vg, lg, s):
    """The recurrence with the state rounded to bfloat16 a token: the
    ``state_bf16`` control. qg [T, g, hd] (scaled), kg, vg [T, hd], lg
    [T]. The state is the full outer product [hd, hd, hd] here: the same
    numbers as any packing of its distinct terms."""
    bf = jnp.bfloat16

    def step(c, x):
        st, zt = c
        q, k, v, l_ = x
        kk = k[:, None] * k[None, :]
        st = (jnp.exp(l_) * st.astype(jnp.float32)
              + kk[:, :, None] * v[None, None, :]).astype(bf)
        zt = (jnp.exp(l_) * zt.astype(jnp.float32) + kk).astype(bf)
        qq = q[:, :, None] * q[:, None, :]                  # [g, hd, hd]
        num = jnp.einsum("gab,abv->gv", qq, st.astype(jnp.float32))
        den = jnp.einsum("gab,ab->g", qq, zt.astype(jnp.float32))
        return (st, zt), num / (den[:, None] + s.ret_eps)

    hd = s.hd
    _, y = jax.lax.scan(step, (jnp.zeros((hd, hd, hd), bf),
                               jnp.zeros((hd, hd), bf)), (qg, kg, vg, lg))
    return y


def retention(a, p, s, precision):
    """Causal power retention over a [T, d], in the attention form."""
    t = a.shape[0]
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    q, k, v = jnp.split(_mm("td,dk->tk", a, p["qkv.w"], precision),
                        [qw, qw + kvw], -1)
    q = rope(rms_norm(q.reshape(t, s.nq, s.hd), p["q_norm.w"], s.eps),
             s.theta)
    k = rope(rms_norm(k.reshape(t, s.nkv, s.hd), p["k_norm.w"], s.eps),
             s.theta)
    v = v.reshape(t, s.nkv, s.hd)
    log_g = jax.nn.log_sigmoid(_mm("td,dk->tk", a, p["gate.w"], precision)
                               + p["gate.b"])               # [T, nkv]
    q = q.reshape(t, s.nkv, s.nq // s.nkv, s.hd) / s.hd
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]

    def group(x):             # one K/V head and the query heads that read it
        qg, kg, vg, lg = x                    # [T, g, hd], [T, hd] x 2, [T]
        if precision == "state_bf16":
            return _recurrent_bf16(qg, kg, vg, lg, s)
        cum = jnp.cumsum(lg)
        gate = jnp.exp(jnp.where(causal, cum[:, None] - cum[None, :],
                                 -jnp.inf))
        w = _mm("tgd,sd->gts", qg, kg, precision) ** s.power * gate
        num = _mm("gts,sd->tgd", w, vg, precision)
        return num / (w.sum(-1).T[..., None] + s.ret_eps)

    # a K/V head at a time: all heads' [T, T] weights at once are 4.2 GB
    # at the 5,120 tokens the benchmark pads to
    o = jax.lax.map(group, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                            v.swapaxes(0, 1), log_g.T))     # [nkv, T, g, hd]
    o = o.swapaxes(0, 1).reshape(t, qw)
    return _mm("tk,kd->td", o, p["o.w"], precision)


def mlp(b, p, precision):
    """(silu(u) * w) W2 with [u | w] = b W1: the first half is activated."""
    u, w = jnp.split(_mm("td,dk->tk", b, p["mlp.w1"], precision), 2, -1)
    return _mm("tk,kd->td", u * jax.nn.sigmoid(u) * w, p["mlp.w2"],
               precision)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def layer(h, p, s, precision):
    h = h + retention(rms_norm(h, p["norm1.w"], s.eps), p, s, precision)
    return h + mlp(rms_norm(h, p["norm2.w"], s.eps), p, precision)


def _widen(w, index):
    """One layer's leaves in float32, without the prefix."""
    return {name[2:]: x[index].astype(jnp.float32)
            for name, x in w.items() if name.startswith("l.")}


def hidden(w, ids, cfg, precision="f32"):
    """The stack's last hidden state [T, d] for one sequence ``ids`` [T].
    One layer at a time (the layer compiles once), that layer's leaves
    widened to float32 as it runs."""
    s = sizes(cfg)
    h = w["embed"][ids].astype(jnp.float32)
    for i in range(s.n):
        h = layer(h, _widen(w, i), s, precision)
    return h


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head(h, norm_w, head_w, eps, precision):
    h = rms_norm(h, norm_w.astype(jnp.float32), eps)
    return _mm("td,dv->tv", h, head_w.astype(jnp.float32),
               "f32" if precision == "state_bf16" else precision)


def logits(w, ids, cfg, precision="f32", rows=None):
    """Logits [T, V] of one sequence (``rows``: only those positions)."""
    s = sizes(cfg)
    h = hidden(w, ids, cfg, precision)
    if rows is not None:
        h = h[rows]
    return head(h, w["norm_f.w"], w["head"], s.eps, precision)
