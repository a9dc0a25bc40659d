"""Plain Granite-4.0-H (``granitemoehybrid``): the forward pass of the
hybrid stack, whole or as one chip's share of its routed experts, and
nothing else.

The yardstick's reference for the ``granitemoehybrid`` family.
Straightforward ``jax.numpy`` in float32 with matrix multiplications at
``highest`` precision; the Mamba-2 recurrence is a ``lax.scan`` over
TOKENS (the sequential form, not the chunked one the program runs),
attention is full-sequence with a causal mask (one K/V head at a time, so
that the scores fit), the routed experts are a
loop over the held experts with every token through each; no cache, no
kernels, no batching. It imports nothing of ``paddle_tpu`` and is handed
only the weights the benchmark made from the seed
(``harness/granite_weights.py``).

Layer ``i`` (``layer_types[i]``), from the published ``config.json`` keys
(Hugging Face ``ibm-granite/granite-4.0-h-small``) and the published
modelling code's equations::

    h0 = embed[ids] * embedding_multiplier
    a  = RMSNorm(h);  h = h + residual_multiplier * mix_i(a)
    b  = RMSNorm(h);  h = h + residual_multiplier * (routed(b) + shared(b))
    logits = RMSNorm(h) embed^T / logits_scaling        (tied table)

    attention  q, k, v = a Wq, a Wk, a Wv (no bias; NO positional encoding:
               ``position_embedding_type`` is ``nope``); scores q k^T *
               attention_multiplier (1/128 at the published size, NOT
               1/sqrt(head width)); causal softmax; query head h reads K/V
               head h // (heads / kv heads); Wo
    mamba      [z | xBC | dt] = a W_in; xBC = silu(conv1d_causal(xBC))
               (depthwise, ``mamba_d_conv`` taps, bias); [x | B | C] = xBC
               (one group); dt = softplus(dt + dt_bias); A = -exp(A_log),
               one scalar a head; per head S_t = exp(dt_t A) S_{t-1} + dt_t
               x_t (outer) B_t, y_t = S_t C_t + D x_t; y = RMSNorm(y *
               silu(z)) * w over the whole inner width (gate BEFORE the
               norm); out = y W_out
    routed     l = b W_r (all ``num_experts`` logits); the
               ``num_experts_per_tok`` largest; g = softmax over THOSE
               logits; expert e: [u | v] = b W1_e, (silu(u) * v) W2_e; the
               gate-weighted sum. No capacity, no dropped token
    shared     the same gated form at ``shared_intermediate_size``

**The share.** ``held = (lo, hi)`` are the routed experts this chip holds
(``num_local_experts`` of the configuration file, from ``experts_first``).
Routing is over all the router's outputs with the gates above, NOT
renormalised over the held experts; only held experts' terms are added, and
that partial result goes on to the next layer. With ``held`` = all experts
this is the published layer.

What was taken where the published description leaves a choice (each the
program's too):

- ``head_dim`` = hidden / heads = 128 (``assumed``);
- of a gated matrix's output the FIRST half is activated (``chunk(2)[0]``
  in the published code);
- the depthwise convolution's LAST tap multiplies the current token;
- Q, K and V are stored as one matrix ``qkv.w`` (columns q | k | v): the
  same three products.

Weights are a flat dict of arrays (names in ``leaf_shapes``): ``m.*``
stacked over the Mamba layers, ``a.*`` over the attention layers, ``f.*``
(second norm, router, HELD experts, shared expert) over all layers. Linear
weights are ``[in, out]``. The arrays may be held in bfloat16: a layer's
leaves are widened to float32 as that layer runs (exact), so the model is
never held whole in float32.

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first (the router's product
too).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


class Sizes(NamedTuple):
    d: int
    types: tuple
    vocab: int
    nq: int
    nkv: int
    hd: int
    f: int
    fs: int
    experts: int
    top_k: int
    held: tuple
    mh: int
    mp: int
    ms: int
    dc: int
    eps: float
    emb: float
    res: float
    att: float
    lsc: float

    @property
    def di(self):
        return self.mh * self.mp

    @property
    def cd(self):
        return self.di + 2 * self.ms

    @property
    def n_held(self):
        return self.held[1] - self.held[0]


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs, from the configuration's keys:
    the published ``config.json`` names; ``assumed`` for the head width;
    ``num_local_experts`` is the count HELD (``num_local_experts_published``
    beside it) from ``experts_first`` on."""
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("mamba_n_groups: only one group is written")
    n = cfg["num_hidden_layers"]
    types = tuple(cfg["layer_types"])[:n]
    if len(types) != n:
        raise ValueError(f"{n} layers but {len(types)} layer_types")
    experts = cfg.get("router_outputs", cfg["num_local_experts"])
    lo = cfg.get("experts_first", 0)
    return Sizes(
        d=cfg["hidden_size"], types=types, vocab=cfg["vocab_size"],
        nq=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
        hd=cfg["assumed"]["head_dim"], f=cfg["intermediate_size"],
        fs=cfg["shared_intermediate_size"], experts=experts,
        top_k=cfg["num_experts_per_tok"],
        held=(lo, lo + cfg["num_local_experts"]), mh=cfg["mamba_n_heads"],
        mp=cfg["mamba_d_head"], ms=cfg["mamba_d_state"],
        dc=cfg["mamba_d_conv"], eps=cfg["rms_norm_eps"],
        emb=cfg["embedding_multiplier"], res=cfg["residual_multiplier"],
        att=cfg["attention_multiplier"], lsc=cfg["logits_scaling"])


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    d, di, cd = s.d, s.di, s.cd
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    mamba = {"norm.w": (d,), "in_proj": (d, di + cd + s.mh),
             "conv.w": (s.dc, cd), "conv.b": (cd,), "dt_bias": (s.mh,),
             "A_log": (s.mh,), "D": (s.mh,), "gnorm.w": (di,),
             "out_proj": (di, d)}
    attn = {"norm.w": (d,), "qkv.w": (d, qw + 2 * kvw), "o.w": (qw, d)}
    ffn = {"norm.w": (d,), "router": (d, s.experts),
           "w1": (s.n_held, d, 2 * s.f), "w2": (s.n_held, s.f, d),
           "shared.w1": (d, 2 * s.fs), "shared.w2": (s.fs, d)}
    nm, na = s.types.count("mamba"), s.types.count("attention")
    out = {"embed": (s.vocab, d), "norm_f.w": (d,)}
    out.update({f"m.{k}": (nm,) + v for k, v in mamba.items()})
    out.update({f"a.{k}": (na,) + v for k, v in attn.items()})
    out.update({f"f.{k}": (len(s.types),) + v for k, v in ffn.items()})
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


def silu(x):
    return x * jax.nn.sigmoid(x)


def gated(b, w1, w2, precision):
    """(silu(u) * v) W2 with [u | v] = b W1: the first half is activated."""
    u, v = jnp.split(_mm("td,dk->tk", b, w1, precision), 2, -1)
    return _mm("tk,kd->td", silu(u) * v, w2, precision)


def route(b, w_router, top_k, precision):
    """(gates [T, experts] f32, zero where an expert was not chosen): the
    ``top_k`` largest logits of each token, a softmax over those alone."""
    logits = _mm("td,de->te", b, w_router, precision)
    top, idx = jax.lax.top_k(logits, top_k)
    g = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(b.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(g)


def routed(b, p, s, precision):
    """The held experts' part of the routed layer: every token through each
    held expert, weighted by its gate (zero where not chosen)."""
    gates = route(b, p["router"], s.top_k, precision)
    out = jnp.zeros_like(b)
    for e in range(s.n_held):
        out = out + gates[:, s.held[0] + e, None] \
            * gated(b, p["w1"][e], p["w2"][e], precision)
    return out


def ffn(h, p, s, precision):
    b = rms_norm(h, p["norm.w"], s.eps)
    return h + s.res * (routed(b, p, s, precision)
                        + gated(b, p["shared.w1"], p["shared.w2"], precision))


def mamba2(a, p, s, precision):
    """Mamba-2 over a [T, d] from a zero state, token by token."""
    t = a.shape[0]
    zxd = _mm("td,dk->tk", a, p["in_proj"], precision)
    z, xbc, dt = jnp.split(zxd, [s.di, s.di + s.cd], -1)
    xp = jnp.concatenate([jnp.zeros((s.dc - 1, s.cd), xbc.dtype), xbc])
    xbc = silu(sum(p["conv.w"][k] * xp[k:k + t] for k in range(s.dc))
               + p["conv.b"])
    x, bm, cm = jnp.split(xbc, [s.di, s.di + s.ms], -1)
    x = x.reshape(t, s.mh, s.mp)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # [T, H]
    a_head = -jnp.exp(p["A_log"])                           # [H]

    def step(state, inp):                                   # [H, P, N]
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a_head)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, (state * c_t[None, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((s.mh, s.mp, s.ms), jnp.float32),
                        (dt, x, bm, cm))
    y = (y + p["D"][None, :, None] * x).reshape(t, s.di)
    y = rms_norm(y * silu(z), p["gnorm.w"], s.eps)
    return _mm("tk,kd->td", y, p["out_proj"], precision)


def attention(a, p, s, precision):
    """Causal grouped-query attention over a [T, d], no positions."""
    t = a.shape[0]
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    q, k, v = jnp.split(_mm("td,dk->tk", a, p["qkv.w"], precision),
                        [qw, qw + kvw], -1)
    q = q.reshape(t, s.nkv, s.nq // s.nkv, s.hd)
    k, v = k.reshape(t, s.nkv, s.hd), v.reshape(t, s.nkv, s.hd)
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]

    def group(qkv):           # one K/V head and the query heads that read it
        qg, kg, vg = qkv                       # [T, g, hd], [T, hd], [T, hd]
        sc = _mm("tgd,sd->gts", qg, kg, precision) * s.att
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        return _mm("gts,sd->tgd", pr, vg, precision)

    # a K/V head at a time: all heads' [T, T] scores at once are 2.4 GB at
    # the 4,352 tokens the benchmark pads to
    o = jax.lax.map(group, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                            v.swapaxes(0, 1)))             # [nkv, T, g, hd]
    o = o.swapaxes(0, 1).reshape(t, qw)
    return _mm("tk,kd->td", o, p["o.w"], precision)


def _widen(w, prefix, index):
    """One layer's leaves in float32, without the prefix."""
    return {name[len(prefix):]: x[index].astype(jnp.float32)
            for name, x in w.items() if name.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def mamba_layer(h, pm, pf, s, precision):
    h = h + s.res * mamba2(rms_norm(h, pm["norm.w"], s.eps), pm, s,
                           precision)
    return ffn(h, pf, s, precision)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def attention_layer(h, pa, pf, s, precision):
    h = h + s.res * attention(rms_norm(h, pa["norm.w"], s.eps), pa, s,
                              precision)
    return ffn(h, pf, s, precision)


def hidden(w, ids, cfg, precision="f32"):
    """The stack's last hidden state [T, d] for one sequence ``ids`` [T].
    One layer at a time (each kind of layer compiles once), that layer's
    leaves widened to float32 as it runs."""
    s = sizes(cfg)
    h = w["embed"][ids].astype(jnp.float32) * s.emb
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(s.types):
        k = seen[kind]
        seen[kind] += 1
        pf = _widen(w, "f.", i)
        if kind == "mamba":
            h = mamba_layer(h, _widen(w, "m.", k), pf, s, precision)
        else:
            h = attention_layer(h, _widen(w, "a.", k), pf, s, precision)
    return h


@functools.partial(jax.jit, static_argnames=("eps", "lsc", "precision"))
def head(h, norm_w, embed, eps, lsc, precision):
    h = rms_norm(h, norm_w.astype(jnp.float32), eps)
    return _mm("td,vd->tv", h, embed.astype(jnp.float32), precision) / lsc


def logits(w, ids, cfg, precision="f32", rows=None):
    """Logits [T, V] of one sequence (``rows``: only those positions)."""
    s = sizes(cfg)
    h = hidden(w, ids, cfg, precision)
    if rows is not None:
        h = h[rows]
    return head(h, w["norm_f.w"], w["embed"], s.eps, s.lsc, precision)
