"""Plain Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607): the forward
pass of the decoder-hybrid-decoder stack, and nothing else.

The yardstick's reference for the ``phi4flash`` family. Straightforward
``jax.numpy`` in float32 with matrix multiplications at ``highest``
precision; the Mamba recurrence is a ``lax.scan`` over tokens, attention is
full-sequence with masks; no cache, no kernels, no batching. It imports
nothing of ``paddle_tpu`` and is handed only the weights the benchmark made
from the seed (``harness/hybrid_weights.py``).

Layer ``i`` of ``n`` (0-based; ``half = n // 2``; the published model has
``n`` = 32)::

    h += mixer_i(LN1_i(h));  h += W2 (SiLU(g) * u),  [u, g] = W1 LN2_i(h)

    i even, i <= half   Mamba-1 (layer ``half`` also hands on its scan
                        output before the gate: the memory ``m``)
    i odd,  i <  half   differential attention over a window (a query sees
                        itself and the ``window - 1`` tokens before it)
    i == half + 1       differential attention over everything; its K and V
                        are the ones the cross layers read
    i even, i > half    gated memory unit: W_o (m * SiLU(W_g r))
    i odd,  i > half+1  differential cross-attention: own queries, layer
                        ``half + 1``'s K and V (causal)

    logits = LN_f(h) E^T  (tied table); no positional encoding anywhere.

What was taken where the published description leaves a choice (none of it
shows under seeded weights, and the program takes the same):

- of a head pair, "1" is the even head: query pair ``j`` is heads ``2j``
  and ``2j + 1``, key pair ``g`` is KV heads ``2g`` and ``2g + 1``, value
  head ``g`` is V heads ``2g`` and ``2g + 1`` side by side (width
  ``2 * head_dim``); query pair ``j`` reads KV pair ``j // (pairs_q /
  pairs_kv)``;
- ``W1``'s first half is ``u``, its second half the gate ``g``;
- the depthwise convolution's last tap multiplies the current token;
- the pair norm is an RMS norm over ``2 * head_dim`` with a learned scale
  and eps 1e-5, and LayerNorm has bias and eps 1e-5.

Weights are a flat dict of arrays (names in ``leaf_shapes``): the
``front.*`` leaves stacked over the ``half / 2`` periods [Mamba, window
attention], ``mid.m.*`` and ``mid.a.*`` for layers ``half`` and ``half +
1``, the ``back.*`` leaves stacked over the periods [memory unit, cross
attention]. Linear weights are laid out ``[in, out]``. The arrays may be
held in bfloat16: every layer's leaves are widened to float32 as that layer
runs (exact), so the whole model is never held in float32.

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

EPS = 1e-5
_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


class Sizes(NamedTuple):
    d: int
    n: int
    half: int
    vocab: int
    ff: int
    nq: int
    nkv: int
    hd: int
    window: int
    di: int
    ds: int
    dc: int
    dtr: int


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs, from the configuration's keys
    (the published ``config.json`` names, and ``assumed`` for what it does
    not give)."""
    a = cfg["assumed"]
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    if n % 4:
        raise ValueError(f"num_hidden_layers {n} is not a multiple of 4")
    hd = d // cfg["num_attention_heads"]
    return Sizes(
        d=d, n=n, half=n // 2, vocab=cfg["vocab_size"],
        ff=cfg["intermediate_size"], nq=cfg["num_attention_heads"],
        nkv=cfg["num_key_value_heads"], hd=hd,
        window=cfg["sliding_window"], di=a["mamba_expand"] * d,
        ds=a["mamba_d_state"], dc=a["mamba_d_conv"], dtr=a["mamba_dt_rank"])


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    d, ff, di, ds, dc, dtr = s.d, s.ff, s.di, s.ds, s.dc, \
        s.dtr
    qw, kvw, hd = s.nq * s.hd, s.nkv * s.hd, s.hd
    tail = {"ln2.w": (d,), "ln2.b": (d,), "mlp.w1": (d, 2 * ff),
            "mlp.w2": (ff, d)}
    head = {"ln1.w": (d,), "ln1.b": (d,)}
    mamba = {**head, "in_proj": (d, 2 * di), "conv.w": (dc, di),
             "conv.b": (di,), "x_proj": (di, dtr + 2 * ds),
             "dt_proj.w": (dtr, di), "dt_proj.b": (di,),
             "A_log": (di, ds), "D": (di,), "out_proj": (di, d), **tail}
    diff = {"lam": (4, hd), "subln.w": (2 * hd,), "out.w": (qw, d),
            "out.b": (d,)}
    attn = {**head, "qkv.w": (d, qw + 2 * kvw), "qkv.b": (qw + 2 * kvw,),
            **diff, **tail}
    gmu = {**head, "gate": (d, di), "out": (di, d), **tail}
    cross = {**head, "q.w": (d, qw), "q.b": (qw,), **diff, **tail}
    nf, nb = s.half // 2, (s.n - s.half - 2) // 2
    out = {"embed": (s.vocab, d), "ln_f.w": (d,), "ln_f.b": (d,)}
    out.update({f"front.m.{k}": (nf,) + v for k, v in mamba.items()})
    out.update({f"front.a.{k}": (nf,) + v for k, v in attn.items()})
    out.update({f"mid.m.{k}": v for k, v in mamba.items()})
    out.update({f"mid.a.{k}": v for k, v in attn.items()})
    out.update({f"back.g.{k}": (nb,) + v for k, v in gmu.items()})
    out.update({f"back.c.{k}": (nb,) + v for k, v in cross.items()})
    return out


def param_count(cfg: dict) -> int:
    return sum(math.prod(v) for v in leaf_shapes(cfg).values())


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _mm(eq, a, b, precision):
    to = _ROUND[precision]
    if to is not None:
        a = a.astype(to).astype(jnp.float32)
        b = b.astype(to).astype(jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * w + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(h, p, precision):
    r = layer_norm(h, p["ln2.w"], p["ln2.b"])
    u, g = jnp.split(_mm("th,hk->tk", r, p["mlp.w1"], precision), 2, -1)
    return h + _mm("tk,kh->th", silu(g) * u, p["mlp.w2"], precision)


def mamba(r, p, s, precision):
    """Mamba-1 over r [T, d] from a zero state. Returns (mixer output
    [T, d], scan output before the gate [T, di])."""
    t = r.shape[0]
    x, z = jnp.split(_mm("td,dk->tk", r, p["in_proj"], precision), 2, -1)
    xp = jnp.concatenate([jnp.zeros((s.dc - 1, s.di), x.dtype), x])
    x = silu(sum(p["conv.w"][k] * xp[k:k + t] for k in range(s.dc))
             + p["conv.b"])
    dbc = _mm("tk,kj->tj", x, p["x_proj"], precision)
    dr, bm, cm = jnp.split(dbc, [s.dtr, s.dtr + s.ds], -1)
    dt = jax.nn.softplus(_mm("tr,rk->tk", dr, p["dt_proj.w"], precision)
                         + p["dt_proj.b"])
    a = -jnp.exp(p["A_log"])                              # [di, ds]

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, (state * c_t[None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((s.di, s.ds), jnp.float32),
                        (dt, x, bm, cm))
    y = y + p["D"] * x
    return _mm("tk,kd->td", y * silu(z), p["out_proj"], precision), y


def diff_attention(q, k, v, mask, p, l0, s, precision):
    """Differential grouped-query attention. q [T, nq*hd]; k, v [S, nkv*hd];
    mask [T, S] bool (True: the query sees the key); l0 = lambda_init of
    the layer (a number, or a traced scalar so that every layer of a kind
    shares one compiled function)."""
    hd, pq, pkv = s.hd, s.nq // 2, s.nkv // 2
    t, n_keys = q.shape[0], k.shape[0]
    q = q.reshape(t, pkv, pq // pkv, 2, hd)    # [T, kv pair, group, 1|2, hd]
    k = k.reshape(n_keys, pkv, 2, hd)
    v = v.reshape(n_keys, pkv, 2 * hd)
    sc = _mm("tgjcd,sgcd->gjcts", q, k, precision) / math.sqrt(hd)
    pr = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    o = _mm("gjcts,sge->tgjce", pr, v, precision)       # [T, g, j, 1|2, 2hd]
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = jnp.exp((lq1 * lk1).sum()) - jnp.exp((lq2 * lk2).sum()) + l0
    o = o[..., 0, :] - lam * o[..., 1, :]
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + EPS) * p["subln.w"]
    o = (1.0 - l0) * o.reshape(t, pq * 2 * hd)
    return _mm("tk,kd->td", o, p["out.w"], precision) + p["out.b"]


def _widen(w, prefix, index=None):
    """One layer's leaves in float32, without the prefix."""
    out = {}
    for name, x in w.items():
        if name.startswith(prefix):
            x = x if index is None else x[index]
            out[name[len(prefix):]] = x.astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def mamba_layer(h, p, s, precision):
    """A whole Mamba layer: (new h, the scan output before the gate)."""
    out, y = mamba(layer_norm(h, p["ln1.w"], p["ln1.b"]), p, s, precision)
    return mlp(h + out, p, precision), y


@functools.partial(jax.jit, static_argnames=("full", "s", "precision"))
def attention_layer(h, p, l0, full, s, precision):
    """A whole self-attention layer (windowed unless ``full``): (new h, its
    K, its V)."""
    qw, kvw = s.nq * s.hd, s.nkv * s.hd
    pos = jnp.arange(h.shape[0])
    mask = pos[None, :] <= pos[:, None]
    if not full:
        mask &= pos[:, None] - pos[None, :] < s.window
    r = layer_norm(h, p["ln1.w"], p["ln1.b"])
    qkv = _mm("td,dk->tk", r, p["qkv.w"], precision) + p["qkv.b"]
    q, k, v = jnp.split(qkv, [qw, qw + kvw], -1)
    out = diff_attention(q, k, v, mask, p, l0, s, precision)
    return mlp(h + out, p, precision), k, v


@functools.partial(jax.jit, static_argnames=("precision",))
def memory_layer(h, memory, p, precision):
    """A whole gated-memory-unit layer."""
    r = layer_norm(h, p["ln1.w"], p["ln1.b"])
    gate = silu(_mm("td,dk->tk", r, p["gate"], precision))
    out = _mm("tk,kd->td", memory * gate, p["out"], precision)
    return mlp(h + out, p, precision)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def cross_layer(h, k, v, p, l0, s, precision):
    """A whole cross-attention layer onto the full layer's K and V."""
    pos = jnp.arange(h.shape[0])
    r = layer_norm(h, p["ln1.w"], p["ln1.b"])
    q = _mm("td,dk->tk", r, p["q.w"], precision) + p["q.b"]
    out = diff_attention(q, k, v, pos[None, :] <= pos[:, None], p, l0, s,
                         precision)
    return mlp(h + out, p, precision)


def hidden(w, ids, cfg, precision="f32"):
    """The stack's last hidden state [T, d] for one sequence ``ids`` [T].
    One layer at a time (each kind of layer compiles once), that layer's
    leaves widened to float32 as it runs."""
    s = sizes(cfg)
    h = w["embed"][ids].astype(jnp.float32)
    for f in range(s.half // 2):
        h, _ = mamba_layer(h, _widen(w, "front.m.", f), s, precision)
        h, _, _ = attention_layer(h, _widen(w, "front.a.", f),
                                  lambda_init(2 * f + 1), False, s,
                                  precision)
    h, memory = mamba_layer(h, _widen(w, "mid.m."), s, precision)
    h, k, v = attention_layer(h, _widen(w, "mid.a."),
                              lambda_init(s.half + 1), True, s, precision)
    for b in range((s.n - s.half - 2) // 2):
        h = memory_layer(h, memory, _widen(w, "back.g.", b), precision)
        h = cross_layer(h, k, v, _widen(w, "back.c.", b),
                        lambda_init(s.half + 3 + 2 * b), s, precision)
    return h


@functools.partial(jax.jit, static_argnames=("precision",))
def head(h, ln_w, ln_b, embed, precision):
    h = layer_norm(h, ln_w.astype(jnp.float32), ln_b.astype(jnp.float32))
    return _mm("td,vd->tv", h, embed.astype(jnp.float32), precision)


def logits(w, ids, cfg, precision="f32", rows=None):
    """Logits [T, V] of one sequence (``rows``: only those positions)."""
    h = hidden(w, ids, cfg, precision)
    if rows is not None:
        h = h[rows]
    return head(h, w["ln_f.w"], w["ln_f.b"], w["embed"], precision)
