"""Plain dots3-note (``dots3_note``, Hugging Face
``dots-studio/dots3-note-prev``): the forward pass of the language model's
stack, whole or as one chip's share of its routed experts and vocabulary,
and nothing else.

The yardstick's reference for the ``dots3_note`` family. Straightforward
``jax.numpy`` in float32 with matrix multiplications at ``highest``
precision; no cache, no chunk, no batching; the PER-HEAD form of latent
attention throughout (every head's keys and values are expanded from the
latent; no matrix is ever absorbed into another); the indexer's scores and
the selection as a MASK over ``[T, T]``, made a block of queries at a time
so that it fits at 33,000 tokens. It imports nothing of ``paddle_tpu`` and
is handed only the weights the benchmark made from the seed
(``harness/dots3_weights.py``). The vision and audio towers and the
multi-token-prediction module that the model card describes are not in the
language model's ``config.json`` and are not here.

``h`` is a block's input after RMSNorm (``rms_norm_eps``); blocks are
pre-norm with plain residuals, ``x += Attn(norm(x)); x += FFN(norm(x))``; a
final RMSNorm; an untied head. From the published ``config.json`` keys;
lines marked *assumed* are choices the config leaves open, listed under
``assumed`` in the configuration file, each the program's too::

    full layer, queries
        cq = RMSNorm(h W_dq) (q_lora_rank)
        [q_nope | q_rope]_i = cq W_uq, num_attention_heads heads of
        (qk_nope_head_dim | qk_rope_head_dim); q_rope rotated at the
        token's position, rope_theta
    full layer, keys and values
        [ckv | k_rope] = h W_dkv (kv_lora_rank | qk_rope_head_dim)
        ckv = RMSNorm(ckv); k_rope rotated, one for all heads
        [k_nope | v]_i = ckv W_ukv (qk_nope_head_dim | v_head_dim a head)
    apply_mla_qkv_lora_rescale (*assumed*: LongCat-Flash's mla_scale_q_lora
        / mla_scale_kv_lora): cq *= sqrt(hidden / q_lora_rank), ckv *=
        sqrt(hidden / kv_lora_rank), after their norms
    indexer
        qI_j = cq W_qI, index_n_heads heads of index_head_dim (*assumed*:
        from the rescaled cq); kI = LayerNorm(h W_kI) (weight and bias,
        eps rms_norm_eps); the FIRST index_rope_dim = 64 dims of each
        rotated, rope_theta (*assumed*: which 64); w = h W_w *
        index_n_heads^-0.5 * index_head_dim^-0.5
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
        S_t = the index_topk positions of largest I[t, .], all of them
        while t < index_topk; ties to the lower position (*assumed*).
        EXACT. DeepSeek-V3.2's fp8 storage and Hadamard rotation of the
        index keys are an implementation's and are left out
    full layer, attention
        a[t, s, i] = (q_nope_i . k_nope_i[s] + q_rope_i . k_rope[s])
                     / sqrt(qk_nope_head_dim + qk_rope_head_dim), s in S_t
        softmax over S_t; o_i = sum_s p v_i[s]
        g = sigmoid(h W_g), one a head; o_i *= g_i (*assumed*: the headwise
        form of the gated-attention paper: from the block's normed input,
        on the heads' outputs before W_o); out = concat(o) W_o
    sliding layer
        the same at the swa_* sizes, swa_rope_theta, no indexer, keys
        t - sliding_window_size < s <= t (*assumed*: the window counts the
        token itself)
    experts (layers >= first_k_dense_replace)
        sc = sigmoid(h W_r), float32, all n_routed_experts; chosen = the
        num_experts_per_tok largest of sc + b (noaux_tc, one group: the
        config has no n_group); weights = sc of the chosen over their sum
        (norm_topk_prob) * routed_scaling_factor
        y = sum_e w_e E_e(h) + E_shared(h),  E(h) = (silu(h W_gate) * h
        W_up) W_down at moe_intermediate_size
    layer < first_k_dense_replace: the same MLP at intermediate_size

Rotation pairs element ``i < hd / 2`` with ``i + hd / 2`` (the half form;
*assumed*, and it does not show under seeded weights). ``W_gate`` and
``W_up`` are stored as one matrix ``w1`` (columns gate | up).

**The share.** ``held = (lo, hi)`` are the routed experts this chip holds
(``n_routed_experts`` of the configuration file, from ``experts_first``;
the router keeps ``router_outputs``). Routing is over all the router's
outputs with the weights above; only held experts' terms are added, and
that partial result goes on to the next layer. The vocabulary is the slice
the file gives. With ``held`` = all experts this is the published layer.

Weights are a flat dict of arrays named by layer (``leaf_shapes``):
``L<i>.a.*`` the attention, ``L<i>.f.*`` the MLP or the router, HELD
experts and shared expert. Linear weights are ``[in, out]``. The arrays may
be held in bfloat16: a layer's leaves are widened to float32 as that layer
runs (exact).

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first. Five further values
name a WRONG model in float32, for the controls that the comparison must
fail: ``"all_keys"`` (every key in sight attended), ``"last_topk"`` (the
last ``index_topk`` in place of the indexer's choice), ``"window_less_1"``
(a window one shorter), ``"no_gate"``, ``"softmax_router"`` (the chosen
experts weighed by a softmax over their logits).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
WRONG = ("all_keys", "last_topk", "window_less_1", "no_gate",
         "softmax_router")
QUERY_BLOCK = 128       # queries whose [block, T] scores are held at a time
HEAD_GROUP = 16         # heads whose queries, keys and values exist at a time


class Attn(NamedTuple):
    heads: int
    q_rank: int
    rank: int
    dn: int
    dr: int
    dv: int
    theta: float
    sq: float
    skv: float


class Sizes(NamedTuple):
    d: int
    types: tuple
    vocab: int
    first_dense: int
    f_dense: int
    f: int
    experts: int
    top_k: int
    held: tuple
    route_scale: float
    full: Attn
    swa: Attn
    hi: int
    di: int
    topk: int
    i_rope: int
    window: int
    eps: float

    @property
    def n_held(self):
        return self.held[1] - self.held[0]

    def attn(self, i) -> Attn:
        return self.full if self.types[i] == "full_attention" else self.swa


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs, from the configuration's keys:
    the published ``config.json`` names; ``n_routed_experts`` is the count
    HELD (``n_routed_experts_published`` beside it) from ``experts_first``
    on, the router keeps ``router_outputs``."""
    n = cfg["num_hidden_layers"]
    types = tuple(cfg["layer_types"])[:n]
    if len(types) != n:
        raise ValueError(f"{n} layers but {len(types)} layer_types")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc" \
            or not cfg["norm_topk_prob"] or cfg["n_shared_experts"] != 1:
        raise ValueError("only the published router is written: sigmoid, "
                         "noaux_tc, normalised, one shared expert")
    if cfg["attention_gate_type"] != "headwise" \
            or cfg["swa_attention_gate_type"] != "headwise":
        raise ValueError("only headwise gates are written")
    d = cfg["hidden_size"]
    on = bool(cfg["apply_mla_qkv_lora_rescale"])

    def attn(pre, theta):
        qr, r = cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"]
        return Attn(cfg[pre + "num_attention_heads"], qr, r,
                    cfg[pre + "qk_nope_head_dim"],
                    cfg[pre + "qk_rope_head_dim"], cfg[pre + "v_head_dim"],
                    float(theta), math.sqrt(d / qr) if on else 1.0,
                    math.sqrt(d / r) if on else 1.0)

    lo = cfg.get("experts_first", 0)
    return Sizes(
        d=d, types=types, vocab=cfg["vocab_size"],
        first_dense=cfg["first_k_dense_replace"],
        f_dense=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        held=(lo, lo + cfg["n_routed_experts"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        full=attn("", cfg["rope_theta"]),
        swa=attn("swa_", cfg["swa_rope_theta"]), hi=cfg["index_n_heads"],
        di=cfg["index_head_dim"], topk=cfg["index_topk"],
        i_rope=cfg["assumed"]["index_rope_dim"],
        window=cfg["sliding_window_size"], eps=cfg["rms_norm_eps"])


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    d = s.d
    out = {"embed": (s.vocab, d), "head": (s.vocab, d), "norm_f.w": (d,)}
    for i, kind in enumerate(s.types):
        a = s.attn(i)
        leaves = {"norm.w": (d,), "dq": (d, a.q_rank),
                  "q_norm.w": (a.q_rank,),
                  "uq": (a.q_rank, a.heads * (a.dn + a.dr)),
                  "dkv": (d, a.rank + a.dr), "kv_norm.w": (a.rank,),
                  "ukv": (a.rank, a.heads * (a.dn + a.dv)),
                  "gate": (d, a.heads), "o": (a.heads * a.dv, d)}
        if kind == "full_attention":
            leaves.update({"iq": (a.q_rank, s.hi * s.di), "ik": (d, s.di),
                           "ik_norm.w": (s.di,), "ik_norm.b": (s.di,),
                           "iw": (d, s.hi)})
        out.update({f"L{i}.a.{k}": v for k, v in leaves.items()})
        if i < s.first_dense:
            ffn = {"norm.w": (d,), "w1": (d, 2 * s.f_dense),
                   "w2": (s.f_dense, d)}
        else:
            ffn = {"norm.w": (d,), "router": (d, s.experts),
                   "bias": (s.experts,), "w1": (s.n_held, d, 2 * s.f),
                   "w2": (s.n_held, s.f, d), "shared.w1": (d, 2 * s.f),
                   "shared.w2": (s.f, d)}
        out.update({f"L{i}.f.{k}": v for k, v in ffn.items()})
    return out


def param_count(cfg: dict) -> int:
    return sum(math.prod(v) for v in leaf_shapes(cfg).values())


def _arith(precision):
    """The precision the products run in: a WRONG model runs in f32."""
    return "f32" if precision in WRONG else precision


def _mm(eq, a, b, precision):
    to = _ROUND[_arith(precision)]
    if to is not None:
        a, b = a.astype(to), b.astype(to)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                               + eps) * w + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta, first=0):
    """Half-rotation rotary over the last axis of ``x`` [T, ..., hd] at
    positions ``first .. first + T - 1``."""
    t, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd))
    ang = (first + jnp.arange(t)).astype(jnp.float32)[:, None] * inv[None]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def gated(b, w1, w2, precision):
    """(silu(gate) * up) W_down with [gate | up] = b w1."""
    u, v = jnp.split(_mm("td,dk->tk", b, w1, precision), 2, -1)
    return _mm("tk,kd->td", silu(u) * v, w2, precision)


def route(b, w_router, bias, top_k, scale, precision):
    """Weights [T, experts] f32, zero where an expert was not chosen."""
    logits = _mm("td,de->te", b, w_router, precision)
    sc = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(sc + bias, top_k)
    rows = jnp.arange(b.shape[0])[:, None]
    if precision == "softmax_router":
        g = jax.nn.softmax(logits[rows, idx], axis=-1)
    else:
        top = sc[rows, idx]
        g = top / top.sum(-1, keepdims=True) * scale
    return jnp.zeros_like(logits).at[rows, idx].set(g)


def experts(b, p, s, precision):
    """The held experts' part of the routed layer, every token through each
    held expert, and the shared expert."""
    gates = route(b, p["router"], p["bias"], s.top_k, s.route_scale,
                  precision)

    def one(e, out):
        g = jax.lax.dynamic_index_in_dim(gates, s.held[0] + e, 1)
        return out + g * gated(b, p["w1"][e], p["w2"][e], precision)

    out = jax.lax.fori_loop(0, s.n_held, one, jnp.zeros_like(b))
    return out + gated(b, p["shared.w1"], p["shared.w2"], precision)


def select_mask(scores, qpos, topk, precision):
    """Which keys each query attends. scores : [Q, T] the indexer's ``I``;
    qpos : [Q] the queries' positions. Returns bool [Q, T]: among ``s <=
    qpos`` the ``topk`` of largest score, ties to the lower position; all
    of them while there are no more than ``topk``."""
    s = jnp.arange(scores.shape[1])
    sight = s[None, :] <= qpos[:, None]
    if precision == "all_keys":
        return sight
    if precision == "last_topk":
        return sight & (s[None, :] > qpos[:, None] - topk)
    sc = jnp.where(sight, scores, -jnp.inf)
    kth = jnp.sort(sc, axis=-1)[:, -min(topk, sc.shape[1])][:, None]
    above = sc > kth
    level = (sc == kth) & sight
    room = topk - above.sum(-1, keepdims=True)
    chosen = above | (level & (jnp.cumsum(level, axis=-1) <= room))
    return jnp.where((qpos[:, None] < topk), sight, chosen)


def _latents(a, p, at: Attn, eps, precision):
    """(cq [T, q_rank], ckv [T, rank], k_rope [T, dr] rotated): what all
    heads share."""
    cq = rms_norm(_mm("td,dk->tk", a, p["dq"], precision), p["q_norm.w"],
                  eps) * at.sq
    kv = _mm("td,dk->tk", a, p["dkv"], precision)
    ckv = rms_norm(kv[:, :at.rank], p["kv_norm.w"], eps) * at.skv
    return cq, ckv, rope(kv[:, at.rank:], at.theta)


def _attend(a, cq, ckv, k_rope, p, at: Attn, mask_of, precision, block):
    """Per-head attention of every query over the keys ``mask_of(q0)``
    [block, T] lets it see, gated and projected: [T, d]. A group of heads
    at a time (their queries, keys and values are made, used and dropped:
    at 34,000 tokens all heads' at once are 8 GB), a block of queries at a
    time inside it; the groups' parts of the out-projection add up."""
    t = a.shape[0]
    hg = min(HEAD_GROUP, at.heads)
    n = at.heads // hg
    scale = 1.0 / math.sqrt(at.dn + at.dr)
    w_uq = jnp.moveaxis(p["uq"].reshape(at.q_rank, n, hg, at.dn + at.dr), 1,
                        0)
    w_ukv = jnp.moveaxis(p["ukv"].reshape(at.rank, n, hg, at.dn + at.dv), 1,
                         0)
    w_o = p["o"].reshape(n, hg, at.dv, -1)
    gate = jnp.ones((t, at.heads)) if precision == "no_gate" else \
        jax.nn.sigmoid(_mm("td,dh->th", a, p["gate"], precision))
    gate = jnp.moveaxis(gate.reshape(t, n, hg), 1, 0)

    def group(out, args):
        wq, wkv, wo, g = args
        q = _mm("tc,chd->thd", cq, wq, precision)         # [T, hg, dn + dr]
        qn, qr = q[..., :at.dn], rope(q[..., at.dn:], at.theta)
        kv = _mm("tc,chd->thd", ckv, wkv, precision)      # [T, hg, dn + dv]

        def rows(q0):
            qn_b = jax.lax.dynamic_slice_in_dim(qn, q0, block, 0)
            qr_b = jax.lax.dynamic_slice_in_dim(qr, q0, block, 0)
            sc = (_mm("qhd,shd->hqs", qn_b, kv[..., :at.dn], precision)
                  + _mm("qhr,sr->hqs", qr_b, k_rope, precision)) * scale
            pr = jax.nn.softmax(jnp.where(mask_of(q0)[None], sc, -1e30),
                                axis=-1)
            return _mm("hqs,shv->qhv", pr, kv[..., at.dn:], precision)

        o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, hg, at.dv)
        return out + _mm("thv,hvd->td", o * g[..., None], wo, precision), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(a), (w_uq, w_ukv, w_o, gate))
    return out


def _block_of(t):
    b = min(QUERY_BLOCK, t)
    while t % b:
        b -= 1
    return b


def _index_operands(a, cq, p, s: Sizes, precision):
    """The index keys [T, DI], rotated, and the head weights [T, HI]."""
    ki = layer_norm(_mm("td,dk->tk", a, p["ik"], precision), p["ik_norm.w"],
                    p["ik_norm.b"], s.eps)
    ki = jnp.concatenate([rope(ki[..., :s.i_rope], s.full.theta),
                          ki[..., s.i_rope:]], -1)
    w = _mm("td,dh->th", a, p["iw"], precision) * (s.hi ** -0.5
                                                   * s.di ** -0.5)
    return ki, w


def _index_scores(cq, p, ki, w, s: Sizes, q0, block, precision):
    """``I[q0 : q0 + block, :]``: the block's index queries are made here
    (at their positions) and dropped."""
    qi = _mm("tk,kn->tn", jax.lax.dynamic_slice_in_dim(cq, q0, block, 0),
             p["iq"], precision).reshape(block, s.hi, s.di)
    qi = jnp.concatenate([rope(qi[..., :s.i_rope], s.full.theta, q0),
                          qi[..., s.i_rope:]], -1)
    wb = jax.lax.dynamic_slice_in_dim(w, q0, block, 0)
    sc = jnp.maximum(_mm("qhd,sd->qhs", qi, ki, precision), 0.0)
    return (sc * wb[..., None]).sum(1)


def full_attention(a, p, s: Sizes, precision):
    """A full layer's attention over a [T, d] (normed input)."""
    at, t = s.full, a.shape[0]
    block = _block_of(t)
    cq, ckv, k_rope = _latents(a, p, at, s.eps, precision)
    ki, w = _index_operands(a, cq, p, s, precision)

    def mask_rows(q0):
        return select_mask(_index_scores(cq, p, ki, w, s, q0, block,
                                         precision),
                           q0 + jnp.arange(block), s.topk, precision)

    # the whole [T, T] mask once (bool), a block of queries at a time
    mask = jax.lax.map(mask_rows, jnp.arange(0, t, block)).reshape(t, t)
    return _attend(a, cq, ckv, k_rope, p, at,
                   lambda q0: jax.lax.dynamic_slice_in_dim(mask, q0, block,
                                                           0),
                   precision, block)


def sliding_attention(a, p, s: Sizes, precision):
    """A sliding layer's attention over a [T, d] (normed input)."""
    at, t = s.swa, a.shape[0]
    block = _block_of(t)
    window = s.window - (1 if precision == "window_less_1" else 0)
    cq, ckv, k_rope = _latents(a, p, at, s.eps, precision)
    pos = jnp.arange(t)

    def mask_of(q0):
        qp = q0 + jnp.arange(block)
        return (pos[None, :] <= qp[:, None]) \
            & (pos[None, :] > qp[:, None] - window)

    return _attend(a, cq, ckv, k_rope, p, at, mask_of, precision, block)


def _widen(w, prefix):
    """One layer's leaves in float32, without the prefix; the held experts'
    stacks (3 GB in float32 at the published widths) stay as they are
    stored and are widened one expert at a time, by the product."""
    return {name[len(prefix):]: x if x.ndim == 3 else x.astype(jnp.float32)
            for name, x in w.items() if name.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("s", "kind", "dense",
                                             "precision"))
def layer(x, pa, pf, s, kind, dense, precision):
    a = rms_norm(x, pa["norm.w"], s.eps)
    attn = full_attention if kind == "full_attention" else sliding_attention
    x = x + attn(a, pa, s, precision)
    b = rms_norm(x, pf["norm.w"], s.eps)
    if dense:
        return x + gated(b, pf["w1"], pf["w2"], precision)
    return x + experts(b, pf, s, precision)


def hidden(w, ids, cfg, precision="f32"):
    """The stack's last hidden state [T, d] for one sequence ``ids`` [T].
    One layer at a time (each kind of layer compiles once), that layer's
    leaves widened to float32 as it runs."""
    s = sizes(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    for i, kind in enumerate(s.types):
        x = layer(x, _widen(w, f"L{i}.a."), _widen(w, f"L{i}.f."), s, kind,
                  i < s.first_dense, precision)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head(h, norm_w, head_w, eps, precision):
    h = rms_norm(h, norm_w.astype(jnp.float32), eps)
    return _mm("td,vd->tv", h, head_w.astype(jnp.float32), precision)


def logits(w, ids, cfg, precision="f32", rows=None):
    """Logits [T, V] of one sequence (``rows``: only those positions)."""
    s = sizes(cfg)
    h = hidden(w, ids, cfg, precision)
    if rows is not None:
        h = h[rows]
    return head(h, w["norm_f.w"], w["head"], s.eps, precision)


def selection_overlap(w, ids, cfg, layer_index):
    """Of the keys the indexer of layer ``layer_index`` picks for the
    queries past ``index_topk``, the share that lies among the last
    ``index_topk`` positions, and the share of those queries whose choice
    differs from that window at all: what tells a selection from a window
    under seeded weights. Computed on the stack's true hidden states."""
    s = sizes(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    for i, kind in enumerate(s.types[:layer_index]):
        x = layer(x, _widen(w, f"L{i}.a."), _widen(w, f"L{i}.f."), s, kind,
                  i < s.first_dense, "f32")
    return _overlap(rms_norm(x, w[f"L{layer_index}.a.norm.w"].astype(
        jnp.float32), s.eps), _widen(w, f"L{layer_index}.a."), s)


@functools.partial(jax.jit, static_argnames=("s",))
def _overlap(a, p, s):
    at, t = s.full, a.shape[0]
    block = _block_of(t)
    cq, _, _ = _latents(a, p, at, s.eps, "f32")
    ki, w = _index_operands(a, cq, p, s, "f32")

    def rows(q0):
        sc = _index_scores(cq, p, ki, w, s, q0, block, "f32")
        qp = q0 + jnp.arange(block)
        pick = select_mask(sc, qp, s.topk, "f32")
        last = select_mask(sc, qp, s.topk, "last_topk")
        past = qp >= s.topk
        return ((pick & last).sum(-1) * past, (pick != last).any(-1) & past,
                past)

    both, differs, past = jax.lax.map(rows, jnp.arange(0, t, block))
    n = jnp.maximum(past.sum(), 1)
    return {"queries_past_topk": past.sum(),
            "keys_shared_with_last_topk": both.sum() / (n * s.topk),
            "queries_that_differ": differs.sum() / n}
