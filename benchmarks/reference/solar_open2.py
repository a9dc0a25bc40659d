"""Plain Solar Open 2 (``solar_open2``, Hugging Face
``upstage/Solar-Open2-250B``): the forward pass of the language model's
stack, whole or as one chip's share of its routed experts and vocabulary,
and nothing else.

The yardstick's reference for the ``solar_open2`` family. Straightforward
``jax.numpy`` in float32 with matrix multiplications at ``highest``
precision; no kernel, no cache, no chunk, no batching: the delta rule as
its TOKEN RECURRENCE (one ``lax.scan`` step a token, never the chunked
form), softmax attention as a plain masked softmax over every earlier
token. It imports nothing of ``paddle_tpu`` and is handed only the weights
the benchmark made from the seed (``harness/solar_weights.py``).

From the published ``config.json`` keys; lines marked *assumed* are
readings the key set does not settle, listed under ``assumed`` in the
configuration file, each the program's too. ``x`` is a token's residual
row; every norm is RMS over the last dimension with a scale ``w``,
``rms_norm_eps``::

    block    pre-norm (*assumed*: the family's):
             x += mixer(N1(x));  x += experts(N2(x));  a = N1(x)
    linear layer (every layer not in gqa_layers; Kimi Delta Attention,
    arXiv:2510.26692, linear_attn_config: num_heads x head_dim, num_kv_heads
    null = as many)
             q~ = a W_q, k~ = a W_k, v~ = a W_v (stored as one W_qkv, columns
             q | k | v); each through its OWN depthwise causal convolution
             of short_conv_kernel_size taps, the LAST on the token itself,
             no bias, then silu (*assumed*: no bias, silu)
             q = q~ / |q~| / sqrt(dk), k = k~ / |k~| per head (x * rsqrt(sum
             x^2 + 1e-6)) (*assumed*);  v = v~
             log decay PER KEY CHANNEL: g = -exp(A_log_h) softplus((a W_fa)
             W_fb + dt_bias), [heads, dk] (kda_use_full_proj false: the
             bottleneck W_fa [hidden, rank], W_fb [rank, heads dk]; rank =
             head_dim, A_log one a head, dt_bias one a channel: *assumed*)
             beta = 2 sigmoid(a W_b), one a head (kda_allow_neg_eigval
             true: the factor 2)
             per head h, S_h a dk x dv state from zero:
               S = Diag(exp(g)) S;  u = beta (v - S^T k);  S = S + k u^T
               o = S^T q
             y_h = (o / rms(o) * w_o) * sigmoid((a W_ga) W_gb + b_g)_h, w_o
             one vector of dv shared by the heads (*assumed*: the gate's
             rank, its bias);  out = concat(y) W_out
    softmax layer (gqa_layers)
             q = a W_q (num_attention_heads x head_dim), k = a W_k, v = a
             W_v (num_key_value_heads x head_dim each; stored as one W_qkv);
             use_rope false: NO rotation and no other position signal
             scores q . k * head_dim^-1/2; causal softmax over ALL earlier
             tokens; query head j reads key-value head j // (heads / kv
             heads)
             y = (att * sigmoid(a W_g)) W_o, W_g [hidden, heads x head_dim]
             (*assumed*: use_gqa_gate is the elementwise head-specific gate
             of the gated-attention paper)
    experts (every layer: first_k_dense_replace 0)
             sc = sigmoid(b W_r), float32, all router outputs; chosen = the
             num_experts_per_tok largest of sc + bias (one group); weights
             sc of the chosen over their sum * routed_scaling_factor
             (*assumed*: DeepSeek-V3's keys read as DeepSeek-V3's router)
             y = sum_e w_e E_e(b) + E_shared(b), E(b) = W_2 (silu(b W_g) *
             b W_u); the shared expert's width n_shared_experts x
             moe_intermediate_size, added ungated (*assumed*)
    head     N_f(x) head^T, untied

**The share.** ``held = (lo, hi)`` are the routed experts this chip holds
(``n_routed_experts`` of the configuration file, from ``experts_first``;
the router keeps ``router_outputs``). Routing is over all the router's
outputs with the weights above; only held experts' terms are added, and
that partial result goes on to the next layer. The vocabulary is the slice
the file gives. With ``held`` = all experts this is the published layer.

Weights are a flat dict of arrays named by layer (``leaf_shapes``):
``L<i>.n.{1,2}`` the two norms, ``L<i>.d.*`` a linear layer's mixer,
``L<i>.a.*`` a softmax layer's, ``L<i>.f.*`` the router, HELD experts and
shared expert. Linear weights are ``[in, out]``; ``W_gate`` and ``W_up``
are one matrix ``w1`` (columns gate | up). The arrays may be held in
bfloat16: a layer's leaves are widened to float32 as that layer runs
(exact).

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first. Further values name a
WRONG model in float32, for the controls that the comparison must fail,
each undoing what this family brings: ``"scalar_decay"`` (a head's channel
decays replaced by their mean: the scalar-gated delta rule),
``"beta_half"`` (``beta = sigmoid``, no factor 2), ``"drop_state"`` (the
linear layers' carried state, matrix and convolution inputs, forgotten at
position ``drop_at``: what a chunk boundary that does not carry would do).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
WRONG = ("scalar_decay", "beta_half", "drop_state")
QUERY_BLOCK = 128       # queries whose [block, T] scores are held at a time
HEAD_GROUP = 16         # linear heads whose q, k, v exist at a time
_HI = jax.lax.Precision.HIGHEST


class Sizes(NamedTuple):
    d: int
    layers: int
    softmax: tuple          # the softmax layers among ``layers``
    vocab: int
    f: int
    experts: int
    top_k: int
    held: tuple
    route_scale: float
    shared: int             # the shared expert's width
    heads: int
    kv_heads: int
    hd: int
    lin_heads: int
    dk: int
    taps: int
    rank: int
    eps: float

    @property
    def n_held(self):
        return self.held[1] - self.held[0]

    @property
    def lin_width(self):
        return self.lin_heads * self.dk


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs, from the configuration's keys:
    the published ``config.json`` names; ``n_routed_experts`` is the count
    HELD (``n_routed_experts_published`` beside it) from ``experts_first``
    on, the router keeps ``router_outputs``; ``gqa_layers`` lists the
    softmax layers among the ``num_hidden_layers`` kept."""
    n = cfg["num_hidden_layers"]
    soft = tuple(cfg["gqa_layers"])
    if not set(soft) <= set(range(n)):
        raise ValueError(f"gqa_layers {sorted(soft)} of {n}")
    if cfg["first_k_dense_replace"] != 0 or not cfg["norm_topk_prob"]:
        raise ValueError("only the published expert layers are written: "
                         "no dense layer, chosen scores normalised")
    if cfg["use_rope"] or not cfg["use_gqa_gate"] \
            or cfg["kda_use_full_proj"] or not cfg["kda_allow_neg_eigval"]:
        raise ValueError("only the published mixers are written: no "
                         "rotation, gated softmax attention, low-rank "
                         "decay projection, beta up to 2")
    lin = cfg["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("linear key heads other than one a head")
    lo = cfg.get("experts_first", 0)
    return Sizes(
        d=cfg["hidden_size"], layers=n, softmax=soft,
        vocab=cfg["vocab_size"], f=cfg["moe_intermediate_size"],
        experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        held=(lo, lo + cfg["n_routed_experts"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        lin_heads=lin["num_heads"], dk=lin["head_dim"],
        taps=lin["short_conv_kernel_size"],
        rank=lin["head_dim"],
        eps=float(cfg["rms_norm_eps"]))


def layer_leaves(s: Sizes, softmax: bool) -> dict:
    """One layer's leaves by part: ``n`` norms, ``a`` / ``d`` the mixer,
    ``f`` router, held experts and shared expert."""
    d, w = s.d, s.lin_width
    qw, kw = s.heads * s.hd, s.kv_heads * s.hd
    if softmax:
        mixer = {"qkv": (d, qw + 2 * kw), "gate": (d, qw), "o": (qw, d)}
    else:
        mixer = {"qkv": (d, 3 * w), "conv": (s.taps, 3 * w),
                 "fa": (d, s.rank), "fb": (s.rank, w), "A_log": (s.lin_heads,),
                 "dt_bias": (w,), "b": (d, s.lin_heads), "o_norm.w": (s.dk,),
                 "ga": (d, s.rank), "gb": (s.rank, w), "gb.bias": (w,),
                 "out": (w, d)}
    return {"n": {"1": (d,), "2": (d,)}, "a" if softmax else "d": mixer,
            "f": {"router": (d, s.experts), "bias": (s.experts,),
                  "w1": (s.n_held, d, 2 * s.f), "w2": (s.n_held, s.f, d),
                  "shared.w1": (d, 2 * s.shared),
                  "shared.w2": (s.shared, d)}}


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    out = {"embed": (s.vocab, s.d), "head": (s.vocab, s.d),
           "norm_f.w": (s.d,)}
    for i in range(s.layers):
        for part, leaves in layer_leaves(s, i in s.softmax).items():
            out.update({f"L{i}.{part}.{k}": v for k, v in leaves.items()})
    return out


def _count(shapes) -> int:
    return sum(math.prod(v) for v in shapes.values())


def param_count(cfg: dict) -> dict:
    """Parameters BY PARTS, of this file's cut (``held``) and of the
    published model (every layer, every expert, the whole vocabulary): what
    every run prints, and what the configuration file's arithmetic is held
    to."""
    s = sizes(cfg)
    lin, soft = (layer_leaves(s, k) for k in (False, True))
    expert = 3 * s.d * s.f
    parts = {"kda_mixer": _count(lin["d"]), "softmax_mixer": _count(soft["a"]),
             "one_expert": expert,
             "router_with_bias": s.d * s.experts + s.experts,
             "shared_expert": 3 * s.d * s.shared, "two_norms": 2 * s.d}
    outside = parts["router_with_bias"] + parts["shared_expert"] \
        + parts["two_norms"]
    parts["linear_layer_outside_experts"] = parts["kda_mixer"] + outside
    parts["softmax_layer_outside_experts"] = parts["softmax_mixer"] + outside
    tables = 2 * s.vocab * s.d + s.d
    n_soft = len(s.softmax)
    held = (s.layers - n_soft) * parts["linear_layer_outside_experts"] \
        + n_soft * parts["softmax_layer_outside_experts"] \
        + s.layers * s.n_held * expert + tables
    parts["tables_and_final_norm"] = tables
    parts["held"] = held
    if held != _count(leaf_shapes(cfg)):
        raise ValueError("the count by parts is not the leaves' count")
    n_pub = cfg.get("num_hidden_layers_published", s.layers)
    soft_pub = len(cfg.get("gqa_layers_published", s.softmax))
    vocab_pub = cfg.get("vocab_size_published", s.vocab)
    per_layer = (n_pub - soft_pub) * parts["linear_layer_outside_experts"] \
        + soft_pub * parts["softmax_layer_outside_experts"]
    parts["published"] = per_layer + n_pub * s.experts * expert \
        + 2 * vocab_pub * s.d + s.d
    parts["published_active"] = per_layer + n_pub * s.top_k * expert \
        + 2 * vocab_pub * s.d + s.d
    return parts


def _arith(precision):
    """The precision the products run in: a WRONG model runs in f32."""
    return "f32" if precision in WRONG else precision


def _mm(eq, a, b, precision):
    to = _ROUND[_arith(precision)]
    if to is not None:
        a, b = a.astype(to), b.astype(to)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=_HI, preferred_element_type=jnp.float32)


def norm(x, w, s: Sizes):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + s.eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def gated(b, w1, w2, precision):
    """W_2 (silu(gate) * up) with [gate | up] = b w1."""
    u, v = jnp.split(_mm("td,dk->tk", b, w1, precision), 2, -1)
    return _mm("tk,kd->td", silu(u) * v, w2, precision)


def route(b, w_router, bias, s: Sizes, precision):
    """Weights [T, experts] f32, zero where an expert was not chosen."""
    sc = jax.nn.sigmoid(_mm("td,de->te", b, w_router, precision))
    _, idx = jax.lax.top_k(sc + bias, s.top_k)
    rows = jnp.arange(b.shape[0])[:, None]
    top = sc[rows, idx]
    g = top / top.sum(-1, keepdims=True) * s.route_scale
    return jnp.zeros_like(sc).at[rows, idx].set(g)


def experts(b, p, s: Sizes, precision, shared=True):
    """The held experts' part of the routed layer, every token through each
    held expert, and (``shared``) the shared expert."""
    gates = route(b, p["router"], p["bias"], s, precision)

    def one(e, out):
        g = jax.lax.dynamic_index_in_dim(gates, s.held[0] + e, 1)
        return out + g * gated(b, p["w1"][e], p["w2"][e], precision)

    out = jax.lax.fori_loop(0, s.n_held, one, jnp.zeros_like(b))
    if shared:
        out = out + gated(b, p["shared.w1"], p["shared.w2"], precision)
    return out


def delta_rule(q, k, v, beta, g, reset=None):
    """The token recurrence. q, k : [T, H, dk]; v : [T, H, dv]; beta : [T,
    H]; g : [T, H, dk] log decay a key channel; reset : [T] bool or None,
    the state forgotten BEFORE that token. Returns o [T, H, dv]."""
    t, h, dk = k.shape
    if reset is None:
        reset = jnp.zeros(t, bool)

    def step(state, x):
        qt, kt, vt, bt, gt, rt = x
        state = jnp.where(rt, 0.0, state) * jnp.exp(gt)[:, :, None]
        read = jnp.einsum("hkv,hk->hv", state, kt, precision=_HI)
        u = bt[:, None] * (vt - read)
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
                        (q, k, v, beta, g, reset))
    return o


def linear_attention(a, p, s: Sizes, precision, drop_at=None):
    """A linear layer's mixer over a [T, d] (normed input), `HEAD_GROUP`
    heads at a time (the convolution is depthwise and the rule per head:
    at 18,000 tokens all heads' q, k and v at once are 1.8 GB, several
    times over); the groups' parts of the out-projection add up."""
    t = a.shape[0]
    pos = jnp.arange(t)
    drop = precision == "drop_state" and drop_at is not None
    hg = min(HEAD_GROUP, s.lin_heads)
    n, dk = s.lin_heads // hg, s.dk

    def by_group(w, lead):
        """[lead, heads * dk] -> [groups, lead, hg * dk]"""
        return jnp.moveaxis(w.reshape(lead, n, hg * dk), 1, 0)

    def qkv_groups(w, lead):
        """[lead, 3 * heads * dk] -> [groups, lead, 3, hg * dk]"""
        return jnp.moveaxis(w.reshape(lead, 3, n, hg * dk), 2, 0)

    fa = _mm("td,dr->tr", a, p["fa"], precision)
    ga = _mm("td,dr->tr", a, p["ga"], precision)
    beta = jax.nn.sigmoid(_mm("td,dh->th", a, p["b"], precision))
    if precision != "beta_half":
        beta = 2.0 * beta
    args = (qkv_groups(p["qkv"], s.d), qkv_groups(p["conv"], s.taps),
            by_group(p["fb"], s.rank), p["dt_bias"].reshape(n, hg * dk),
            p["A_log"].reshape(n, hg), by_group(p["gb"], s.rank),
            p["gb.bias"].reshape(n, hg * dk), p["out"].reshape(n, hg * dk, -1),
            beta.reshape(t, n, hg).swapaxes(0, 1))

    def group(out, x):
        w_qkv, w_conv, w_fb, dt_bias, a_log, w_gb, b_g, w_out, bt = x
        xin = _mm("td,dck->tck", a, w_qkv, precision)       # [T, 3, hg dk]
        xp = jnp.pad(xin, ((s.taps - 1, 0), (0, 0), (0, 0)))
        conv = 0.0
        for j in range(s.taps):
            src = pos - (s.taps - 1 - j)             # the input's position
            tap = xp[j:j + t]
            if drop:     # an input from before the drop is forgotten
                tap = jnp.where(((pos >= drop_at) & (src < drop_at))
                                [:, None, None], 0.0, tap)
            conv = conv + w_conv[j] * tap
        x3 = silu(conv).reshape(t, 3, hg, dk)
        q, k, v = x3[:, 0], x3[:, 1], x3[:, 2]

        def unit(u):
            return u * jax.lax.rsqrt((u * u).sum(-1, keepdims=True) + 1e-6)

        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            _mm("tr,rk->tk", fa, w_fb, precision) + dt_bias
        ).reshape(t, hg, dk)
        if precision == "scalar_decay":
            g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        o = delta_rule(unit(q) * dk ** -0.5, unit(k), v, bt, g,
                       (pos == drop_at) if drop else None)
        gate = jax.nn.sigmoid(_mm("tr,rk->tk", ga, w_gb, precision)
                              + b_g).reshape(t, hg, dk)
        y = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + s.eps) \
            * p["o_norm.w"] * gate
        return out + _mm("tk,kd->td", y.reshape(t, -1), w_out,
                         precision), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(a), args)
    return out


def _block_of(t):
    b = min(QUERY_BLOCK, t)
    while t % b:
        b -= 1
    return b


def softmax_attention(a, p, s: Sizes, precision):
    """A softmax layer's mixer over a [T, d] (normed input): a plain masked
    softmax, one key-value head (with the query heads that read it) at a
    time and a block of queries at a time inside it (at 18,000 tokens all
    heads' scores at once are 85 GB); the heads' parts of the
    out-projection add up."""
    t = a.shape[0]
    block = _block_of(t)
    g = s.heads // s.kv_heads
    qw, kw = s.heads * s.hd, s.kv_heads * s.hd
    qkv = _mm("td,dk->tk", a, p["qkv"], precision)
    q = jnp.moveaxis(qkv[:, :qw].reshape(t, s.kv_heads, g, s.hd), 1, 0)
    k = jnp.moveaxis(qkv[:, qw:qw + kw].reshape(t, s.kv_heads, s.hd), 1, 0)
    v = jnp.moveaxis(qkv[:, qw + kw:].reshape(t, s.kv_heads, s.hd), 1, 0)
    gate = jax.nn.sigmoid(_mm("td,dk->tk", a, p["gate"], precision))
    gate = jnp.moveaxis(gate.reshape(t, s.kv_heads, g, s.hd), 1, 0)
    w_o = p["o"].reshape(s.kv_heads, g, s.hd, -1)
    pos = jnp.arange(t)
    scale = s.hd ** -0.5

    def head(out, x):
        qh, kh, vh, gh, wo = x             # [T, g, hd], [T, hd], [T, hd]

        def rows(q0):
            qb = jax.lax.dynamic_slice_in_dim(qh, q0, block, 0)
            sc = _mm("qgd,sd->gqs", qb, kh, precision) * scale
            see = pos[None, :] <= (q0 + jnp.arange(block))[:, None]
            pr = jax.nn.softmax(jnp.where(see[None], sc, -1e30), axis=-1)
            return _mm("gqs,sd->qgd", pr, vh, precision)

        o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, g, s.hd)
        return out + _mm("tgd,gdm->tm", o * gh, wo, precision), None

    out, _ = jax.lax.scan(head, jnp.zeros_like(a), (q, k, v, gate, w_o))
    return out


def _widen(w, prefix):
    """One layer's leaves in float32, without the prefix; the held experts'
    stacks stay as they are stored and are widened one expert at a time,
    by the product."""
    return {name[len(prefix):]: x if x.ndim == 3 else x.astype(jnp.float32)
            for name, x in w.items() if name.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("s", "softmax", "precision"))
def layer(x, pn, pm, pf, s, softmax, precision, drop_at=None):
    a = norm(x, pn["1"], s)
    if softmax:
        x = x + softmax_attention(a, pm, s, precision)
    else:
        x = x + linear_attention(a, pm, s, precision, drop_at)
    return x + experts(norm(x, pn["2"], s), pf, s, precision)


def hidden(w, ids, cfg, precision="f32", drop_at=None):
    """The stack's last hidden state [T, d] for one sequence ``ids`` [T].
    One layer at a time (each kind of layer compiles once), that layer's
    leaves widened to float32 as it runs. ``drop_at`` : the position whose
    token no longer sees the linear layers' carried state, under
    ``precision`` ``"drop_state"``."""
    s = sizes(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    if drop_at is not None:
        drop_at = jnp.asarray(drop_at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for i in range(s.layers):
            soft = i in s.softmax
            x = layer(x, _widen(w, f"L{i}.n."),
                      _widen(w, f"L{i}.a." if soft else f"L{i}.d."),
                      _widen(w, f"L{i}.f."), s, soft, precision,
                      None if soft else drop_at)
    return x


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def head(h, norm_w, head_w, s, precision):
    h = norm(h, norm_w.astype(jnp.float32), s)
    return _mm("td,vd->tv", h, head_w.astype(jnp.float32), precision)


def logits(w, ids, cfg, precision="f32", rows=None, drop_at=None):
    """Logits [T, V] of one sequence (``rows``: only those positions)."""
    h = hidden(w, ids, cfg, precision, drop_at)
    if rows is not None:
        h = h[rows]
    return head(h, w["norm_f.w"], w["head"], sizes(cfg), precision)
