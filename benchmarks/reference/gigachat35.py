"""Plain GigaChat3.5 (``gigachat3_5``, Hugging Face
``ai-sage/GigaChat3.5-432B-A28B``): the forward pass of the language
model's stack, whole or as one chip's share of its routed experts and
vocabulary, and nothing else.

The yardstick's reference for the ``gigachat3_5`` family. Straightforward
``jax.numpy`` in float32 with matrix multiplications at ``highest``
precision; no cache, no chunk, no batching: the delta rule as its TOKEN
RECURRENCE (one ``lax.scan`` step a token, never the chunked form), latent
attention per head with a full softmax over every earlier token (no matrix
absorbed into another). It imports nothing of ``paddle_tpu`` and is handed
only the weights the benchmark made from the seed
(``harness/giga_weights.py``). The two multi-token-prediction layers
(``num_nextn_predict_layers``) are not here.

From the published ``config.json`` keys; lines marked *assumed* are
readings the key set does not settle, listed under ``assumed`` in the
configuration file, each the program's too. ``x`` is a token's residual
row; every norm is RMS over the last dimension, ``rms_norm_eps``::

    norm     N_w(x) = x / rms(x) * layernorm_gating_weight sigmoid(w)
             (*assumed*: ``ZeroCenteredGatedNorm`` read as a scale that is
             1 at w = 0)
    block    layernorm_type pre_post:
             x += N_post1(mixer(N_pre1(x)));  x += N_post2(ffn(N_pre2(x)))
    linear layer (every layer not in full_attention_layers; Gated
    DeltaNet, arXiv:2412.06464, as Qwen3-Next's public modelling code)
             [q | k | v | z] = a W_qkvz (key heads x dk | the same | value
             heads x dv | the same);  [b | g] = a W_ba (value heads each)
             [q | k | v] = silu(conv(.)) depthwise, causal, no bias,
             linear_conv_kernel_dim taps, the LAST on the token itself
             q = q / |q| / sqrt(dk), k = k / |k| per key head (x * rsqrt(
             sum x^2 + 1e-6)); key head j serves value heads j r .. j r +
             r - 1
             per value head h, S_h a dk x dv state from zero:
               beta = sigmoid(b_h);  decay = exp(-exp(A_log_h) softplus(g_h
               + dt_bias_h))
               S = decay S;  u = beta (v - S^T k);  S = S + k u^T
               o = S^T q
             y_h = (o / rms(o) * (1 + w_o)) * linear_sigmoid_gate_scale
             sigmoid(z_h), eps linear_attn_o_norm_eps (*assumed* from
             gated_rmsnorm_sigmoid_zero_centered);  out = concat(y) W_out
    full layer (DeepSeek-V3's latent attention)
             cq = N(a W_dq);  [q_nope | q_rope]_j = cq W_uq
             [ckv | k_rope] = a W_dkv;  ckv = N(ckv)
             [k_nope | v]_j = ckv W_ukv
             q_rope, k_rope rotated in INTERLEAVED pairs (rope_interleave)
             at YaRN's frequencies (rope_scaling)
             scores (q_nope . k_nope + q_rope . k_rope) * (dn + dr)^-1/2 *
             m^2, m = 0.1 mscale_all_dim ln(factor) + 1 (*assumed*:
             use_mla_scaling_factor read as DeepSeek's rule); causal
             softmax over ALL earlier tokens
             y = (att * sigmoid(a W_g)) W_o, W_g [hidden, heads x dv]
             (*assumed*: gated_attention is the elementwise gate of the
             gated-attention paper)
    experts (layers >= first_k_dense_replace)
             sc = sigmoid(b W_r), float32, all router outputs; chosen = the
             num_experts_per_tok largest of sc + bias (one group); weights
             sc of the chosen over their sum * routed_scaling_factor
             y = sum_e w_e E_e(b) + E_shared(b)
    mlp      E(b) = W_2 (silu(min(b W_g, L)) * clip(b W_u, -L, L)), L =
             swiglu_limit (*assumed*: it clamps the gate from above and
             the linear half both ways, in the dense MLP, every expert and
             the shared expert alike)
    head     N_f(x) head^T, untied

**The share.** ``held = (lo, hi)`` are the routed experts this chip holds
(``n_routed_experts`` of the configuration file, from ``experts_first``;
the router keeps ``router_outputs``). Routing is over all the router's
outputs with the weights above; only held experts' terms are added, and
that partial result goes on to the next layer. The vocabulary is the slice
the file gives. With ``held`` = all experts this is the published layer.

Weights are a flat dict of arrays named by layer (``leaf_shapes``):
``L<i>.n.*`` the four norms, ``L<i>.d.*`` a linear layer's mixer,
``L<i>.a.*`` a full layer's, ``L<i>.f.*`` the MLP or the router, HELD
experts and shared expert. Linear weights are ``[in, out]``; ``W_gate`` and
``W_up`` are one matrix ``w1`` (columns gate | up). The arrays may be held
in bfloat16: a layer's leaves are widened to float32 as that layer runs
(exact).

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first. Further values name a
WRONG model in float32, for the controls that the comparison must fail:
``"drop_state"`` (the linear layers' carried state, matrix and convolution
inputs, forgotten at position ``drop_at``: what a chunk boundary that does
not carry would do), ``"beta0"`` (``beta`` forced to 0: nothing is ever
written), ``"no_delta"`` (``u = beta v``: the read of the state with the
key left out, a gated linear attention), ``"no_clamp"`` (``swiglu_limit``
left out), ``"half_rope"`` (the half-form rotation at plain ``theta``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
WRONG = ("drop_state", "beta0", "no_delta", "no_clamp", "half_rope")
QUERY_BLOCK = 128       # queries whose [block, T] scores are held at a time
HEAD_GROUP = 16         # heads whose queries, keys and values exist at a time
_HI = jax.lax.Precision.HIGHEST


class Sizes(NamedTuple):
    d: int
    types: tuple            # "linear_attention" | "full_attention" a layer
    vocab: int
    first_dense: int
    f_dense: int
    f: int
    experts: int
    top_k: int
    held: tuple
    route_scale: float
    limit: float
    heads: int
    q_rank: int
    rank: int
    dn: int
    dr: int
    dv: int
    theta: float
    yarn: tuple             # (factor, beta_fast, beta_slow, original, all_dim)
    hk: int
    hv: int
    dk: int
    dlv: int
    taps: int
    gate_scale: float
    o_eps: float
    norm_scale: float
    eps: float

    @property
    def n_held(self):
        return self.held[1] - self.held[0]

    @property
    def conv_dim(self):
        return 2 * self.hk * self.dk + self.hv * self.dlv


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs, from the configuration's keys:
    the published ``config.json`` names; ``n_routed_experts`` is the count
    HELD (``n_routed_experts_published`` beside it) from ``experts_first``
    on, the router keeps ``router_outputs``; ``full_attention_layers``
    lists the full layers among the ``num_hidden_layers`` kept."""
    n = cfg["num_hidden_layers"]
    full = set(cfg["full_attention_layers"])
    if not full <= set(range(n)):
        raise ValueError(f"full_attention_layers {sorted(full)} of {n}")
    if cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1 \
            or not cfg["norm_topk_prob"]:
        raise ValueError("only the published router is written: one group, "
                         "normalised, one shared expert")
    if cfg["layernorm_type"] != "pre_post" or not cfg["rope_interleave"] \
            or cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError("only pre_post norms and interleaved YaRN rotary "
                         "positions are written")
    ys = cfg["rope_scaling"]
    lo = cfg.get("experts_first", 0)
    return Sizes(
        d=cfg["hidden_size"],
        types=tuple("full_attention" if i in full else "linear_attention"
                    for i in range(n)),
        vocab=cfg["vocab_size"], first_dense=cfg["first_k_dense_replace"],
        f_dense=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        held=(lo, lo + cfg["n_routed_experts"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        limit=float(cfg["swiglu_limit"]), heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], rank=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], theta=float(cfg["rope_theta"]),
        yarn=(float(ys["factor"]), float(ys["beta_fast"]),
              float(ys["beta_slow"]),
              int(ys["original_max_position_embeddings"]),
              float(ys["mscale_all_dim"])),
        hk=cfg["linear_num_key_heads"], hv=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dlv=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"],
        gate_scale=float(cfg["linear_sigmoid_gate_scale"]),
        o_eps=float(cfg["linear_attn_o_norm_eps"]),
        norm_scale=float(cfg["layernorm_gating_weight"]),
        eps=float(cfg["rms_norm_eps"]))


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    d = s.d
    out = {"embed": (s.vocab, d), "head": (s.vocab, d), "norm_f.w": (d,)}
    for i, kind in enumerate(s.types):
        out.update({f"L{i}.n.{k}": (d,)
                    for k in ("pre1", "post1", "pre2", "post2")})
        if kind == "linear_attention":
            leaves = {"qkvz": (d, s.conv_dim + s.hv * s.dlv),
                      "ba": (d, 2 * s.hv), "conv": (s.taps, s.conv_dim),
                      "A_log": (s.hv,), "dt_bias": (s.hv,),
                      "o_norm.w": (s.dlv,), "out": (s.hv * s.dlv, d)}
            out.update({f"L{i}.d.{k}": v for k, v in leaves.items()})
        else:
            leaves = {"dq": (d, s.q_rank), "q_norm.w": (s.q_rank,),
                      "uq": (s.q_rank, s.heads * (s.dn + s.dr)),
                      "dkv": (d, s.rank + s.dr), "kv_norm.w": (s.rank,),
                      "ukv": (s.rank, s.heads * (s.dn + s.dv)),
                      "gate": (d, s.heads * s.dv), "o": (s.heads * s.dv, d)}
            out.update({f"L{i}.a.{k}": v for k, v in leaves.items()})
        if i < s.first_dense:
            ffn = {"w1": (d, 2 * s.f_dense), "w2": (s.f_dense, d)}
        else:
            ffn = {"router": (d, s.experts), "bias": (s.experts,),
                   "w1": (s.n_held, d, 2 * s.f), "w2": (s.n_held, s.f, d),
                   "shared.w1": (d, 2 * s.f), "shared.w2": (s.f, d)}
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
                      precision=_HI, preferred_element_type=jnp.float32)


def norm(x, w, s: Sizes):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + s.eps) \
        * (s.norm_scale * jax.nn.sigmoid(w))


def silu(x):
    return x * jax.nn.sigmoid(x)


def yarn_inv_freq(s: Sizes):
    """YaRN's rotary frequencies of the ``dr / 2`` pairs."""
    factor, fast, slow, original, _ = s.yarn
    dim = s.dr

    def turns_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(s.theta))

    low = max(math.floor(turns_dim(fast)), 0)
    high = min(math.ceil(turns_dim(slow)), dim - 1)
    plain = 1.0 / s.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def softmax_scale(s: Sizes):
    factor, _, _, _, all_dim = s.yarn
    m = 1.0 if factor <= 1 else 0.1 * all_dim * math.log(factor) + 1.0
    return m * m / math.sqrt(s.dn + s.dr)


def rope(x, s: Sizes, precision):
    """Rotary over the last axis of ``x`` [T, ..., dr] at positions ``0 ..
    T - 1``: interleaved pairs at YaRN's frequencies (``half_rope``: the
    half form at plain ``theta``, the wrong model)."""
    t, hd = x.shape[0], x.shape[-1]
    if precision == "half_rope":
        inv = 1.0 / s.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    else:
        inv = yarn_inv_freq(s)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), hd // 2)
    c, sn = jnp.cos(ang), jnp.sin(ang)
    if precision == "half_rope":
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * c - b * sn, b * c + a * sn], axis=-1)
    pairs = x.reshape(*x.shape[:-1], hd // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * c - b * sn, b * c + a * sn], -1).reshape(x.shape)


def gated(b, w1, w2, s: Sizes, precision):
    """W_2 (silu(min(gate, L)) * clip(up, -L, L)) with [gate | up] = b w1."""
    u, v = jnp.split(_mm("td,dk->tk", b, w1, precision), 2, -1)
    if precision != "no_clamp":
        u, v = jnp.minimum(u, s.limit), jnp.clip(v, -s.limit, s.limit)
    return _mm("tk,kd->td", silu(u) * v, w2, precision)


def route(b, w_router, bias, s: Sizes, precision):
    """Weights [T, experts] f32, zero where an expert was not chosen."""
    sc = jax.nn.sigmoid(_mm("td,de->te", b, w_router, precision))
    _, idx = jax.lax.top_k(sc + bias, s.top_k)
    rows = jnp.arange(b.shape[0])[:, None]
    top = sc[rows, idx]
    g = top / top.sum(-1, keepdims=True) * s.route_scale
    return jnp.zeros_like(sc).at[rows, idx].set(g)


def experts(b, p, s: Sizes, precision):
    """The held experts' part of the routed layer, every token through each
    held expert, and the shared expert."""
    gates = route(b, p["router"], p["bias"], s, precision)

    def one(e, out):
        g = jax.lax.dynamic_index_in_dim(gates, s.held[0] + e, 1)
        return out + g * gated(b, p["w1"][e], p["w2"][e], s, precision)

    out = jax.lax.fori_loop(0, s.n_held, one, jnp.zeros_like(b))
    return out + gated(b, p["shared.w1"], p["shared.w2"], s, precision)


def delta_rule(q, k, v, beta, decay, reset=None, no_delta=False):
    """The token recurrence. q, k : [T, H, dk]; v : [T, H, dv]; beta, decay
    : [T, H]; reset : [T] bool or None, the state forgotten BEFORE that
    token. Returns o [T, H, dv]."""
    t, h, dk = k.shape
    if reset is None:
        reset = jnp.zeros(t, bool)

    def step(state, x):
        qt, kt, vt, bt, dt, rt = x
        state = jnp.where(rt, 0.0, state) * dt[:, None, None]
        read = 0.0 if no_delta else jnp.einsum("hkv,hk->hv", state, kt,
                                               precision=_HI)
        u = bt[:, None] * (vt - read)
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
                        (q, k, v, beta, decay, reset))
    return o


def linear_attention(a, p, s: Sizes, precision, drop_at=None):
    """A linear layer's mixer over a [T, d] (normed input)."""
    t = a.shape[0]
    pos = jnp.arange(t)
    drop = precision == "drop_state" and drop_at is not None
    qkvz = _mm("td,dk->tk", a, p["qkvz"], precision)
    ba = _mm("td,dk->tk", a, p["ba"], precision)
    x = qkvz[:, :s.conv_dim]
    xp = jnp.pad(x, ((s.taps - 1, 0), (0, 0)))
    conv = 0.0
    for j in range(s.taps):
        src = pos - (s.taps - 1 - j)                 # the input's position
        tap = xp[j:j + t]
        if drop:         # an input from before the drop is forgotten
            tap = jnp.where(((pos >= drop_at) & (src < drop_at))[:, None],
                            0.0, tap)
        conv = conv + p["conv"][j] * tap
    x = silu(conv)
    kw = s.hk * s.dk
    q, k, v = x[:, :kw], x[:, kw:2 * kw], x[:, 2 * kw:]

    def unit(u):
        u = u.reshape(t, s.hk, s.dk)
        u = u * jax.lax.rsqrt((u * u).sum(-1, keepdims=True) + 1e-6)
        return jnp.repeat(u, s.hv // s.hk, axis=1)

    beta = jax.nn.sigmoid(ba[:, :s.hv])
    if precision == "beta0":
        beta = jnp.zeros_like(beta)
    decay = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[:, s.hv:] + p["dt_bias"]))
    o = delta_rule(unit(q) * s.dk ** -0.5, unit(k),
                   v.reshape(t, s.hv, s.dlv), beta, decay,
                   (pos == drop_at) if drop else None,
                   no_delta=precision == "no_delta")
    z = qkvz[:, s.conv_dim:].reshape(t, s.hv, s.dlv)
    y = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + s.o_eps) \
        * (1.0 + p["o_norm.w"]) * (s.gate_scale * jax.nn.sigmoid(z))
    return _mm("tk,kd->td", y.reshape(t, -1), p["out"], precision)


def _block_of(t):
    b = min(QUERY_BLOCK, t)
    while t % b:
        b -= 1
    return b


def full_attention(a, p, s: Sizes, precision):
    """A full layer's mixer over a [T, d] (normed input): per head, a group
    of heads at a time and a block of queries at a time inside it (at
    3,800 tokens all heads' scores at once are 3.7 GB); the groups' parts
    of the out-projection add up."""
    t = a.shape[0]
    block = _block_of(t)
    hg = min(HEAD_GROUP, s.heads)
    n = s.heads // hg
    cq = norm(_mm("td,dk->tk", a, p["dq"], precision), p["q_norm.w"], s)
    kv = _mm("td,dk->tk", a, p["dkv"], precision)
    ckv = norm(kv[:, :s.rank], p["kv_norm.w"], s)
    k_rope = rope(kv[:, s.rank:], s, precision)
    scale = softmax_scale(s)
    pos = jnp.arange(t)
    w_uq = jnp.moveaxis(p["uq"].reshape(s.q_rank, n, hg, s.dn + s.dr), 1, 0)
    w_ukv = jnp.moveaxis(p["ukv"].reshape(s.rank, n, hg, s.dn + s.dv), 1, 0)
    w_o = p["o"].reshape(n, hg, s.dv, -1)
    gate = jax.nn.sigmoid(_mm("td,dk->tk", a, p["gate"], precision))
    gate = jnp.moveaxis(gate.reshape(t, n, hg, s.dv), 1, 0)

    def group(out, args):
        wq, wkv, wo, g = args
        q = _mm("tc,chd->thd", cq, wq, precision)         # [T, hg, dn + dr]
        qn, qr = q[..., :s.dn], rope(q[..., s.dn:], s, precision)
        kvh = _mm("tc,chd->thd", ckv, wkv, precision)     # [T, hg, dn + dv]

        def rows(q0):
            qn_b = jax.lax.dynamic_slice_in_dim(qn, q0, block, 0)
            qr_b = jax.lax.dynamic_slice_in_dim(qr, q0, block, 0)
            sc = (_mm("qhd,shd->hqs", qn_b, kvh[..., :s.dn], precision)
                  + _mm("qhr,sr->hqs", qr_b, k_rope, precision)) * scale
            see = pos[None, :] <= (q0 + jnp.arange(block))[:, None]
            pr = jax.nn.softmax(jnp.where(see[None], sc, -1e30), axis=-1)
            return _mm("hqs,shv->qhv", pr, kvh[..., s.dn:], precision)

        o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, hg, s.dv)
        return out + _mm("thv,hvd->td", o * g, wo, precision), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(a), (w_uq, w_ukv, w_o, gate))
    return out


def _widen(w, prefix):
    """One layer's leaves in float32, without the prefix; the held experts'
    stacks stay as they are stored and are widened one expert at a time,
    by the product."""
    return {name[len(prefix):]: x if x.ndim == 3 else x.astype(jnp.float32)
            for name, x in w.items() if name.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("s", "kind", "dense",
                                             "precision"))
def layer(x, pn, pm, pf, s, kind, dense, precision, drop_at=None):
    a = norm(x, pn["pre1"], s)
    if kind == "full_attention":
        y = full_attention(a, pm, s, precision)
    else:
        y = linear_attention(a, pm, s, precision, drop_at)
    x = x + norm(y, pn["post1"], s)
    b = norm(x, pn["pre2"], s)
    y = gated(b, pf["w1"], pf["w2"], s, precision) if dense \
        else experts(b, pf, s, precision)
    return x + norm(y, pn["post2"], s)


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
        for i, kind in enumerate(s.types):
            mixer = ".a." if kind == "full_attention" else ".d."
            x = layer(x, _widen(w, f"L{i}.n."), _widen(w, f"L{i}{mixer}"),
                      _widen(w, f"L{i}.f."), s, kind, i < s.first_dense,
                      precision, drop_at)
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
