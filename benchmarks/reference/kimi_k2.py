"""Plain Kimi K2 (``kimi_k2``, Hugging Face ``moonshotai/Kimi-K2.7-Code``):
the forward pass of the language model's stack, whole or as one chip's
share of its routed experts and vocabulary, and nothing else.

The yardstick's reference for the ``kimi_k2`` family. Straightforward
``jax.numpy`` in float32 with matrix multiplications at ``highest``
precision; no cache, no chunk, no batching: latent attention per head with
a full softmax over every earlier token (no matrix absorbed into another),
a loop over the held experts. It imports nothing of ``paddle_tpu`` and is
handed only the weights the benchmark made from the seed
(``harness/kimi_weights.py``). What is not the model's own (rounded
products, the interleaved YaRN rotation, the softmax scale) is
``reference/gigachat35.py``'s, whose full layers have this attention.

From the published ``config.json`` keys (DeepSeek-V3's block); lines marked
*assumed* are readings the key set does not settle, listed under
``assumed`` in the configuration file, each the program's too. ``x`` is a
token's residual row; ``N`` is an RMSNorm with a scale, ``x / rms(x) * w``,
``rms_norm_eps``::

    a = N1(x)
    cq = Nq(a W_dq);  [q_nope | q_rope]_j = cq W_uq
    [ckv | k_rope] = a W_dkv;  ckv = Nkv(ckv);  [k_nope | v]_j = ckv W_ukv
    q_rope, k_rope rotated in INTERLEAVED pairs (*assumed*: DeepSeek-V3's
        public modelling code) at YaRN's frequencies (rope_scaling); cos
        and sin unscaled (mscale / mscale_all_dim = 1)
    scores (q_nope . k_nope + q_rope . k_rope) * (dn + dr)^-1/2 * m^2,
        m = 0.1 mscale_all_dim ln(factor) + 1 (*assumed*: DeepSeek's rule);
        causal softmax over ALL earlier tokens
    x += concat_j(att_j) W_o
    b = N2(x)
    layers < first_k_dense_replace:  x += W_2 (silu(b W_g) * b W_u)
    the others:  sc = sigmoid(b W_r), float32, all router outputs; chosen =
        the num_experts_per_tok largest of sc + bias (*assumed*: n_group 1
        is one group); weights sc of the chosen over their sum *
        routed_scaling_factor;  x += sum_e w_e E_e(b) + E_shared(b)
    logits = Nf(x) head^T, untied

**The share.** ``held = (lo, hi)`` are the routed experts this chip holds
(``n_routed_experts`` of the configuration file, from ``experts_first``;
the router keeps ``router_outputs``). Routing is over all the router's
outputs with the weights above; only held experts' terms are added, and
that partial result goes on to the next layer. The vocabulary is the slice
the file gives. With ``held`` = all experts this is the published layer.

Weights are a flat dict of arrays named by layer (``leaf_shapes``):
``L<i>.n.{1,2}`` the two norms, ``L<i>.a.*`` the attention, ``L<i>.f.*``
the MLP or the router, HELD experts and shared expert. Linear weights are
``[in, out]``; ``W_gate`` and ``W_up`` are one matrix ``w1`` (columns gate
| up). The arrays may be held in bfloat16: a layer's leaves are widened to
float32 as that layer runs (exact).

``precision`` states the arithmetic of every matrix multiplication, as in
``reference/gpt2.py``: ``"f32"`` is the reference itself; ``"bf16"`` and
``"fp8"`` round both operands to that type first. Further values name a
WRONG model in float32, for the controls that the comparison must fail:
``"no_context"`` (a query at or past position ``hide`` sees no key before
it: a sequence that attends its own tail alone, what a prefix attached
wrongly would serve), ``"softmax_router"`` (a softmax over the chosen
logits in place of the sigmoids over their sum), ``"no_yarn_scale"``
(``m`` = 1), ``"half_rope"`` (the half-form rotation at plain ``theta``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from reference import gigachat35 as _g

WRONG = ("no_context", "softmax_router", "no_yarn_scale", "half_rope")
QUERY_BLOCK = 128       # queries whose [block, T] scores are held at a time
HEAD_GROUP = 16         # heads whose queries, keys and values exist at a time
WIDE_ELEMENTS = 1 << 27  # float32 values of [gate | up] held at a time
silu = _g.silu


class Sizes(NamedTuple):
    d: int
    layers: int
    vocab: int
    first_dense: int
    f_dense: int
    f: int
    experts: int
    top_k: int
    held: tuple
    route_scale: float
    heads: int
    q_rank: int
    rank: int
    dn: int
    dr: int
    dv: int
    theta: float
    yarn: tuple             # (factor, beta_fast, beta_slow, original, all_dim)
    eps: float

    @property
    def n_held(self):
        return self.held[1] - self.held[0]


def sizes(cfg: dict) -> Sizes:
    """Every size the forward pass needs, from the configuration's keys:
    the published ``config.json`` names; ``n_routed_experts`` is the count
    HELD (``n_routed_experts_published`` beside it) from ``experts_first``
    on, the router keeps ``router_outputs``."""
    if cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1 \
            or not cfg["norm_topk_prob"] \
            or cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only the published router is written: sigmoid "
                         "scores, one group, normalised, one shared expert")
    if cfg["rope_scaling"]["type"] != "yarn" \
            or cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("only YaRN rotary positions and experts in every "
                         "layer after the dense ones are written")
    ys = cfg["rope_scaling"]
    lo = cfg.get("experts_first", 0)
    return Sizes(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], first_dense=cfg["first_k_dense_replace"],
        f_dense=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        experts=cfg.get("router_outputs", cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        held=(lo, lo + cfg["n_routed_experts"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        rank=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        theta=float(cfg["rope_theta"]),
        yarn=(float(ys["factor"]), float(ys["beta_fast"]),
              float(ys["beta_slow"]),
              int(ys["original_max_position_embeddings"]),
              float(ys["mscale_all_dim"])),
        eps=float(cfg["rms_norm_eps"]))


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape of every weight leaf, in a fixed order."""
    s = sizes(cfg)
    d = s.d
    out = {"embed": (s.vocab, d), "head": (s.vocab, d), "norm_f.w": (d,)}
    for i in range(s.layers):
        out.update({f"L{i}.n.1": (d,), f"L{i}.n.2": (d,)})
        out.update({f"L{i}.a.{k}": v for k, v in {
            "dq": (d, s.q_rank), "q_norm.w": (s.q_rank,),
            "uq": (s.q_rank, s.heads * (s.dn + s.dr)),
            "dkv": (d, s.rank + s.dr), "kv_norm.w": (s.rank,),
            "ukv": (s.rank, s.heads * (s.dn + s.dv)),
            "o": (s.heads * s.dv, d)}.items()})
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


def _mm(eq, a, b, precision):
    """A WRONG model's products run in float32."""
    return _g._mm(eq, a, b, "f32" if precision in WRONG else precision)


def norm(x, w, s: Sizes):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + s.eps) * w


def gated(b, w1, w2, precision):
    """W_2 (silu(gate) * up) with [gate | up] = b w1; a block of rows at a
    time where all rows' [gate | up] at once would pass `WIDE_ELEMENTS`
    (26,000 tokens through the dense MLP: 3.9 GB)."""
    def rows(x):
        u, v = jnp.split(_mm("td,dk->tk", x, w1, precision), 2, -1)
        return _mm("tk,kd->td", silu(u) * v, w2, precision)

    t = b.shape[0]
    if t * w1.shape[1] <= WIDE_ELEMENTS:
        return rows(b)
    n = -(-t * w1.shape[1] // WIDE_ELEMENTS)
    while t % n:
        n += 1
    return jax.lax.map(rows, b.reshape(n, t // n, -1)).reshape(t, -1)


def route(b, w_router, bias, s: Sizes, precision):
    """Weights [T, experts] f32, zero where an expert was not chosen."""
    logits = _mm("td,de->te", b, w_router, precision)
    sc = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(sc + bias, s.top_k)
    rows = jnp.arange(b.shape[0])[:, None]
    top = sc[rows, idx]
    g = top / top.sum(-1, keepdims=True)
    if precision == "softmax_router":
        g = jax.nn.softmax(logits[rows, idx], axis=-1)
    return jnp.zeros_like(sc).at[rows, idx].set(g * s.route_scale)


def experts(b, p, s: Sizes, precision):
    """The held experts' part of the routed layer, every token through each
    held expert, and the shared expert."""
    gates = route(b, p["router"], p["bias"], s, precision)

    def one(e, out):
        g = jax.lax.dynamic_index_in_dim(gates, s.held[0] + e, 1)
        return out + g * gated(b, p["w1"][e], p["w2"][e], precision)

    out = jax.lax.fori_loop(0, s.n_held, one, jnp.zeros_like(b))
    return out + gated(b, p["shared.w1"], p["shared.w2"], precision)


def _block_of(t):
    b = min(QUERY_BLOCK, t)
    while t % b:
        b -= 1
    return b


def attention(a, p, s: Sizes, precision, hide=None):
    """The layer's mixer over a [T, d] (normed input): per head, a group of
    heads at a time and a block of queries at a time inside it (at 26,000
    tokens 16 heads' scores of 128 queries are 0.2 GB); the groups' parts
    of the out-projection add up. ``hide``: under ``"no_context"`` the
    position from which a query no longer sees the keys before it."""
    t = a.shape[0]
    block = _block_of(t)
    hg = min(HEAD_GROUP, s.heads)
    n = s.heads // hg
    cq = norm(_mm("td,dk->tk", a, p["dq"], precision), p["q_norm.w"], s)
    kv = _mm("td,dk->tk", a, p["dkv"], precision)
    ckv = norm(kv[:, :s.rank], p["kv_norm.w"], s)
    k_rope = _g.rope(kv[:, s.rank:], s, precision)
    scale = (s.dn + s.dr) ** -0.5 if precision == "no_yarn_scale" \
        else _g.softmax_scale(s)
    pos = jnp.arange(t)
    w_uq = jnp.moveaxis(p["uq"].reshape(s.q_rank, n, hg, s.dn + s.dr), 1, 0)
    w_ukv = jnp.moveaxis(p["ukv"].reshape(s.rank, n, hg, s.dn + s.dv), 1, 0)
    w_o = p["o"].reshape(n, hg, s.dv, -1)

    def group(out, args):
        wq, wkv, wo = args
        q = _mm("tc,chd->thd", cq, wq, precision)         # [T, hg, dn + dr]
        qn, qr = q[..., :s.dn], _g.rope(q[..., s.dn:], s, precision)
        kvh = _mm("tc,chd->thd", ckv, wkv, precision)     # [T, hg, dn + dv]

        def rows(q0):
            qn_b = jax.lax.dynamic_slice_in_dim(qn, q0, block, 0)
            qr_b = jax.lax.dynamic_slice_in_dim(qr, q0, block, 0)
            sc = (_mm("qhd,shd->hqs", qn_b, kvh[..., :s.dn], precision)
                  + _mm("qhr,sr->hqs", qr_b, k_rope, precision)) * scale
            qp = (q0 + jnp.arange(block))[:, None]
            see = pos[None, :] <= qp
            if precision == "no_context" and hide is not None:
                see &= (qp < hide) | (pos[None, :] >= hide)
            pr = jax.nn.softmax(jnp.where(see[None], sc, -1e30), axis=-1)
            return _mm("hqs,shv->qhv", pr, kvh[..., s.dn:], precision)

        o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, hg, s.dv)
        return out + _mm("thv,hvd->td", o, wo, precision), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(a), (w_uq, w_ukv, w_o))
    return out


def _widen(w, prefix):
    """One layer's leaves in float32, without the prefix; the held experts'
    stacks stay as they are stored and are widened one expert at a time,
    by the product."""
    return {name[len(prefix):]: x if x.ndim == 3 else x.astype(jnp.float32)
            for name, x in w.items() if name.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("s", "dense", "precision"))
def layer(x, pn, pa, pf, s, dense, precision, hide=None):
    x = x + attention(norm(x, pn["1"], s), pa, s, precision, hide)
    b = norm(x, pn["2"], s)
    return x + (gated(b, pf["w1"], pf["w2"], precision) if dense
                else experts(b, pf, s, precision))


def hidden(w, ids, cfg, precision="f32", hide=None):
    """The stack's last hidden state [T, d] for one sequence ``ids`` [T].
    One layer at a time (each kind of layer compiles once), that layer's
    leaves widened to float32 as it runs."""
    s = sizes(cfg)
    x = w["embed"][ids].astype(jnp.float32)
    if hide is not None:
        hide = jnp.asarray(hide, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for i in range(s.layers):
            x = layer(x, _widen(w, f"L{i}.n."), _widen(w, f"L{i}.a."),
                      _widen(w, f"L{i}.f."), s, i < s.first_dense, precision,
                      hide)
    return x


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def head(h, norm_w, head_w, s, precision):
    h = norm(h, norm_w.astype(jnp.float32), s)
    return _mm("td,vd->tv", h, head_w.astype(jnp.float32), precision)


def logits(w, ids, cfg, precision="f32", rows=None, hide=None):
    """Logits [T, V] of one sequence (``rows``: only those positions)."""
    h = hidden(w, ids, cfg, precision, hide)
    if rows is not None:
        h = h[rows]
    return head(h, w["norm_f.w"], w["head"], sizes(cfg), precision)
