"""Solar Open 2 (``solar_open2``) for the serving engine: a delta rule whose
decay is per key CHANNEL (Kimi Delta Attention, arXiv:2510.26692) in three
layers of four, gated grouped-query softmax attention WITHOUT positions
over a paged K/V pool in the fourth, and sigmoid-routed experts beside a
shared one in EVERY layer, as ONE CHIP'S SHARE of an expert-parallel
deployment.

Layer ``i`` (a softmax layer where ``i`` is in ``gqa_layers``, else a
linear one; ``N`` an RMSNorm with a scale ``w``)::

    a = N1(x);  x += mixer_i(a);  x += experts_i(N2(x))
    logits = Nf(x) head^T                        (untied head)

    linear   [q | k | v] = a W_qkv (heads x dk each);  each through its own
             depthwise causal convolution of 4 taps, no bias, over the last
             3 inputs carried per sequence, then silu;
             q = q / |q| / sqrt(dk), k = k / |k| per head
             log decay PER KEY CHANNEL g = -exp(A_log_h) softplus((a W_fa)
             W_fb + dt_bias) [heads, dk]; write strength beta = 2 sigmoid(a
             W_b) a head, in (0, 2); per head the delta rule
             (kernels/deltanet.py) S <- Diag(exp(g)) S; u = beta (v - S^T
             k); S <- S + k u^T; o = S^T q
             y = (o / rms(o) * w_o) * sigmoid((a W_ga) W_gb + b_g) a head;
             W_out
    softmax  [q | k | v] = a W_qkv (64 | 8 | 8 heads of 128); NO rotation
             and no other position signal; K and V rows written to the
             pool's pages; scores * 128^-1/2, causal softmax over ALL
             earlier tokens, query head j reads key-value head j // 8
             (`pa.paged_attention` a decode step, `pa.prefill_attention` a
             chunk); y = (att * sigmoid(a W_g)) W_o, the gate elementwise
    experts  sc = sigmoid(b W_r) float32 over ALL ``n_routed_experts``; the
             ``experts_per_token`` largest of sc + bias; weights sc / sum of
             the chosen * routed_scaling_factor; this chip adds the terms
             of the experts ``experts_held = (lo, hi)`` it holds
             (kernels/moe.py) and the shared expert's, ungated

The equations, and what the published config leaves open, are the
benchmark's plain reference's (``benchmarks/reference/solar_open2.py``),
which the CPU tests hold this file to. Like the other families this file
is PURE step functions over one flat dict of arrays, named by layer:
``embed``, ``head``, ``norm_f.w``, ``L<i>.n.{1,2}`` (the layer's two norms),
``L<i>.d.*`` (a linear layer's mixer) or ``L<i>.a.*`` (a softmax layer's),
``L<i>.f.*`` (its router, HELD experts and shared expert). The stack is
unrolled, every layer's leaves arrays of their own (`models/dots3note.py`
says why).

Per-sequence state (docs/SERVING.md "Three kinds of state"), all of it in
ONE `DeviceCache`:

- paged, growing: ``k_pages`` / ``v_pages`` ``[n_softmax, P, page, 8 *
  128]``, twin K and V pools of the softmax layers (no ``page_rows``);
- recurrent: ``delta`` ``[n_linear, slots, heads, dk, dv]`` float32, the
  delta rule's matrix state, a stack rewritten in place by layer, and
  ``conv.<i>`` ``[slots, 3 * conv_dim]`` float32, the convolutions' last
  three inputs, ONE ARRAY A LINEAR LAYER (kernels/deltanet.py says why it
  is no stack); zero at a sequence's first chunk, carried across chunks
  and into decode.

The first family here with K/V PAGES beside a delta-rule state.

Counts: each step adds to an int32 vector (inference/family.py
``step_counts``): one entry a held expert, all routing assignments, then
the (query, key) pairs the softmax layers attended in decode steps and in
chunks, the live tokens through a linear layer in decode steps and in
chunks, and the held experts a decode step's and a chunk's live tokens HIT.
`count_step` turns what reaches the host into ``engine.moe.*``,
``engine.gqa.pairs.*`` and ``engine.kda.tokens.*`` counters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import deltanet, moe
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models.gigachat35 import _add, _silu, _sub
from paddle_tpu.models.granitemoehybrid import (_rms, count_routing,
                                                expert_totals)  # noqa: F401
from paddle_tpu.observability import metrics

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM", "decode_step",
           "prefill_step", "prefill_chunk_step", "leaf_shapes",
           "init_params", "state_arrays", "tiny_config", "count_step",
           "family", "expert_totals"]


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_layers: int = 48
    gqa_layers: tuple = tuple(range(0, 48, 4))  # the softmax layers
    moe_intermediate_size: int = 1280         # one expert's width
    n_routed_experts: int = 320               # the router's outputs
    experts_per_token: int = 8
    experts_held: tuple = (0, 320)            # [lo, hi) on this chip
    routed_scaling_factor: float = 1.0
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    linear_heads: int = 64
    linear_head_dim: int = 128                # dk = dv
    linear_conv_kernel: int = 4               # short_conv_kernel_size
    linear_gate_rank: int = 128               # of W_fa W_fb and W_ga W_gb
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576

    def __post_init__(self):
        object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if not set(self.gqa_layers) <= set(range(self.num_layers)):
            raise ValueError(f"gqa_layers {self.gqa_layers} of "
                             f"{self.num_layers} layers")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads is no multiple of num_kv_heads")

    @property
    def softmax_layers(self):
        return tuple(i for i in range(self.num_layers)
                     if i in self.gqa_layers)

    @property
    def linear_layers(self):
        return tuple(i for i in range(self.num_layers)
                     if i not in self.gqa_layers)

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def kv_width(self):                       # a K (or V) page row
        return self.num_kv_heads * self.head_dim

    @property
    def linear_width(self):                   # all heads' q (or k, or v)
        return self.linear_heads * self.linear_head_dim

    @property
    def conv_dim(self):                       # [q | k | v]
        return 3 * self.linear_width


def tiny_config(**over):
    """The CPU tests' preset: one period (softmax, linear, linear, linear:
    the order the benchmark's cut keeps), every width ratio kept: 8 query
    heads over 2 key-value heads, 4 linear heads of 8, 32 experts of which
    4 held, 2 a token."""
    kw = dict(vocab_size=96, hidden_size=64, num_layers=4, gqa_layers=(0,),
              moe_intermediate_size=16, n_routed_experts=32,
              experts_per_token=2, experts_held=(0, 4), num_heads=8,
              num_kv_heads=2, head_dim=8, linear_heads=4, linear_head_dim=8,
              linear_gate_rank=8, max_position_embeddings=4096)
    kw.update(over)
    return SolarOpen2Config(**kw)


def leaf_shapes(cfg: SolarOpen2Config) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    h, w, r = cfg.linear_heads, cfg.linear_width, cfg.linear_gate_rank
    qw = cfg.num_heads * cfg.head_dim
    out = {"embed": (cfg.vocab_size, d), "head": (cfg.vocab_size, d),
           "norm_f.w": (d,)}
    for i in range(cfg.num_layers):
        out.update({f"L{i}.n.1": (d,), f"L{i}.n.2": (d,)})
        if i in cfg.gqa_layers:
            out.update({f"L{i}.a.{k}": v for k, v in {
                "qkv": (d, qw + 2 * cfg.kv_width), "gate": (d, qw),
                "o": (qw, d)}.items()})
        else:
            out.update({f"L{i}.d.{k}": v for k, v in {
                "qkv": (d, cfg.conv_dim),
                "conv": (cfg.linear_conv_kernel, cfg.conv_dim),
                "fa": (d, r), "fb": (r, w), "A_log": (h,), "dt_bias": (w,),
                "b": (d, h), "o_norm.w": (cfg.linear_head_dim,),
                "ga": (d, r), "gb": (r, w), "gb.bias": (w,),
                "out": (w, d)}.items()})
        out.update({f"L{i}.f.{k}": v for k, v in {
            "router": (d, cfg.n_routed_experts),
            "bias": (cfg.n_routed_experts,),
            "w1": (cfg.n_held, d, 2 * f), "w2": (cfg.n_held, f, d),
            "shared.w1": (d, 2 * f), "shared.w2": (f, d)}.items()})
    return out


def init_params(cfg: SolarOpen2Config, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std) (at the
    tiny preset's widths a larger ``std`` makes the mechanisms bite), the
    norms' scales 1 + N(0, std), the router's bias N(0, 0.005), ``gb.bias``
    N(0, std), ``A_log`` from ``A ~ U(0, 16)`` and ``dt_bias`` from ``dt``
    log-uniform in [1e-3, 0.1] (the published initial ranges of this
    layer's family, as `models/gigachat35.py` draws them)."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(".f.bias"):
            w = 0.005 * jax.random.normal(k, shape)
        elif name.endswith(".A_log"):
            w = jnp.log(jax.random.uniform(k, shape, minval=1e-3,
                                           maxval=16.0))
        elif name.endswith(".dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(1e-3), maxval=math.log(0.1)))
            w = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
        elif name.endswith((".n.1", ".n.2", "norm.w", "norm_f.w")):
            w = 1.0 + std * jax.random.normal(k, shape)
        else:
            w = std * jax.random.normal(k, shape)
        out[name] = w.astype(dtype)
    return out


def state_arrays(cfg: SolarOpen2Config, slots: int, page_size: int, dtype):
    """The per-slot state beside the page pool, as ``(name, kind, shape,
    dtype)`` in the order the step functions take and return it: the delta
    rule's matrix state, a stack over the linear layers, then the
    convolutions' last inputs, an array a linear layer; all float32."""
    del page_size, dtype
    taps = (cfg.linear_conv_kernel - 1) * cfg.conv_dim
    return (("delta", "recurrent", deltanet.state_shape(
                len(cfg.linear_layers), slots, cfg.linear_heads,
                cfg.linear_head_dim, cfg.linear_head_dim), jnp.float32),
            *((f"conv.{i}", "recurrent", (slots, taps), jnp.float32)
              for i in cfg.linear_layers))


# the vector a step adds to: one entry a held expert and all routing
# assignments (`kernels/moe.py`'s), then these
(_PAIRS_DECODE, _PAIRS_PREFILL, _KDA_DECODE, _KDA_PREFILL, _HIT_DECODE,
 _HIT_PREFILL) = range(6)
_COUNTERS = ("engine.gqa.pairs.decode", "engine.gqa.pairs.prefill",
             "engine.kda.tokens.decode", "engine.kda.tokens.prefill",
             "engine.moe.experts_hit.decode", "engine.moe.experts_hit.prefill")


def step_counts(cfg: SolarOpen2Config) -> int:
    """Entries of the vector a step adds to."""
    return cfg.n_held + 1 + len(_COUNTERS)


def count_step(cfg: SolarOpen2Config, grown: np.ndarray):
    """What the counts vector grew by between two readbacks: the routing
    counts as the other families with held experts keep them
    (`count_routing`), and the `_COUNTERS`: (query, key) pairs the softmax
    layers attended, live tokens through a linear layer (summed over the
    layers; dead slots and padding not counted) and held experts hit
    (summed over the layers), each by the decode steps and by the
    chunks."""
    n = cfg.n_held + 1
    count_routing(cfg, grown[:n])
    for name, add in zip(_COUNTERS, grown[n:]):
        metrics.counter(name).inc(int(add))


# ------------------------------------------------------------------ layers

def _norm(x, w, cfg):
    return _rms(x, w, cfg.rms_norm_eps)


def _gated_mlp(b, w1, w2):
    u, v = jnp.split(b @ w1, 2, axis=-1)
    return (_silu(u) * v) @ w2


def _ffn(b, p, valid, counts, cfg, hit_at):
    """The layer's second half on its normed input: this chip's routed
    experts and the shared one. The held experts that got a row of a
    ``valid`` token are added to the counts' entry ``hit_at``."""
    n = cfg.n_held + 1
    with jax.named_scope("moe_experts"):
        routed, tally = moe.routed_experts(
            b, p["router"], p["w1"], p["w2"], top_k=cfg.experts_per_token,
            held=cfg.experts_held, counts=counts[:n], valid=valid,
            scoring="sigmoid", bias=p["bias"],
            scale=cfg.routed_scaling_factor)
    shared = _gated_mlp(b, p["shared.w1"], p["shared.w2"])
    hit = jnp.sum(tally[:n - 1] > counts[:n - 1], dtype=counts.dtype)
    return routed + shared, jnp.concatenate(
        [tally, counts[n:].at[hit_at].add(hit)])


def _f32_dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _low_rank(a, down, up):
    """``(a W_down) W_up`` in float32, the bottleneck's values kept float32
    INTO the second product (rank 128: a thousandth of the layer's FLOPs
    at ``highest``): a token's log decay is summed over thousands of
    tokens, and rounding the bottleneck to bfloat16 is an error that does
    not average out."""
    return jnp.dot(_f32_dot(a, down), up.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _kda_inputs(a, p, cfg):
    """(conv input [N, conv_dim], output gate [N, H, dv] f32, beta [N, H]
    f32 in (0, 2), log decay [N, H, dk] f32) of a linear layer's
    projections."""
    n = a.shape[0]
    h, dk = cfg.linear_heads, cfg.linear_head_dim
    f32 = jnp.float32
    f = _low_rank(a, p["fa"], p["fb"]) + p["dt_bias"].astype(f32)
    log_g = -jnp.exp(p["A_log"].astype(f32))[:, None] \
        * jax.nn.softplus(f).reshape(n, h, dk)
    gate = jax.nn.sigmoid(_low_rank(a, p["ga"], p["gb"])
                          + p["gb.bias"].astype(f32)).reshape(n, h, dk)
    beta = 2.0 * jax.nn.sigmoid(_f32_dot(a, p["b"]))
    return a @ p["qkv"], gate, beta, log_g


def _kda_operands(xc, cfg):
    """The convolutions' output (f32, before the activation) -> (q, k [N,
    H, dk] f32 normalised; v [N, H, dv] f32)."""
    n = xc.shape[0]
    h, dk = cfg.linear_heads, cfg.linear_head_dim
    q, k, v = (u.reshape(n, h, dk) for u in jnp.split(_silu(xc), 3, axis=-1))

    def unit(u):
        return u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True)
                                 + 1e-6)

    return unit(q) * dk ** -0.5, unit(k), v


def _kda_out(o, gate, p, cfg, dtype):
    """The heads' norm and gate, then the out-projection."""
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y * p["o_norm.w"].astype(jnp.float32) * gate
    return y.reshape(y.shape[0], -1).astype(dtype) @ p["out"]


def _gqa_qkv(a, p, cfg):
    """(q [N, H, hd], k, v [N, Hkv, hd], gate [N, H, hd] f32) of a softmax
    layer's projections; no position enters."""
    n = a.shape[0]
    qw = cfg.num_heads * cfg.head_dim
    q, k, v = jnp.split(a @ p["qkv"], [qw, qw + cfg.kv_width], axis=-1)
    gate = jax.nn.sigmoid(_f32_dot(a, p["gate"]))
    return (q.reshape(n, cfg.num_heads, cfg.head_dim),
            k.reshape(n, cfg.num_kv_heads, cfg.head_dim),
            v.reshape(n, cfg.num_kv_heads, cfg.head_dim),
            gate.reshape(n, cfg.num_heads, cfg.head_dim))


def _gqa_out(att, gate, p, dtype):
    y = (att.astype(jnp.float32) * gate).astype(dtype)
    return y.reshape(y.shape[0], -1) @ p["o"]


def _logits(params, h, cfg):
    h = _norm(h, params["norm_f.w"], cfg)
    return jnp.dot(h, params["head"].T, preferred_element_type=jnp.float32)


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token.

    ids : [B] int32; cache : ``k_pages`` / ``v_pages`` [n_softmax, P, page,
    kv_width], ``page_table`` [B, pages], ``lengths`` [B], ``state`` =
    (delta, a conv array a linear layer), ``counts`` (optional); slot_mask :
    [B] bool: an inactive slot writes to the trash page, leaves its state
    alone and is not counted. Returns (logits [B, V] f32, new cache)."""
    table, pos = cache["page_table"], cache["lengths"]
    kc, vc = cache["k_pages"], cache["v_pages"]
    delta, *conv = cache["state"]
    counts = cache.get("counts")
    if counts is None:
        counts = jnp.zeros(step_counts(cfg), jnp.int32)
    live = jnp.sum(slot_mask, dtype=jnp.int32)
    pairs = jnp.sum(jnp.where(slot_mask, pos + 1, 0))
    h = params["embed"][ids]
    n_soft = n_lin = 0
    for i in range(cfg.num_layers):
        a = _norm(h, params[f"L{i}.n.1"], cfg)
        if i in cfg.gqa_layers:
            k, n_soft = n_soft, n_soft + 1
            p = _sub(params, f"L{i}.a.")
            q, kk, vv, gate = _gqa_qkv(a, p, cfg)
            kc, vc = pa.write_token_kv(kc, vc, kk, vv, table, pos, slot_mask,
                                       k)
            with jax.named_scope("gqa_decode"):
                att = pa.paged_attention(q, kc, vc, table, pos, layer=k)
            h = h + _gqa_out(att, gate, p, h.dtype)
            counts = _add(counts, cfg, _PAIRS_DECODE, pairs)
        else:
            k, n_lin = n_lin, n_lin + 1
            p = _sub(params, f"L{i}.d.")
            x, gate, beta, log_g = _kda_inputs(a, p, cfg)
            with jax.named_scope("conv"):
                xc, conv[k] = deltanet.conv_update(conv[k], x, p["conv"],
                                                   slot_mask)
            q, kk, v = _kda_operands(xc, cfg)
            with jax.named_scope("kda_update"):
                o, delta = deltanet.deltanet_update(
                    delta, log_g, beta, q, kk, v, slot_mask, layer=k)
            h = h + _kda_out(o, gate, p, cfg, h.dtype)
            counts = _add(counts, cfg, _KDA_DECODE, live)
        y, counts = _ffn(_norm(h, params[f"L{i}.n.2"], cfg),
                         _sub(params, f"L{i}.f."), slot_mask, counts, cfg,
                         _HIT_DECODE)
        h = h + y
    new_cache = dict(cache, k_pages=kc, v_pages=vc,
                     lengths=jnp.where(slot_mask, pos + 1, pos),
                     state=(delta, *conv), counts=counts)
    return _logits(params, h, cfg), new_cache


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, state, slot, counts=None):
    """One chunk of ONE slot's prompt: ``ids`` [C] padded, ``start`` its
    first token's position, ``valid`` its true token count, ``page_table``
    the slot's page row, ``slot`` where its state lives. ``start == 0``
    starts a sequence: the slot's old state reads as zero. Returns (logits
    [V] f32 of the last valid token, k_pages, v_pages, delta, the conv
    arrays) and, when ``counts`` came, the counts vector after them."""
    kc, vc = k_pages, v_pages
    delta, *conv = state
    t = ids.shape[0]
    i_tok = jnp.arange(t)
    live = i_tok < valid
    fresh = start == 0
    pairs = jnp.sum(jnp.where(live, start + i_tok + 1, 0))
    tally = jnp.zeros(step_counts(cfg), jnp.int32) if counts is None \
        else counts
    h = params["embed"][ids]
    n_soft = n_lin = 0
    for i in range(cfg.num_layers):
        a = _norm(h, params[f"L{i}.n.1"], cfg)
        if i in cfg.gqa_layers:
            k, n_soft = n_soft, n_soft + 1
            p = _sub(params, f"L{i}.a.")
            q, kk, vv, gate = _gqa_qkv(a, p, cfg)
            page, off = pa.chunk_page_coords(page_table, start, valid, t,
                                             kc.shape[2])
            kc = kc.at[k, page, off].set(pa.kv_rows(kk, kc))
            vc = vc.at[k, page, off].set(pa.kv_rows(vv, vc))
            with jax.named_scope("gqa_chunk"):
                att = pa.prefill_attention(q[None], kc, vc, page_table, start,
                                           valid, layer=k)[0]
            h = h + _gqa_out(att, gate, p, h.dtype)
            tally = _add(tally, cfg, _PAIRS_PREFILL, pairs)
        else:
            k, n_lin = n_lin, n_lin + 1
            p = _sub(params, f"L{i}.d.")
            x, gate, beta, log_g = _kda_inputs(a, p, cfg)
            with jax.named_scope("conv"):
                xc, conv[k] = deltanet.conv_chunk(conv[k], x, p["conv"],
                                                  slot, fresh, valid)
            q, kk, v = _kda_operands(xc, cfg)
            with jax.named_scope("kda_chunk"):
                o, delta = deltanet.deltanet_chunk(
                    delta, log_g, beta, q, kk, v, slot, fresh, valid,
                    layer=k)
            h = h + _kda_out(o, gate, p, cfg, h.dtype)
            tally = _add(tally, cfg, _KDA_PREFILL, valid)
        y, tally = _ffn(_norm(h, params[f"L{i}.n.2"], cfg),
                        _sub(params, f"L{i}.f."), live, tally, cfg,
                        _HIT_PREFILL)
        h = h + y
    last = h[jnp.clip(valid - 1, 0, t - 1)]
    out = (_logits(params, last, cfg), kc, vc, delta, *conv)
    return out if counts is None else (*out, tally)


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 state, slot, counts=None):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, state=state,
                              slot=slot, counts=counts)


# ------------------------------------------------------------------- model

class SolarOpen2ForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays. ``engine_family`` tells `DecodeEngine` how to run
    it (inference/family.py)."""

    def __init__(self, cfg: SolarOpen2Config, params: dict):
        want = leaf_shapes(cfg)
        for name, shape in want.items():
            if name not in params:
                raise KeyError(f"missing parameter {name}")
            if tuple(params[name].shape) != tuple(shape):
                raise ValueError(f"{name}: {tuple(params[name].shape)}, "
                                 f"expected {tuple(shape)}")
        self.cfg = cfg
        self.params = {k: params[k] for k in want}

    def eval(self):
        return self

    def engine_family(self):
        return family(self.cfg)


def family(cfg: SolarOpen2Config):
    """What `DecodeEngine` takes from this family (inference/family.py):
    the softmax layers own rows of twin K and V pools (no ``page_rows``),
    and beside the pool ``recurrent`` state of two kinds a linear layer:
    its slab of the matrix state's stack and a convolution array of its
    own."""
    import sys
    from paddle_tpu.inference.family import ModelFamily
    return ModelFamily(
        name="solar_open2", steps=sys.modules[__name__],
        params=lambda m: dict(m.params), table_key="embed",
        kv_layers=len(cfg.softmax_layers), kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, max_positions=cfg.max_position_embeddings,
        state=lambda slots, page, dtype: state_arrays(cfg, slots, page,
                                                      dtype),
        step_counts=step_counts(cfg),
        on_counts=lambda grown: count_step(cfg, grown))
