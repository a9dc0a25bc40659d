"""Granite-4.0-H (``granitemoehybrid``) for the serving engine: Mamba-2
layers with one grouped-query attention layer a period, and in every layer
drop-free routed experts beside a shared expert, as ONE CHIP'S SHARE of an
expert-parallel deployment.

Layer ``i`` (``layer_types[i]`` is ``mamba`` or ``attention``)::

    h0 = embed[ids] * embedding_multiplier
    a  = RMSNorm(h);  h = h + r * mix_i(a)          r = residual_multiplier
    b  = RMSNorm(h);  h = h + r * (routed(b) + shared(b))
    logits = RMSNorm(h) embed^T / logits_scaling    (tied table)

    attention   q, k, v = a Wq, a Wk, a Wv; no bias, NO positional
                encoding; scores q k^T * attention_multiplier; each K/V
                head read by num_heads / num_kv_heads query heads; Wo
    mamba       [z | xBC | dt] = a W_in; xBC = silu(conv1d(xBC)) (depthwise,
                causal, with bias); [x | B | C] = xBC; dt = softplus(dt +
                dt_bias); per head S_t = exp(dt A) S_{t-1} + dt x (outer) B,
                y = S C + D x (kernels/ssm2.py); y = RMSNorm(y * silu(z)) *
                w over the whole inner width (the gate BEFORE the norm);
                out = y W_out
    routed      float32 router logits over ALL ``num_experts``, the
                ``experts_per_token`` largest, a softmax over those; this
                chip adds the terms of the experts ``experts_held = (lo,
                hi)`` it holds and leaves the others' out
                (kernels/moe.py)
    shared      the same gated form, for every token

The equations are the benchmark's plain reference's
(``benchmarks/reference/granitemoehybrid.py``), which the CPU tests hold
this file to. Like ``models/phi4flash.py`` this file is PURE step functions
over one flat dict of arrays: ``embed``, ``norm_f.w``; ``m.*`` stacked over
the Mamba layers, ``a.*`` over the attention layers, ``f.*`` (the second
norm, router, held experts, shared expert) over ALL layers. A run of like
layers is one ``lax.fori_loop`` whose body reads its layer's leaves at a
traced index (what a ``lax.scan`` over the stack lowers to, without
slicing the stack first), so a program holds the Mamba layer once per run
however deep the model is; an attention layer stands alone.

Per-sequence state (docs/SERVING.md "Three kinds of state"), updated in
place by every step:

- paged, growing: ``k_pages`` / ``v_pages`` ``[n_attention, P, page, nkv *
  hd]``;
- recurrent: ``conv`` ``[n_mamba, slots, (d_conv - 1) * conv_dim]`` (served
  type) and ``ssm`` ``[n_mamba, slots, d_state, heads * head_dim]`` float32
  (kernels/ssm2.py says why: the state size on sublanes, heads and head
  width as one lane axis) — at the published size 38 MB a slot, the cache
  manager's LARGEST array.

Routing counts: each step adds its routing to an int32 vector (one entry a
held expert summed over layers, then all assignments) that the engine
carries beside the token chain and reads back with the tokens
(inference/family.py ``step_counts``); `count_routing` turns what reaches
the host into the ``engine.moe.*`` counters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import moe, ssm as ssm_ops, ssm2
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.observability import metrics

__all__ = ["GraniteMoeHybridConfig", "GraniteMoeHybridForCausalLM",
           "decode_step", "prefill_step", "prefill_chunk_step",
           "leaf_shapes", "init_params", "state_arrays", "tiny_config",
           "count_routing", "expert_totals"]

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: tuple = PERIOD * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128                       # not in config.json: d / heads
    intermediate_size: int = 768              # one routed expert's width
    shared_intermediate_size: int = 1536
    num_experts: int = 72                     # the router's outputs
    experts_per_token: int = 10
    experts_held: tuple = (0, 72)             # [lo, hi) on this chip
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    max_position_embeddings: int = 131072
    # SSM state kept in this type (a control keeps it in the served type)
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"layer_types: unknown kinds {sorted(bad)}")
        if self.mamba_n_groups != 1:
            raise ValueError("mamba_n_groups: only one group is written")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads must divide num_heads")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")

    @property
    def n_layers(self):
        return len(self.layer_types)

    @property
    def n_mamba(self):
        return self.layer_types.count("mamba")

    @property
    def n_attention(self):
        return self.layer_types.count("attention")

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def kv_width(self):
        return self.num_kv_heads * self.head_dim

    def runs(self):
        """The stack as runs of like layers: ``(kind, first layer, first
        index among its kind, count)``; an attention layer is a run of
        one."""
        out, seen = [], {"mamba": 0, "attention": 0}
        for i, kind in enumerate(self.layer_types):
            if out and kind == "mamba" and out[-1][0] == "mamba":
                out[-1][3] += 1
            else:
                out.append([kind, i, seen[kind], 1])
            seen[kind] += 1
        return [tuple(r) for r in out]


def tiny_config(**over):
    """The CPU tests' preset: one short period, every mechanism present."""
    kw = dict(vocab_size=96, hidden_size=32,
              layer_types=("mamba", "mamba", "attention", "mamba"),
              num_heads=4, num_kv_heads=2, head_dim=8, intermediate_size=16,
              shared_intermediate_size=24, num_experts=8,
              experts_per_token=3, experts_held=(0, 4), mamba_n_heads=4,
              mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
              max_position_embeddings=4096)
    kw.update(over)
    return GraniteMoeHybridConfig(**kw)


def leaf_shapes(cfg: GraniteMoeHybridConfig) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d, di, cd = cfg.hidden_size, cfg.d_inner, cfg.conv_dim
    nh = cfg.mamba_n_heads
    qw, kvw = cfg.num_heads * cfg.head_dim, cfg.kv_width
    f, fs = cfg.intermediate_size, cfg.shared_intermediate_size
    mamba = {"norm.w": (d,), "in_proj": (d, di + cd + nh),
             "conv.w": (cfg.mamba_d_conv, cd), "conv.b": (cd,),
             "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
             "gnorm.w": (di,), "out_proj": (di, d)}
    attn = {"norm.w": (d,), "qkv.w": (d, qw + 2 * kvw), "o.w": (qw, d)}
    ffn = {"norm.w": (d,), "router": (d, cfg.num_experts),
           "w1": (cfg.n_held, d, 2 * f), "w2": (cfg.n_held, f, d),
           "shared.w1": (d, 2 * fs), "shared.w2": (fs, d)}
    out = {"embed": (cfg.vocab_size, d), "norm_f.w": (d,)}
    out.update({f"m.{k}": (cfg.n_mamba,) + v for k, v in mamba.items()})
    out.update({f"a.{k}": (cfg.n_attention,) + v for k, v in attn.items()})
    out.update({f"f.{k}": (cfg.n_layers,) + v for k, v in ffn.items()})
    return out


def init_params(cfg: GraniteMoeHybridConfig, seed: int = 0,
                dtype=jnp.float32, std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std), norm
    scales and ``D`` near 1, ``A_log`` = log U(1, 16) and the step bias in
    Mamba-2's published ranges — every term alive."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("A_log"):
            w = jnp.log(jax.random.uniform(k, shape, minval=1.0,
                                           maxval=16.0))
        elif name.endswith("dt_bias"):
            dt = jnp.exp(jax.random.uniform(k, shape) * math.log(100.0)
                         + math.log(1e-3))
            w = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith(("conv.w", "conv.b")):
            w = jax.random.uniform(k, shape, minval=-0.5, maxval=0.5)
        else:
            w = std * jax.random.normal(k, shape)
            if name.endswith(("norm.w", "norm_f.w", ".D")):
                w = 1.0 + w
        out[name] = w.astype(dtype)
    return out


def state_arrays(cfg: GraniteMoeHybridConfig, slots: int, page_size: int,
                 dtype):
    """The per-slot state beside the page pool, as ``(name, kind, shape,
    dtype)`` in the order the step functions take and return it."""
    return (
        ("conv", "recurrent",
         (cfg.n_mamba, slots, (cfg.mamba_d_conv - 1) * cfg.conv_dim), dtype),
        ("ssm", "recurrent",
         (cfg.n_mamba, slots, cfg.mamba_d_state, cfg.d_inner),
         jnp.dtype(cfg.ssm_state_dtype)),
    )


def step_counts(cfg: GraniteMoeHybridConfig) -> int:
    """Entries of the routing vector a step adds to: one a held expert,
    then all assignments."""
    return cfg.n_held + 1


# ----------------------------------------------------- routing on the host

_expert_totals: dict = {}      # (lo, hi) -> int64 totals a held expert


def count_routing(cfg: GraniteMoeHybridConfig, grown: np.ndarray):
    """What the routing vector grew by between two readbacks of the token
    chain, into ``engine.moe.assignments`` (tokens x experts a token x
    layers), ``engine.moe.assignments_held`` (those on a held expert) and
    the totals a held expert (`expert_totals`)."""
    metrics.counter("engine.moe.assignments").inc(int(grown[-1]))
    metrics.counter("engine.moe.assignments_held").inc(int(grown[:-1].sum()))
    tot = _expert_totals.setdefault(
        cfg.experts_held, np.zeros(cfg.n_held, np.int64))
    tot += grown[:-1].astype(np.int64)


def expert_totals(held) -> list:
    """Assignments that landed on each expert of ``held`` since the
    process began, over every engine that holds them."""
    return _expert_totals.get(tuple(held), np.zeros(0, np.int64)).tolist()


# ------------------------------------------------------------------ layers

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _sub(params, prefix):
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def _at(stacked, i):
    """Layer ``i``'s leaves of a stack of leaves (``i`` may be traced)."""
    return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            for k, v in stacked.items()}


def _ffn(h, p, valid, counts, cfg):
    """The layer's second half: routed experts (this chip's) + shared."""
    b = _rms(h, p["norm.w"], cfg.rms_norm_eps)
    routed, counts = moe.routed_experts(
        b, p["router"], p["w1"], p["w2"], top_k=cfg.experts_per_token,
        held=cfg.experts_held, counts=counts, valid=valid)
    u, v = jnp.split(b @ p["shared.w1"], 2, axis=-1)
    shared = (_silu(u) * v) @ p["shared.w2"]
    r = jnp.asarray(cfg.residual_multiplier, h.dtype)
    return h + r * (routed + shared), counts


def _mamba_inputs(a, p, cfg):
    """(gate z, conv input xBC, raw dt) of the in-projection."""
    di, cd = cfg.d_inner, cfg.conv_dim
    zxd = a @ p["in_proj"]
    return zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]


def _ssm_operands(xc, dt_raw, p, cfg):
    """Convolution output (f32, before the activation) and the raw step ->
    the f32 operands of the recurrence: (x [.., H, P], dt [.., H], B, C,
    A [H])."""
    di, n = cfg.d_inner, cfg.mamba_d_state
    xbc = _silu(xc)
    x = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.mamba_n_heads,
                              cfg.mamba_d_head)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    return x, dt, xbc[..., di:di + n], xbc[..., di + n:], a


def _mamba_out(h, y, z, p, cfg):
    """Gate, the gated norm over the whole inner width, out-projection."""
    y = y.reshape(*y.shape[:-2], cfg.d_inner) * _silu(z.astype(jnp.float32))
    y = _rms(y, p["gnorm.w"], cfg.rms_norm_eps).astype(h.dtype)
    return h + jnp.asarray(cfg.residual_multiplier, h.dtype) \
        * (y @ p["out_proj"])


def _mamba_decode(h, p, conv, ssm, active, layer, cfg):
    z, xbc, dt_raw = _mamba_inputs(_rms(h, p["norm.w"], cfg.rms_norm_eps),
                                   p, cfg)
    xc, conv = ssm_ops.conv_update(conv, xbc, p["conv.w"], p["conv.b"],
                                   active, layer=layer)
    x, dt, bm, cm, a = _ssm_operands(xc, dt_raw, p, cfg)
    y, ssm = ssm2.ssm2_update(ssm, dt, x, bm, cm, a, p["D"], active,
                              layer=layer)
    return _mamba_out(h, y, z, p, cfg), conv, ssm


def _mamba_prefill(h, p, conv, ssm, slot, start, valid, layer, cfg):
    z, xbc, dt_raw = _mamba_inputs(_rms(h, p["norm.w"], cfg.rms_norm_eps),
                                   p, cfg)
    fresh = start == 0
    xc, conv = ssm_ops.conv_scan(conv, xbc, p["conv.w"], p["conv.b"], slot,
                                 fresh, valid, layer=layer)
    x, dt, bm, cm, a = _ssm_operands(xc, dt_raw, p, cfg)
    dt = jnp.where((jnp.arange(h.shape[0]) < valid)[:, None], dt, 0.0)
    y, ssm = ssm2.ssm2_scan(ssm, dt, x, bm, cm, a, p["D"], slot, fresh,
                            layer=layer, chunk=cfg.mamba_chunk_size)
    return _mamba_out(h, y, z, p, cfg), conv, ssm


def _qkv(h, p, cfg):
    a = _rms(h, p["norm.w"], cfg.rms_norm_eps)
    qw, kvw = cfg.num_heads * cfg.head_dim, cfg.kv_width
    q, k, v = jnp.split(a @ p["qkv.w"], [qw, qw + kvw], axis=-1)
    heads = lambda x, n: x.reshape(*x.shape[:-1], n, cfg.head_dim)  # noqa
    return (heads(q, cfg.num_heads), heads(k, cfg.num_kv_heads),
            heads(v, cfg.num_kv_heads))


def _attn_out(h, att, p, cfg):
    att = att.reshape(*att.shape[:-2], cfg.num_heads * cfg.head_dim)
    return h + jnp.asarray(cfg.residual_multiplier, h.dtype) \
        * (att @ p["o.w"])


def _logits(params, h, cfg):
    h = _rms(h, params["norm_f.w"], cfg.rms_norm_eps)
    return (h @ params["embed"].T).astype(jnp.float32) / cfg.logits_scaling


def _embed(params, ids, cfg):
    h = params["embed"][ids]
    return h * jnp.asarray(cfg.embedding_multiplier, h.dtype)


def _walk(params, h, carry, counts, valid, cfg, mamba, attention):
    """The stack: ``mamba(h, p, carry, mamba index)`` and ``attention(h, p,
    carry, attention index)`` -> ``(h, carry)`` a layer's first half, then
    the layer's experts; a run of Mamba layers as one loop."""
    pm, pa_, pf = (_sub(params, s) for s in ("m.", "a.", "f."))
    for kind, first, k0, n in cfg.runs():
        if kind == "attention":
            h, carry = attention(h, _at(pa_, k0), carry, k0)
            h, counts = _ffn(h, _at(pf, first), valid, counts, cfg)
            continue

        def layer(i, c, first=first, k0=k0):
            h, carry, counts = c
            h, carry = mamba(h, _at(pm, k0 + i), carry, k0 + i)
            h, counts = _ffn(h, _at(pf, first + i), valid, counts, cfg)
            return h, carry, counts

        h, carry, counts = jax.lax.fori_loop(0, n, layer,
                                             (h, carry, counts))
    return h, carry, counts


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token.

    ids : [B] int32; cache : ``k_pages`` / ``v_pages`` [n_attention, P,
    page, nkv * hd], ``page_table`` [B, pages_per_slot], ``lengths`` [B],
    ``state`` = (conv, ssm), ``counts`` the routing vector (int32
    [held + 1]; optional); slot_mask : [B] bool — an inactive slot writes
    to the trash page, leaves its recurrent state alone and is not
    counted. Returns (logits [B, V] f32, new cache)."""
    table, pos = cache["page_table"], cache["lengths"]
    conv, ssm = cache["state"]
    counts = cache.get("counts")
    if counts is None:
        counts = jnp.zeros(step_counts(cfg), jnp.int32)

    def mamba(h, p, carry, k):
        kc, vc, conv, ssm = carry
        h, conv, ssm = _mamba_decode(h, p, conv, ssm, slot_mask, k, cfg)
        return h, (kc, vc, conv, ssm)

    def attention(h, p, carry, k):
        kc, vc, conv, ssm = carry
        q, kk, vv = _qkv(h, p, cfg)
        kc, vc = pa.write_token_kv(kc, vc, kk, vv, table, pos, slot_mask, k)
        att = pa.paged_attention(q, kc, vc, table, pos, layer=k,
                                 scale=cfg.attention_multiplier)
        return _attn_out(h, att, p, cfg), (kc, vc, conv, ssm)

    h, (kc, vc, conv, ssm), counts = _walk(
        params, _embed(params, ids, cfg),
        (cache["k_pages"], cache["v_pages"], conv, ssm), counts, slot_mask,
        cfg, mamba, attention)
    new_cache = dict(k_pages=kc, v_pages=vc, page_table=table,
                     lengths=jnp.where(slot_mask, pos + 1, pos),
                     state=(conv, ssm), counts=counts)
    return _logits(params, h, cfg), new_cache


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, state, slot, counts=None):
    """One chunk of ONE slot's prompt: ``ids`` [C] padded, ``start`` its
    first token's position, ``valid`` its true token count, ``page_table``
    the slot's page row, ``slot`` where its recurrent state lives. ``start
    == 0`` starts a sequence: the slot's old state reads as zero. Returns
    (logits [V] f32 of the last valid token, k_pages, v_pages, conv, ssm)
    and, when ``counts`` came, the routing vector after them."""
    conv, ssm = state
    live = jnp.arange(ids.shape[0]) < valid
    tally = jnp.zeros(step_counts(cfg), jnp.int32) if counts is None \
        else counts

    def mamba(h, p, carry, k):
        kc, vc, conv, ssm = carry
        h, conv, ssm = _mamba_prefill(h, p, conv, ssm, slot, start, valid, k,
                                      cfg)
        return h, (kc, vc, conv, ssm)

    def attention(h, p, carry, k):
        kc, vc, conv, ssm = carry
        q, kk, vv = _qkv(h, p, cfg)
        page, off = pa.chunk_page_coords(page_table, start, valid,
                                         ids.shape[0], kc.shape[2])
        kc = kc.at[k, page, off].set(pa.kv_rows(kk, kc))
        vc = vc.at[k, page, off].set(pa.kv_rows(vv, vc))
        att = pa.prefill_attention(q[None], kc, vc, page_table, start, valid,
                                   layer=k,
                                   scale=cfg.attention_multiplier)[0]
        return _attn_out(h, att, p, cfg), (kc, vc, conv, ssm)

    h, (k_pages, v_pages, conv, ssm), tally = _walk(
        params, _embed(params, ids, cfg), (k_pages, v_pages, conv, ssm),
        tally, live, cfg, mamba, attention)
    last = h[jnp.clip(valid - 1, 0, h.shape[0] - 1)]
    out = (_logits(params, last, cfg), k_pages, v_pages, conv, ssm)
    return out if counts is None else (*out, tally)


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 state, slot, counts=None):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, state=state,
                              slot=slot, counts=counts)


# ------------------------------------------------------------------- model

class GraniteMoeHybridForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays. ``engine_family`` tells `DecodeEngine` how to run
    it (inference/family.py)."""

    def __init__(self, cfg: GraniteMoeHybridConfig, params: dict):
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
        import sys
        from paddle_tpu.inference.family import ModelFamily
        cfg = self.cfg
        return ModelFamily(
            name="granitemoehybrid", steps=sys.modules[__name__],
            params=lambda m: dict(m.params), table_key="embed",
            kv_layers=cfg.n_attention, kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim,
            max_positions=cfg.max_position_embeddings,
            state=lambda slots, page, dtype: state_arrays(cfg, slots, page,
                                                          dtype),
            step_counts=step_counts(cfg),
            on_counts=lambda grown: count_routing(cfg, grown))
