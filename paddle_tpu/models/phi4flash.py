"""Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607) for the serving
engine: a decoder-hybrid-decoder stack with four kinds of token mixer and
three kinds of per-sequence state.

Layer ``i`` of ``n`` (``half = n // 2``; the published model: ``n`` = 32)::

    h += mixer_i(LN1_i(h));  h += W2 (SiLU(g) * u),  [u, g] = W1 LN2_i(h)

    i even, i <= half   Mamba-1; layer ``half`` also hands on its scan
                        output before the gate (the memory ``m``)
    i odd,  i <  half   differential attention over a window
    i == half + 1       differential attention over everything (its K and
                        V are kept in the page pool)
    i even, i > half    gated memory unit  W_o (m * SiLU(W_g r))
    i odd,  i > half+1  differential cross-attention onto layer
                        ``half + 1``'s K and V

No positional encoding; LayerNorm with bias; tied table. The equations and
the choices the published description leaves open (which head of a pair is
"1", which half of ``W1`` gates) are those of the benchmark's plain
reference, ``benchmarks/reference/phi4flash.py``, which the CPU tests hold
this file to.

Like ``models/gpt.py`` this file is PURE step functions over one parameter
layout, and the serving engine runs nothing else of it: ``decode_step``,
``prefill_step`` and ``prefill_chunk_step``. The layout is a flat dict of
arrays: ``embed``, ``ln_f.*``; ``front.m.*`` / ``front.a.*`` stacked over
the ``half / 2`` periods [Mamba, window attention]; ``mid.m.*`` / ``mid.a.*``
(layers ``half`` and ``half + 1``); ``back.g.*`` / ``back.c.*`` stacked over
the periods [memory unit, cross attention]. The back stack runs as a
``lax.scan`` over its periods, so a step program holds its two kinds of
layer once however deep the model is. The front stack is unrolled: each
window layer's rings are an array of their own (below), and a scan could
only reach one through a dynamic slice of a stack, which XLA materialises.

Per-sequence state (docs/SERVING.md "Three kinds of state"), everything a
step updates in place:

- paged, growing: ``k_pages`` / ``v_pages`` ``[1, P, page, nkv * hd]`` —
  layer ``half + 1`` alone, read by it and by every cross layer;
- window: ``win_k.<f>`` / ``win_v.<f>`` ``[slots, window + page, nkv *
  hd]``, one pair a window layer — a ring per slot
  (kernels/diff_attention.py);
- recurrent: ``conv`` ``[half / 2 + 1, slots, (d_conv - 1) * d_inner]`` and
  ``ssm`` ``[half / 2 + 1, slots, d_state, d_inner]`` float32
  (kernels/ssm.py), read as zero by the chunk that starts a sequence.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import diff_attention as da
from paddle_tpu.kernels import ssm as ssm_ops

__all__ = ["Phi4FlashConfig", "Phi4FlashForCausalLM", "decode_step",
           "prefill_step", "prefill_chunk_step", "leaf_shapes",
           "init_params", "state_arrays", "lambda_init", "tiny_config"]


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    # not in the published config.json (the modelling code's defaults)
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int | None = None          # None: ceil(hidden / 16)
    # SSM state kept in this type (a control keeps it in the served type)
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        if self.num_layers % 4:
            raise ValueError(
                f"num_layers {self.num_layers} is not a multiple of 4")
        if self.num_heads % self.num_kv_heads or self.num_kv_heads % 2:
            raise ValueError("heads pair up: num_kv_heads must be even and "
                             "divide num_heads")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self):
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def half(self):
        return self.num_layers // 2

    @property
    def n_front(self):                     # [Mamba, window] periods
        return self.half // 2

    @property
    def n_back(self):                      # [memory unit, cross] periods
        return (self.num_layers - self.half - 2) // 2

    @property
    def kv_width(self):
        return self.num_kv_heads * self.head_dim


def tiny_config(**over):
    """The CPU tests' preset: 8 layers, so all five mixers occur."""
    kw = dict(vocab_size=96, hidden_size=64, num_layers=8, num_heads=4,
              num_kv_heads=2, intermediate_size=96, sliding_window=8,
              max_position_embeddings=4096, mamba_d_state=8)
    kw.update(over)
    return Phi4FlashConfig(**kw)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def leaf_shapes(cfg: Phi4FlashConfig) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d, ff, di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    ds, dc, dtr, hd = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.dt_rank, \
        cfg.head_dim
    qw, kvw = cfg.num_heads * hd, cfg.kv_width
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
    nf, nb = cfg.n_front, cfg.n_back
    out = {"embed": (cfg.vocab_size, d), "ln_f.w": (d,), "ln_f.b": (d,)}
    out.update({f"front.m.{k}": (nf,) + v for k, v in mamba.items()})
    out.update({f"front.a.{k}": (nf,) + v for k, v in attn.items()})
    out.update({f"mid.m.{k}": v for k, v in mamba.items()})
    out.update({f"mid.a.{k}": v for k, v in attn.items()})
    out.update({f"back.g.{k}": (nb,) + v for k, v in gmu.items()})
    out.update({f"back.c.{k}": (nb,) + v for k, v in cross.items()})
    return out


def init_params(cfg: Phi4FlashConfig, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std), norm
    scales and ``D`` near 1, ``A_log`` = log(1..d_state), the step bias in
    Mamba's published range — every term alive."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        leaf = name.rsplit(".", 2)[-2:]
        if name.endswith("A_log"):
            w = jnp.broadcast_to(jnp.log(jnp.arange(1.0, shape[-1] + 1)),
                                 shape)
        elif name.endswith("dt_proj.b"):
            dt = jnp.exp(jax.random.uniform(k, shape) * math.log(100.0)
                         + math.log(1e-3))
            w = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith(("conv.w", "conv.b")):
            w = jax.random.uniform(k, shape, minval=-0.5, maxval=0.5)
        elif name.endswith(".lam"):
            w = 0.1 * jax.random.normal(k, shape)
        else:
            w = std * jax.random.normal(k, shape)
            if leaf[-1] == "D" or (leaf[-1] == "w" and (
                    leaf[0].startswith(("ln", "subln")))):
                w = 1.0 + w
        out[name] = w.astype(dtype)
    return out


def state_arrays(cfg: Phi4FlashConfig, slots: int, page_size: int, dtype):
    """The per-slot state beside the page pool, as ``(name, kind, shape,
    dtype)`` in the order the step functions take and return it."""
    ring = (slots, cfg.sliding_window + page_size, cfg.kv_width)
    nm = cfg.n_front + 1
    return (
        *((f"win_k.{f}", "window", ring, dtype) for f in range(cfg.n_front)),
        *((f"win_v.{f}", "window", ring, dtype) for f in range(cfg.n_front)),
        ("conv", "recurrent",
         (nm, slots, (cfg.mamba_d_conv - 1) * cfg.d_inner), dtype),
        ("ssm", "recurrent",
         (nm, slots, cfg.mamba_d_state, cfg.d_inner),
         jnp.dtype(cfg.ssm_state_dtype)),
    )


# ------------------------------------------------------------------ layers

def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w + b).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mlp(h, p, cfg):
    r = _ln(h, p["ln2.w"], p["ln2.b"], cfg.layer_norm_eps)
    u, g = jnp.split(r @ p["mlp.w1"], 2, axis=-1)
    return h + (_silu(g) * u) @ p["mlp.w2"]


def _split_state(state, cfg):
    """(window K rings, window V rings, conv, ssm) of :func:`state_arrays`'
    flat order; the rings as lists, one array a window layer."""
    n = cfg.n_front
    return list(state[:n]), list(state[n:2 * n]), state[2 * n], \
        state[2 * n + 1]


def _layer(stacked, f):
    """Layer ``f``'s leaves of a stack of leaves."""
    return {k: v[f] for k, v in stacked.items()}


def _sub(params, prefix):
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def _lam(p):
    lam = p["lam"].astype(jnp.float32)
    return jnp.exp((lam[0] * lam[1]).sum()) - jnp.exp((lam[2] * lam[3]).sum())


def _mamba_ssm_inputs(xc, p, cfg):
    """Convolution output (f32, before the activation) -> the f32 operands
    of the recurrence: (x, dt, B, C, A^T)."""
    dtype = p["x_proj"].dtype
    x = _silu(xc)
    dbc = x.astype(dtype) @ p["x_proj"]
    dr, bm, cm = jnp.split(
        dbc, [cfg.dt_rank, cfg.dt_rank + cfg.mamba_d_state], axis=-1)
    dt = jax.nn.softplus((dr @ p["dt_proj.w"]).astype(jnp.float32)
                         + p["dt_proj.b"].astype(jnp.float32))
    a_t = -jnp.exp(p["A_log"].astype(jnp.float32)).T
    return x, dt, bm.astype(jnp.float32), cm.astype(jnp.float32), a_t


def _mamba_decode(h, p, conv, ssm, active, layer, cfg):
    r = _ln(h, p["ln1.w"], p["ln1.b"], cfg.layer_norm_eps)
    x, z = jnp.split(r @ p["in_proj"], 2, axis=-1)   # conv input, gate
    xc, conv = ssm_ops.conv_update(conv, x, p["conv.w"], p["conv.b"], active,
                                   layer=layer)
    x, dt, bm, cm, a_t = _mamba_ssm_inputs(xc, p, cfg)
    y, ssm = ssm_ops.ssm_update(ssm, dt, x, bm, cm, a_t, p["D"], active,
                                layer=layer)
    out = (y.astype(h.dtype) * _silu(z)) @ p["out_proj"]
    return _mlp(h + out, p, cfg), y.astype(h.dtype), conv, ssm


def _mamba_prefill(h, p, conv, ssm, slot, start, valid, layer, cfg):
    r = _ln(h, p["ln1.w"], p["ln1.b"], cfg.layer_norm_eps)
    x, z = jnp.split(r @ p["in_proj"], 2, axis=-1)   # conv input, gate
    fresh = start == 0
    xc, conv = ssm_ops.conv_scan(conv, x, p["conv.w"], p["conv.b"], slot,
                                 fresh, valid, layer=layer)
    x, dt, bm, cm, a_t = _mamba_ssm_inputs(xc, p, cfg)
    dt = jnp.where((jnp.arange(h.shape[0]) < valid)[:, None], dt, 0.0)
    y, ssm = ssm_ops.ssm_scan(ssm, dt, x, bm, cm, a_t, p["D"], slot, fresh,
                              layer=layer)
    out = (y.astype(h.dtype) * _silu(z)) @ p["out_proj"]
    return _mlp(h + out, p, cfg), y.astype(h.dtype), conv, ssm


def _qkv(h, p, cfg):
    r = _ln(h, p["ln1.w"], p["ln1.b"], cfg.layer_norm_eps)
    qw, kvw = cfg.num_heads * cfg.head_dim, cfg.kv_width
    return jnp.split(r @ p["qkv.w"] + p["qkv.b"], [qw, qw + kvw], axis=-1)


def _attn_out(h, att, p, cfg):
    return _mlp(h + att @ p["out.w"] + p["out.b"], p, cfg)


def _heads(cfg):
    return dict(nq=cfg.num_heads, nkv=cfg.num_kv_heads)


def _gmu(h, memory, p, cfg):
    r = _ln(h, p["ln1.w"], p["ln1.b"], cfg.layer_norm_eps)
    out = (memory * _silu(r @ p["gate"])) @ p["out"]
    return _mlp(h + out, p, cfg)


def _cross(h, read, p, l0, cfg):
    r = _ln(h, p["ln1.w"], p["ln1.b"], cfg.layer_norm_eps)
    att = read(r @ p["q.w"] + p["q.b"], _lam(p) + l0, l0, p["subln.w"],
               eps=cfg.layer_norm_eps)
    return _attn_out(h, att, p, cfg)


def _logits(params, h, cfg):
    h = _ln(h, params["ln_f.w"], params["ln_f.b"], cfg.layer_norm_eps)
    return (h @ params["embed"].T).astype(jnp.float32)


def _back(params, h, memory, read, cfg):
    """The [memory unit, cross attention] periods over stacked leaves;
    ``read`` is layer ``half + 1``'s attention over its K and V
    (`da.paged_decode` / `da.paged_prefill`)."""
    if cfg.n_back == 0:
        return h

    def period(h, xs):
        pg, pc, l0 = xs
        h = _gmu(h, memory, pg, cfg)
        return _cross(h, read, pc, l0, cfg), None

    l0s = jnp.asarray([lambda_init(cfg.half + 3 + 2 * b)
                       for b in range(cfg.n_back)], jnp.float32)
    h, _ = jax.lax.scan(period, h, (_sub(params, "back.g."),
                                    _sub(params, "back.c."), l0s))
    return h


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token
    through all three kinds of state.

    ids : [B] int32; cache : ``k_pages`` / ``v_pages`` [1, P, page, nkv *
    hd], ``page_table`` [B, pages_per_slot], ``lengths`` [B], ``state`` =
    (every window K ring, every window V ring, conv, ssm) as
    :func:`state_arrays` orders them;
    slot_mask : [B] bool — an inactive slot writes to the trash page and
    leaves its window ring and recurrent state alone (it may be in the
    middle of a chunked prefill). Returns (logits [B, V] f32, new cache)."""
    kc, vc = cache["k_pages"], cache["v_pages"]
    table, pos = cache["page_table"], cache["lengths"]
    wk, wv, conv, ssm = _split_state(cache["state"], cfg)
    h = params["embed"][ids]
    hk = _heads(cfg)
    front_m, front_a = _sub(params, "front.m."), _sub(params, "front.a.")
    for f in range(cfg.n_front):
        pm, pa = _layer(front_m, f), _layer(front_a, f)
        l0 = lambda_init(2 * f + 1)
        h, _, conv, ssm = _mamba_decode(h, pm, conv, ssm, slot_mask, f, cfg)
        q, k, v = _qkv(h, pa, cfg)
        att, wk[f], wv[f] = da.window_decode(
            q, k, v, wk[f], wv[f], pos, slot_mask, _lam(pa) + l0, l0,
            pa["subln.w"], window=cfg.sliding_window, **hk)
        h = _attn_out(h, att, pa, cfg)
    h, memory, conv, ssm = _mamba_decode(
        h, _sub(params, "mid.m."), conv, ssm, slot_mask, cfg.n_front, cfg)
    pa = _sub(params, "mid.a.")
    l0 = lambda_init(cfg.half + 1)
    q, k, v = _qkv(h, pa, cfg)
    att, kc, vc, read = da.paged_decode(
        q, k, v, kc, vc, table, pos, slot_mask, _lam(pa) + l0, l0,
        pa["subln.w"], **hk)
    h = _attn_out(h, att, pa, cfg)
    h = _back(params, h, memory, read, cfg)
    new_cache = dict(k_pages=kc, v_pages=vc, page_table=table,
                     lengths=jnp.where(slot_mask, pos + 1, pos),
                     state=(*wk, *wv, conv, ssm))
    return _logits(params, h, cfg), new_cache


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, state, slot):
    """One chunk of ONE slot's prompt: ``ids`` [C] padded, ``start`` its
    first token's position, ``valid`` its true token count, ``page_table``
    the slot's page row, ``slot`` where its window ring and recurrent
    state live. ``start == 0`` starts a sequence: the slot's old state and
    ring read as empty. Otherwise the state is carried in from the chunk
    before. Returns (logits [V] f32 of the last valid token, k_pages,
    v_pages, *state)."""
    wk, wv, conv, ssm = _split_state(state, cfg)
    h = params["embed"][ids]
    hk = _heads(cfg)
    front_m, front_a = _sub(params, "front.m."), _sub(params, "front.a.")
    for f in range(cfg.n_front):
        pm, pa = _layer(front_m, f), _layer(front_a, f)
        l0 = lambda_init(2 * f + 1)
        h, _, conv, ssm = _mamba_prefill(h, pm, conv, ssm, slot, start,
                                         valid, f, cfg)
        q, k, v = _qkv(h, pa, cfg)
        att, wk[f], wv[f] = da.window_prefill(
            q, k, v, wk[f], wv[f], slot, start, valid, _lam(pa) + l0, l0,
            pa["subln.w"], window=cfg.sliding_window, **hk)
        h = _attn_out(h, att, pa, cfg)
    h, memory, conv, ssm = _mamba_prefill(
        h, _sub(params, "mid.m."), conv, ssm, slot, start, valid,
        cfg.n_front, cfg)
    pa = _sub(params, "mid.a.")
    l0 = lambda_init(cfg.half + 1)
    q, k, v = _qkv(h, pa, cfg)
    att, k_pages, v_pages, read = da.paged_prefill(
        q, k, v, k_pages, v_pages, page_table, start, valid, _lam(pa) + l0,
        l0, pa["subln.w"], **hk)
    h = _attn_out(h, att, pa, cfg)
    h = _back(params, h, memory, read, cfg)
    last = h[jnp.clip(valid - 1, 0, h.shape[0] - 1)]
    return (_logits(params, last, cfg), k_pages, v_pages, *wk, *wv, conv,
            ssm)


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 state, slot):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, state=state,
                              slot=slot)


# ------------------------------------------------------------------- model

class Phi4FlashForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays, nothing built leaf by leaf. ``engine_family``
    tells `DecodeEngine` how to run it (inference/family.py)."""

    def __init__(self, cfg: Phi4FlashConfig, params: dict):
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
            name="phi4flash", steps=sys.modules[__name__],
            params=lambda m: dict(m.params), table_key="embed",
            kv_layers=1, kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            max_positions=cfg.max_position_embeddings,
            state=lambda slots, page, dtype: state_arrays(cfg, slots, page,
                                                          dtype),
            window_tokens=cfg.sliding_window)
