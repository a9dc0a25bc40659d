"""Brumby (``brumby``) for the serving engine: a dense decoder whose every
layer mixes tokens by POWER RETENTION, a gated linear attention of degree 2,
and keeps no K or V: a sequence's memory is a fixed-size matrix state a
layer, whatever its length.

Layer (pre-norm residual block; ``hd`` the head width, query head ``i``
reads key-value head ``i // (heads / kv_heads)``)::

    h0 = embed[ids]
    a  = RMSNorm(h)
    q  = RoPE(RMSNorm_head(a Wq));  k = RoPE(RMSNorm_head(a Wk));  v = a Wv
         RMSNorm_head over a head's ``hd`` values with one learned vector
         for q and one for k; RoPE the half-rotation form at the token's
         absolute position, ``rope_theta``
    log g = logsigmoid(a Wg + bg)            one gate a kv head and token
    S_t = g_t S_{t-1} + phi(k_t) v_t^T;  z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t / hd)^T S_t / (phi(q_t / hd)^T z_t + eps)
         with phi(u) . phi(w) = (u . w)^2 (kernels/retention.py)
    h  = h + concat(y) Wo
    b  = RMSNorm(h);  h = h + (silu(b W_gate) * (b W_up)) W_down
    logits = RMSNorm(h) W_head               (untied)

The equations are the benchmark's plain reference's
(``benchmarks/reference/brumby.py``, which computes step 3 as causal
attention with ``(q . k / hd)^2`` weights and never forms a state), which
the CPU tests hold this file to. The state form is kept at EVERY length:
the published code's switch to K and V below some sequence length is not
written.

Like ``models/granitemoehybrid.py`` this file is PURE step functions over
one flat dict of arrays: ``embed``, ``head``, ``norm_f.w`` and ``l.*``
stacked over the layers; the stack is ONE ``lax.fori_loop`` whose body
reads its layer's leaves at a traced index. Matrices are ``[in, out]``;
``l.qkv.w`` holds the columns q | k | v, ``l.mlp.w1`` the columns gate |
up.

Per-sequence state (docs/SERVING.md "The model seam"): two ``recurrent``
arrays, ``S`` ``[layers, slots, kv_heads, hd / 2 + 1, hd, hd]`` and ``z``
``[layers, slots, kv_heads, hd / 2 + 1, hd]`` float32 — at the published
widths 34.3 MB a layer a sequence — and NO page pool: ``kv_layers`` is 0,
the engine allocates no page for a sequence of this family, and the
sequence's length costs no memory. Positions come from the step's
``lengths`` (decode) and the chunk's ``start`` (prefill).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import retention

__all__ = ["BrumbyConfig", "BrumbyForCausalLM", "decode_step",
           "prefill_step", "prefill_chunk_step", "leaf_shapes",
           "init_params", "state_arrays", "tiny_config"]


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    num_layers: int = 40
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    retention_eps: float = 1e-6               # the normaliser's (assumed)
    # the retention state kept in this type (a control keeps it in bf16)
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads must divide num_heads")
        retention.diagonals(self.head_dim)

    @property
    def q_width(self):
        return self.num_heads * self.head_dim

    @property
    def kv_width(self):
        return self.num_kv_heads * self.head_dim


def tiny_config(**over):
    """The CPU tests' preset: every mechanism present."""
    kw = dict(vocab_size=96, hidden_size=32, num_layers=3, num_heads=4,
              num_kv_heads=2, head_dim=8, intermediate_size=48,
              max_position_embeddings=4096)
    kw.update(over)
    return BrumbyConfig(**kw)


def leaf_shapes(cfg: BrumbyConfig) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d, n, hd = cfg.hidden_size, cfg.num_layers, cfg.head_dim
    qw, kvw, f = cfg.q_width, cfg.kv_width, cfg.intermediate_size
    layer = {"norm1.w": (d,), "qkv.w": (d, qw + 2 * kvw),
             "q_norm.w": (hd,), "k_norm.w": (hd,),
             "gate.w": (d, cfg.num_kv_heads), "gate.b": (cfg.num_kv_heads,),
             "o.w": (qw, d), "norm2.w": (d,), "mlp.w1": (d, 2 * f),
             "mlp.w2": (f, d)}
    out = {"embed": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
           "norm_f.w": (d,)}
    out.update({f"l.{k}": (n,) + v for k, v in layer.items()})
    return out


def init_params(cfg: BrumbyConfig, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std), norm
    scales near 1, the gate's bias so that -log g is log-uniform in [1e-3,
    1e-1] over the heads (a state that forgets over 10 to 1,000 tokens:
    every term alive at a test's lengths)."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name == "l.gate.b":
            lam = jnp.exp(jax.random.uniform(k, shape) * math.log(100.0)
                          + math.log(1e-3))
            w = -jnp.log(jnp.expm1(lam))        # softplus(-w) = lam
        else:
            w = std * jax.random.normal(k, shape)
            if "norm" in name:
                w = 1.0 + w
        out[name] = w.astype(dtype)
    return out


def state_arrays(cfg: BrumbyConfig, slots: int, page_size: int, dtype):
    """The per-slot state, as ``(name, kind, shape, dtype)`` in the order
    the step functions take and return it."""
    s, z = retention.state_shapes(cfg.num_layers, slots, cfg.num_kv_heads,
                                  cfg.head_dim)
    dt = jnp.dtype(cfg.state_dtype)
    return (("retention", "recurrent", s, dt),
            ("normaliser", "recurrent", z, dt))


# ------------------------------------------------------------------ layers

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _at(stacked, i):
    """Layer ``i``'s leaves of the stack (``i`` may be traced)."""
    return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            for k, v in stacked.items()}


def _mixer_inputs(h, p, positions, cfg):
    """(q [T, heads, hd] f32, k, v [T, kv, hd], log g [T, kv] f32) of a
    layer's first half, for ``T`` tokens at ``positions``."""
    a = _rms(h, p["norm1.w"], cfg.rms_norm_eps)
    t, hd = a.shape[0], cfg.head_dim
    q, k, v = jnp.split(a @ p["qkv.w"], [cfg.q_width,
                                         cfg.q_width + cfg.kv_width], -1)
    q = _rms(q.reshape(t, cfg.num_heads, hd).astype(jnp.float32),
             p["q_norm.w"], cfg.rms_norm_eps)
    k = _rms(k.reshape(t, cfg.num_kv_heads, hd).astype(jnp.float32),
             p["k_norm.w"], cfg.rms_norm_eps)
    qk = retention.rotary(jnp.concatenate([q, k], axis=1), positions,
                          cfg.rope_theta)
    x = jnp.dot(a, p["gate.w"], preferred_element_type=jnp.float32) \
        + p["gate.b"].astype(jnp.float32)
    return (qk[:, :cfg.num_heads], qk[:, cfg.num_heads:],
            v.reshape(t, cfg.num_kv_heads, hd), jax.nn.log_sigmoid(x))


def _mixer_out(h, y, p, cfg):
    """The out-projection's residual, then the gated MLP's."""
    h = h + y.reshape(y.shape[0], cfg.q_width).astype(h.dtype) @ p["o.w"]
    b = _rms(h, p["norm2.w"], cfg.rms_norm_eps)
    u, w = jnp.split(b @ p["mlp.w1"], 2, axis=-1)
    return h + (u * jax.nn.sigmoid(u) * w) @ p["mlp.w2"]


def _stack(params):
    return {k[2:]: v for k, v in params.items() if k.startswith("l.")}


def _logits(params, h, cfg):
    h = _rms(h, params["norm_f.w"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token.

    ids : [B] int32; cache : ``lengths`` [B] (each slot's position),
    ``state`` = (S, z); ``k_pages`` / ``v_pages`` / ``page_table`` are the
    engine's empty ones and pass through; slot_mask : [B] bool — an
    inactive slot's state is left alone. Returns (logits [B, V] f32, new
    cache)."""
    pos = cache["lengths"]
    stack = _stack(params)

    def layer(i, c):
        h, s, z = c
        p = _at(stack, i)
        q, k, v, lg = _mixer_inputs(h, p, pos, cfg)
        y, s, z = retention.retention_update(
            s, z, lg, q, k, v, slot_mask, layer=i, eps=cfg.retention_eps)
        return _mixer_out(h, y, p, cfg), s, z

    h, s, z = jax.lax.fori_loop(0, cfg.num_layers, layer,
                                (params["embed"][ids], *cache["state"]))
    return _logits(params, h, cfg), dict(
        cache, lengths=jnp.where(slot_mask, pos + 1, pos), state=(s, z))


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, state, slot):
    """One chunk of ONE slot's prompt: ``ids`` [C] padded, ``start`` its
    first token's position, ``valid`` its true token count, ``slot`` where
    its state lives. ``start == 0`` starts a sequence: the slot's old state
    reads as zero. Returns (logits [V] f32 of the last valid token,
    k_pages, v_pages, S, z); the pools are the engine's empty ones."""
    del page_table
    pos = start + jnp.arange(ids.shape[0])
    stack = _stack(params)

    def layer(i, c):
        h, s, z = c
        p = _at(stack, i)
        q, k, v, lg = _mixer_inputs(h, p, pos, cfg)
        y, s, z = retention.retention_chunk(
            s, z, lg, q, k, v, slot, start == 0, valid, layer=i,
            eps=cfg.retention_eps)
        return _mixer_out(h, y, p, cfg), s, z

    h, s, z = jax.lax.fori_loop(0, cfg.num_layers, layer,
                                (params["embed"][ids], *state))
    last = h[jnp.clip(valid - 1, 0, h.shape[0] - 1)]
    return _logits(params, last, cfg), k_pages, v_pages, s, z


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 state, slot):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, state=state,
                              slot=slot)


# ------------------------------------------------------------------- model

class BrumbyForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays. ``engine_family`` tells `DecodeEngine` how to run
    it (inference/family.py)."""

    def __init__(self, cfg: BrumbyConfig, params: dict):
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
            name="brumby", steps=sys.modules[__name__],
            params=lambda m: dict(m.params), table_key="embed",
            kv_layers=0, kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            max_positions=cfg.max_position_embeddings,
            state=lambda slots, page, dtype: state_arrays(cfg, slots, page,
                                                          dtype))
