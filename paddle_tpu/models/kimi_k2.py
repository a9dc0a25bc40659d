"""Kimi K2 (``kimi_k2``; DeepSeek-V3's block at its own sizes) for the
serving engine: latent (MLA) attention in EVERY layer over a paged latent
pool and NOTHING beside it, one leading dense layer, then sigmoid-routed
experts beside a shared one, as ONE CHIP'S SHARE of an expert-parallel
deployment.

Layer ``i`` (``N`` an RMSNorm with a scale ``w``)::

    a = N1(x)
    cq = Nq(a W_dq);  [q_nope | q_rope]_j = cq W_uq
    [ckv | k_rope] = a W_dkv;  ckv = Nkv(ckv)
    q_rope, k_rope rotated in INTERLEAVED pairs at YaRN's frequencies; the
        page row of a token is [ckv | k_rope | 0...]
    causal softmax over ALL earlier tokens, scale (dn + dr)^-1/2 * m^2, m =
        0.1 mscale_all_dim ln(factor) + 1; decode in the absorbed form
        over pages read whole (`mla.latent_decode_paged`), a chunk per
        head (`mla.latent_prefill`)
    x += concat_j(att_j) W_o                     (no gate, no post-norm)
    b = N2(x)
    i < first_dense:  x += W_2 (silu(b W_g) * b W_u)
    else:             sc = sigmoid(b W_r) float32 over ALL
                      ``n_routed_experts``; the ``experts_per_token``
                      largest of sc + bias; weights sc / sum of the chosen
                      * routed_scaling_factor; this chip adds the terms of
                      the experts ``experts_held = (lo, hi)`` it holds
                      (kernels/moe.py) and the shared expert's
    logits = Nf(x) head^T                        (untied head)

The equations, and what the published config leaves open, are the
benchmark's plain reference's (``benchmarks/reference/kimi_k2.py``), which
the CPU tests hold this file to. Like the other families this file is PURE
step functions over one flat dict of arrays, named by layer: ``embed``,
``head``, ``norm_f.w``, ``L<i>.n.{1,2}`` (the layer's two norms),
``L<i>.a.*`` (its latent attention), ``L<i>.f.*`` (its MLP, or its router,
HELD experts and shared expert). The stack is unrolled, every layer's
leaves arrays of their own (`models/dots3note.py` says why). The latent
projections' absorbed and expanded forms, the page row and the rotation
are `models/gigachat35.py`'s, whose full layers have these widths.

A sequence keeps ONE thing: its latent rows, ``k_pages`` ``[layers, P,
page, latent_width]``; ``v_pages`` is empty (``page_rows`` of ONE part) and
there is NO state beside the pool, so pages alone restore a sequence and
the engine's prefix store serves this family (docs/SERVING.md "The model
seam").

Counts: each step adds to an int32 vector (inference/family.py
``step_counts``): one entry a held expert, all routing assignments, then
the (query, key) pairs attended in decode steps and in chunks (summed over
the layers) and the held experts a decode step's and a chunk's live tokens
HIT. `count_step` turns what reaches the host into ``engine.moe.*`` and
``engine.latent.pairs.*`` counters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import mla, moe, retention
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import gigachat35 as _giga
from paddle_tpu.models.granitemoehybrid import (_rms, count_routing,
                                                expert_totals)  # noqa: F401
from paddle_tpu.observability import metrics

__all__ = ["KimiK2Config", "KimiK2ForCausalLM", "decode_step",
           "prefill_step", "prefill_chunk_step", "leaf_shapes",
           "init_params", "tiny_config", "count_step", "family",
           "expert_totals"]

LANES = _giga.LANES


@dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    num_layers: int = 61
    first_dense: int = 1                      # first_k_dense_replace
    intermediate_size: int = 18432            # the dense MLP's width
    moe_intermediate_size: int = 2048         # one expert's width
    n_routed_experts: int = 384               # the router's outputs
    experts_per_token: int = 8
    experts_held: tuple = (0, 384)            # [lo, hi) on this chip
    routed_scaling_factor: float = 2.827
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 5e4
    rope_factor: float = 64.0                 # rope_scaling (YaRN)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max: int = 4096
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts}")
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError(f"first_dense {self.first_dense} of "
                             f"{self.num_layers} layers")

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_width(self):
        """A page row: ``[ckv | k_rope]`` and zeros up to a whole number of
        the chip's `LANES` (`models/dots3note.py`)."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // LANES) * LANES

    @property
    def attn_scale(self):
        m = retention.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)

    @property
    def inv_freq(self):
        return retention.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_beta_fast, self.rope_beta_slow, self.rope_original_max)


def tiny_config(**over):
    """The CPU tests' preset: the dense layer and two expert layers, every
    width ratio kept: 4 heads of 8 + 4 over ranks 16 / 8, 32 router outputs
    of which 4 held, 2 a token."""
    kw = dict(vocab_size=96, hidden_size=64, num_layers=3, first_dense=1,
              intermediate_size=96, moe_intermediate_size=16,
              n_routed_experts=32, experts_per_token=2, experts_held=(0, 4),
              num_heads=4, q_lora_rank=16, kv_lora_rank=8,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
              rope_theta=1e3, rope_original_max=16,
              max_position_embeddings=4096)
    kw.update(over)
    return KimiK2Config(**kw)


def leaf_shapes(cfg: KimiK2Config) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d = cfg.hidden_size
    h, qr, r = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    out = {"embed": (cfg.vocab_size, d), "head": (cfg.vocab_size, d),
           "norm_f.w": (d,)}
    for i in range(cfg.num_layers):
        out.update({f"L{i}.n.1": (d,), f"L{i}.n.2": (d,)})
        out.update({f"L{i}.a.{k}": v for k, v in {
            "dq": (d, qr), "q_norm.w": (qr,), "uq": (qr, h * (dn + dr)),
            "dkv": (d, r + dr), "kv_norm.w": (r,),
            "ukv": (r, h * (dn + dv)), "o": (h * dv, d)}.items()})
        if i < cfg.first_dense:
            f = cfg.intermediate_size
            ffn = {"w1": (d, 2 * f), "w2": (f, d)}
        else:
            f = cfg.moe_intermediate_size
            ffn = {"router": (d, cfg.n_routed_experts),
                   "bias": (cfg.n_routed_experts,),
                   "w1": (cfg.n_held, d, 2 * f), "w2": (cfg.n_held, f, d),
                   "shared.w1": (d, 2 * f), "shared.w2": (f, d)}
        out.update({f"L{i}.f.{k}": v for k, v in ffn.items()})
    return out


def init_params(cfg: KimiK2Config, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std) (at the
    tiny preset's widths a larger ``std`` makes the mechanisms bite), the
    norms' scales 1 + N(0, std), the router's bias N(0, 0.005)."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        w = jax.random.normal(jax.random.fold_in(key, i), shape)
        if name.endswith(".bias"):
            w = 0.005 * w
        elif len(shape) == 1:
            w = 1.0 + std * w
        else:
            w = std * w
        out[name] = w.astype(dtype)
    return out


# the vector a step adds to: one entry a held expert and all routing
# assignments (`kernels/moe.py`'s), then these
_PAIRS_DECODE, _PAIRS_PREFILL, _HIT_DECODE, _HIT_PREFILL = range(4)
_COUNTERS = ("engine.latent.pairs.decode", "engine.latent.pairs.prefill",
             "engine.moe.experts_hit.decode", "engine.moe.experts_hit.prefill")


def step_counts(cfg: KimiK2Config) -> int:
    """Entries of the vector a step adds to."""
    return cfg.n_held + 1 + len(_COUNTERS)


def count_step(cfg: KimiK2Config, grown: np.ndarray):
    """What the counts vector grew by between two readbacks: the routing
    counts as the other families with held experts keep them
    (`count_routing`), and the `_COUNTERS`: (query, key) pairs attended
    (summed over the layers; dead slots and padding not counted) and held
    experts hit (summed over the expert layers), each by the decode steps
    and by the chunks."""
    n = cfg.n_held + 1
    count_routing(cfg, grown[:n])
    for name, add in zip(_COUNTERS, grown[n:]):
        metrics.counter(name).inc(int(add))


# ------------------------------------------------------------------ layers

def _norm(x, w, cfg):
    return _rms(x, w, cfg.rms_norm_eps)


def _gated_mlp(b, w1, w2):
    u, v = jnp.split(b @ w1, 2, axis=-1)
    return (_giga._silu(u) * v) @ w2


def _ffn(b, p, valid, counts, cfg, dense, hit_at):
    """The layer's second half on its normed input: the dense MLP, or this
    chip's routed experts and the shared one. The held experts that got a
    row of a ``valid`` token are added to the counts' entry ``hit_at``."""
    if dense:
        return _gated_mlp(b, p["w1"], p["w2"]), counts
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


def _latent_qkv(a, p, pos, cfg):
    """The latent projections for ``N`` tokens ``a`` [N, d] (the normed
    input) at ``pos``: (q_nope [N, H, dn], q_rope [N, H, dr] rotated, row
    [N, rank + dr] = [ckv | rotated k_rope])."""
    n = a.shape[0]
    h, r, dn = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    cq = _norm(a @ p["dq"], p["q_norm.w"], cfg)
    q = (cq @ p["uq"]).reshape(n, h, dn + cfg.qk_rope_head_dim)
    q_rope = _giga._rot(q[..., dn:], pos, cfg).astype(a.dtype)
    kv = a @ p["dkv"]
    ckv = _norm(kv[:, :r], p["kv_norm.w"], cfg)
    kr = _giga._rot(kv[:, None, r:], pos, cfg)[:, 0].astype(a.dtype)
    return q[..., :dn], q_rope, jnp.concatenate([ckv, kr], -1)


def _attn_out(o, p, dtype):
    o = o.astype(dtype)
    return o.reshape(o.shape[0], -1) @ p["o"]


def _logits(params, h, cfg):
    h = _norm(h, params["norm_f.w"], cfg)
    return jnp.dot(h, params["head"].T, preferred_element_type=jnp.float32)


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token.

    ids : [B] int32; cache : ``k_pages`` [layers, P, page, latent_width]
    the latent rows, ``v_pages`` the engine's empty one (passed through),
    ``page_table`` [B, pages], ``lengths`` [B], ``counts`` (optional);
    slot_mask : [B] bool: an inactive slot writes to the trash page and is
    not counted. Returns (logits [B, V] f32, new cache)."""
    table, pos = cache["page_table"], cache["lengths"]
    lat = cache["k_pages"]
    counts = cache.get("counts")
    if counts is None:
        counts = jnp.zeros(step_counts(cfg), jnp.int32)
    qpos = jnp.where(slot_mask, pos, -1)
    page, off = pa.token_page_coords(table, pos, slot_mask, lat.shape[2])
    h = params["embed"][ids]
    for i in range(cfg.num_layers):
        p = _giga._sub(params, f"L{i}.a.")
        a = _norm(h, params[f"L{i}.n.1"], cfg)
        q_nope, q_rope, row = _latent_qkv(a, p, pos, cfg)
        lat = lat.at[i, page, off].set(
            _giga._page_row(row, lat.shape[3]).astype(lat.dtype))
        q = _giga._absorb(q_nope, q_rope, p, cfg, lat.shape[3])
        with jax.named_scope("mla_decode"):
            o_lat = mla.latent_decode_paged(
                q, lat, i, table, qpos, rank=cfg.kv_lora_rank,
                scale=cfg.attn_scale)
        h = h + _attn_out(_giga._expand(o_lat, p, cfg), p, h.dtype)
        counts = _giga._add(counts, cfg, _PAIRS_DECODE, jnp.sum(qpos + 1))
        y, counts = _ffn(_norm(h, params[f"L{i}.n.2"], cfg),
                         _giga._sub(params, f"L{i}.f."), slot_mask, counts,
                         cfg, i < cfg.first_dense, _HIT_DECODE)
        h = h + y
    new_cache = dict(cache, k_pages=lat,
                     lengths=jnp.where(slot_mask, pos + 1, pos),
                     counts=counts)
    return _logits(params, h, cfg), new_cache


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, counts=None):
    """One chunk of ONE slot's prompt: ``ids`` [C] padded, ``start`` its
    first token's position, ``valid`` its true token count, ``page_table``
    the slot's page row. The rows before ``start`` are whatever the pages
    hold: this sequence's earlier chunks, or a prefix another sequence
    wrote (the engine's prefix store). Returns (logits [V] f32 of the last
    valid token, k_pages, v_pages) and, when ``counts`` came, the counts
    vector after them."""
    lat = k_pages
    t = ids.shape[0]
    i_tok = jnp.arange(t)
    live = i_tok < valid
    pos = start + i_tok
    qpos = jnp.where(live, pos, -1)
    tally = jnp.zeros(step_counts(cfg), jnp.int32) if counts is None \
        else counts
    page, off = pa.chunk_page_coords(page_table, start, valid, t,
                                     lat.shape[2])
    h = params["embed"][ids]
    for i in range(cfg.num_layers):
        p = _giga._sub(params, f"L{i}.a.")
        a = _norm(h, params[f"L{i}.n.1"], cfg)
        q_nope, q_rope, row = _latent_qkv(a, p, pos, cfg)
        lat = lat.at[i, page, off].set(
            _giga._page_row(row, lat.shape[3]).astype(lat.dtype))
        with jax.named_scope("mla_chunk"):
            o, pairs = mla.latent_prefill(
                q_nope, q_rope, lat, i, page_table, qpos, p["ukv"],
                rank=cfg.kv_lora_rank, rope=cfg.qk_rope_head_dim,
                dv=cfg.v_head_dim, scale=cfg.attn_scale)
        h = h + _attn_out(o, p, h.dtype)
        tally = _giga._add(tally, cfg, _PAIRS_PREFILL, pairs)
        y, tally = _ffn(_norm(h, params[f"L{i}.n.2"], cfg),
                        _giga._sub(params, f"L{i}.f."), live, tally, cfg,
                        i < cfg.first_dense, _HIT_PREFILL)
        h = h + y
    last = h[jnp.clip(valid - 1, 0, t - 1)]
    out = (_logits(params, last, cfg), lat, v_pages)
    return out if counts is None else (*out, tally)


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 counts=None):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, counts=counts)


# ------------------------------------------------------------------- model

class KimiK2ForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays. ``engine_family`` tells `DecodeEngine` how to run
    it (inference/family.py)."""

    def __init__(self, cfg: KimiK2Config, params: dict):
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


def family(cfg: KimiK2Config):
    """What `DecodeEngine` takes from this family (inference/family.py):
    every layer owns rows of the pool, a page row is ONE latent row (no
    second part: ``v_pages`` is empty), and there is no state beside it."""
    import sys
    from paddle_tpu.inference.family import ModelFamily
    return ModelFamily(
        name="kimi_k2", steps=sys.modules[__name__],
        params=lambda m: dict(m.params), table_key="embed",
        kv_layers=cfg.num_layers, kv_heads=1, head_dim=cfg.latent_width,
        max_positions=cfg.max_position_embeddings,
        step_counts=step_counts(cfg),
        on_counts=lambda grown: count_step(cfg, grown),
        page_rows=(("latent", cfg.latent_width),))
