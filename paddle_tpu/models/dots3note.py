"""dots3-note (``dots3_note``) for the serving engine: latent (MLA)
attention in two kinds of layer, a learned indexer that picks the keys a
full layer attends, headwise gates, and sigmoid-routed experts beside a
shared one, as ONE CHIP'S SHARE of an expert-parallel deployment.

Layer ``i`` (``layer_types[i]`` is ``full_attention`` or
``sliding_attention``; the first ``first_dense`` layers have a dense MLP,
the others routed experts)::

    x += Attn_i(RMSNorm(x));  x += FFN_i(RMSNorm(x))
    logits = RMSNorm(x) head^T                  (untied head)

    full     cq = RMSNorm(h W_dq) * sq;  [q_nope | q_rope]_j = cq W_uq
             [ckv | k_rope] = h W_dkv;  ckv = RMSNorm(ckv) * skv
             q_rope, k_rope rotated at the token's position (rope_theta);
             the page row of a token is [ckv | k_rope]
             indexer: qI_j = cq W_qI, kI = LayerNorm(h W_kI), the first
             ``index_rope_dim`` of each rotated, w = h W_w / sqrt(HI * DI);
             I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]); the
             ``index_topk`` best s <= t are attended (all, while there are
             no more); kI is the token's row of the second pool
             attention over the chosen rows, scale 1 / sqrt(dn + dr), the
             absorbed form (kernels/mla.py), or per head while a chunk
             attends everything; o_j *= sigmoid(h W_g)_j; W_o
    sliding  the same at the ``swa_*`` sizes without an indexer, over the
             last ``sliding_window`` positions (the token counts), rows in a
             ring per slot
    experts  sc = sigmoid(h W_r) float32 over ALL ``n_routed_experts``; the
             ``experts_per_token`` largest of sc + b; weights sc / sum of
             the chosen * routed_scaling_factor; this chip adds the terms
             of the experts ``experts_held = (lo, hi)`` it holds
             (kernels/moe.py) and the shared expert's
    sq, skv  sqrt(hidden / rank) with ``lora_rescale``, else 1

The equations, and what the published config leaves open, are the
benchmark's plain reference's (``benchmarks/reference/dots3note.py``),
which the CPU tests hold this file to. Like the other hybrids this file is
PURE step functions over one flat dict of arrays, named by layer:
``embed``, ``head``, ``norm_f.w``, ``L<i>.a.*`` (the layer's attention),
``L<i>.f.*`` (its MLP or its router, HELD experts and shared expert). The
stack is unrolled: every layer's leaves are arrays of their own, which is
what `kernels/moe.py`'s grouped arm asks for and what keeps XLA from
copying a layer out of a stack.

Per-sequence state (docs/SERVING.md "Three kinds of state"):

- paged, growing: ``k_pages`` ``[n_full, P, page, latent_width]``, a
  token's latent row ``[ckv | k_rope | 0...]``, and ``v_pages`` ``[n_full, P, page,
  index_head_dim]``, its index key (``page_rows`` of inference/family.py:
  a row that is not K and V, and no twin);
- window: ``win.<i>`` ``[slots, R, swa_kv_lora_rank + rope]``, one ring a
  sliding layer, ``R`` the window rounded up to whole pages plus one.

Counts: each step adds to an int32 vector (inference/family.py
``step_counts``): one entry a held expert, all routing assignments, the
keys in sight of the full layers' queries (what the indexer chooses from),
the keys they attended and how many of those a decode step attended, and
the held experts that a decode step's and a chunk's live tokens HIT (one
for each layer and call in which an expert got a row). `count_step` turns
what reaches the host into ``engine.moe.*`` and ``engine.sparse.*``
counters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import mla, moe, retention
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models.granitemoehybrid import (count_routing,  # noqa: F401
                                                expert_totals)
from paddle_tpu.observability import metrics

__all__ = ["Dots3NoteConfig", "Dots3NoteForCausalLM", "decode_step",
           "prefill_step", "prefill_chunk_step", "leaf_shapes",
           "init_params", "state_arrays", "tiny_config", "count_step",
           "ring_rows", "family", "expert_totals"]

PERIOD = ("full_attention",) + ("sliding_attention",) * 3
LANES = 128          # a TPU tile's minor dimension: a page row's width is a
#                      whole number of them (`Dots3NoteConfig.latent_width`)


@dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    layer_types: tuple = ("full_attention",) + PERIOD * 11 \
        + ("full_attention",)
    first_dense: int = 1                      # first_k_dense_replace
    intermediate_size: int = 13824            # the dense MLP's width
    moe_intermediate_size: int = 1536         # one expert's width
    n_routed_experts: int = 256               # the router's outputs
    experts_per_token: int = 8
    experts_held: tuple = (0, 256)            # [lo, hi) on this chip
    routed_scaling_factor: float = 1.0
    num_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_rope_dim: int = 64                  # not in config.json
    swa_num_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window: int = 513
    lora_rescale: bool = True                 # apply_mla_qkv_lora_rescale
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.layer_types) - set(PERIOD)
        if bad:
            raise ValueError(f"layer_types: unknown kinds {sorted(bad)}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts}")
        if self.index_rope_dim > self.index_head_dim:
            raise ValueError("index_rope_dim exceeds index_head_dim")

    @property
    def n_layers(self):
        return len(self.layer_types)

    @property
    def full_layers(self):
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "full_attention")

    @property
    def sliding_layers(self):
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == "sliding_attention")

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_width(self):
        """A full layer's page row: ``[ckv | k_rope]`` and zeros up to a
        whole number of the chip's `LANES`. The chip stores a bfloat16
        array whose rows are 576 wide with the PAGES as its minor dimension
        (a 640-lane row would waste 10%), and every step then copies the
        pool into rows and back (PERF.md section 6, PR 40: 2 x 3 ms a
        program); 640 declared is 640 stored, rows as rows."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // LANES) * LANES

    @property
    def swa_latent_width(self):               # a sliding layer's ring row
        return self.swa_kv_lora_rank + self.swa_qk_rope_head_dim

    def attn(self, i) -> "_Attn":
        """Layer ``i``'s attention sizes."""
        d = self.hidden_size
        if self.layer_types[i] == "full_attention":
            return _Attn(self.num_heads, self.q_lora_rank, self.kv_lora_rank,
                         self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim, self.rope_theta, d,
                         self.lora_rescale)
        return _Attn(self.swa_num_heads, self.swa_q_lora_rank,
                     self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                     self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                     self.swa_rope_theta, d, self.lora_rescale)


@dataclass(frozen=True)
class _Attn:
    heads: int
    q_rank: int
    rank: int
    dn: int
    dr: int
    dv: int
    theta: float
    hidden: int
    rescale: bool

    @property
    def sq(self):
        return math.sqrt(self.hidden / self.q_rank) if self.rescale else 1.0

    @property
    def skv(self):
        return math.sqrt(self.hidden / self.rank) if self.rescale else 1.0

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.dn + self.dr)


def tiny_config(**over):
    """The CPU tests' preset: a dense first layer and one period, every
    mechanism present and biting at sequences of some 40 tokens."""
    kw = dict(vocab_size=96, hidden_size=64,
              layer_types=("full_attention",) + PERIOD, first_dense=1,
              intermediate_size=96, moe_intermediate_size=16,
              n_routed_experts=8, experts_per_token=2, experts_held=(0, 4),
              num_heads=4, q_lora_rank=16, kv_lora_rank=8,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
              rope_theta=1e4, index_n_heads=2, index_head_dim=8,
              index_topk=8, index_rope_dim=4, swa_num_heads=2,
              swa_q_lora_rank=16, swa_kv_lora_rank=16,
              swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
              swa_v_head_dim=8, swa_rope_theta=1e3, sliding_window=5,
              max_position_embeddings=4096)
    kw.update(over)
    return Dots3NoteConfig(**kw)


def leaf_shapes(cfg: Dots3NoteConfig) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d = cfg.hidden_size
    out = {"embed": (cfg.vocab_size, d), "head": (cfg.vocab_size, d),
           "norm_f.w": (d,)}
    for i, kind in enumerate(cfg.layer_types):
        a = cfg.attn(i)
        leaves = {"norm.w": (d,), "dq": (d, a.q_rank), "q_norm.w": (a.q_rank,),
                  "uq": (a.q_rank, a.heads * (a.dn + a.dr)),
                  "dkv": (d, a.rank + a.dr), "kv_norm.w": (a.rank,),
                  "ukv": (a.rank, a.heads * (a.dn + a.dv)),
                  "gate": (d, a.heads), "o": (a.heads * a.dv, d)}
        if kind == "full_attention":
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            leaves.update({"iq": (a.q_rank, hi * di), "ik": (d, di),
                           "ik_norm.w": (di,), "ik_norm.b": (di,),
                           "iw": (d, hi)})
        out.update({f"L{i}.a.{k}": v for k, v in leaves.items()})
        if i < cfg.first_dense:
            f = cfg.intermediate_size
            ffn = {"norm.w": (d,), "w1": (d, 2 * f), "w2": (f, d)}
        else:
            f = cfg.moe_intermediate_size
            ffn = {"norm.w": (d,), "router": (d, cfg.n_routed_experts),
                   "bias": (cfg.n_routed_experts,),
                   "w1": (cfg.n_held, d, 2 * f), "w2": (cfg.n_held, f, d),
                   "shared.w1": (d, 2 * f), "shared.w2": (f, d)}
        out.update({f"L{i}.f.{k}": v for k, v in ffn.items()})
    return out


def init_params(cfg: Dots3NoteConfig, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std) (at the
    tiny preset's widths a larger ``std`` makes the mechanisms bite), norm
    scales 1 + N(0, std), the router's bias N(0, 0.05)."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(".bias"):
            w = 0.05 * jax.random.normal(k, shape)
        else:
            w = std * jax.random.normal(k, shape)
            if name.endswith("norm.w") or name == "norm_f.w":
                w = 1.0 + w
        out[name] = w.astype(dtype)
    return out


def ring_rows(cfg: Dots3NoteConfig, page_size: int) -> int:
    """Rows of a sliding layer's ring: the window in whole pages, and one
    more page."""
    return (-(-cfg.sliding_window // page_size) + 1) * page_size


def state_arrays(cfg: Dots3NoteConfig, slots: int, page_size: int, dtype):
    """The per-slot state beside the page pools, as ``(name, kind, shape,
    dtype)`` in the order the step functions take and return it: one ring a
    sliding layer."""
    r = ring_rows(cfg, page_size)
    return tuple((f"win.{i}", "window", (slots, r, cfg.swa_latent_width),
                  dtype) for i in cfg.sliding_layers)


# the vector a step adds to: one entry a held expert and all routing
# assignments (`kernels/moe.py`'s), then these
_SCORED, _ATTENDED, _ATTENDED_DECODE, _HIT_DECODE, _HIT_PREFILL = range(5)
_COUNTERS = ("engine.sparse.keys_scored", "engine.sparse.keys_attended",
             "engine.sparse.keys_attended.decode",
             "engine.moe.experts_hit.decode", "engine.moe.experts_hit.prefill")


def step_counts(cfg: Dots3NoteConfig) -> int:
    """Entries of the vector a step adds to."""
    return cfg.n_held + 1 + len(_COUNTERS)


# ------------------------------------------------------ counts on the host

def count_step(cfg: Dots3NoteConfig, grown: np.ndarray):
    """What the counts vector grew by between two readbacks: the routing
    counts into ``engine.moe.assignments`` / ``engine.moe.assignments_held``
    and the totals a held expert (`expert_totals`), as the other family
    with held experts does, and the `_COUNTERS`: keys scored and attended
    (summed over the full layers and a step's live queries; ``.decode``:
    the decode steps' part), and the held experts hit, summed over the
    expert layers, by the decode steps' and by the chunks' live tokens."""
    n = cfg.n_held + 1
    count_routing(cfg, grown[:n])
    for name, add in zip(_COUNTERS, grown[n:]):
        metrics.counter(name).inc(int(add))


# ------------------------------------------------------------------ layers

def _rms(x, w, eps, scale=1.0):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32) * scale).astype(x.dtype)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _sub(params, prefix):
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def _rot(x, pos, theta):
    """Rotary over the last axis of ``x`` [N, heads, hd] at ``pos`` [N]."""
    return retention.rotary(x, pos, theta)


def _qkv(a, p, pos, at: _Attn, eps):
    """A layer's latent projections for ``N`` tokens ``a`` [N, d] (the
    normed input) at ``pos``: (cq [N, q_rank], q_nope [N, H, dn], q_rope
    [N, H, dr] rotated, row [N, rank + dr] = [ckv | rotated k_rope], gate
    [N, H] float32)."""
    n = a.shape[0]
    cq = _rms(a @ p["dq"], p["q_norm.w"], eps, at.sq)
    q = (cq @ p["uq"]).reshape(n, at.heads, at.dn + at.dr)
    q_rope = _rot(q[..., at.dn:], pos, at.theta).astype(a.dtype)
    kv = a @ p["dkv"]
    ckv = _rms(kv[:, :at.rank], p["kv_norm.w"], eps, at.skv)
    kr = _rot(kv[:, None, at.rank:], pos, at.theta)[:, 0].astype(a.dtype)
    gate = jax.nn.sigmoid(jnp.dot(a, p["gate"],
                                  preferred_element_type=jnp.float32))
    return cq, q[..., :at.dn], q_rope, jnp.concatenate([ckv, kr], -1), gate


def _page_row(row, width):
    """``[ckv | k_rope]`` with zeros up to the pool's row width."""
    return jnp.pad(row, ((0, 0), (0, width - row.shape[-1])))


def _index_inputs(a, cq, p, pos, cfg):
    """(qI [N, HI, DI], kI [N, DI], w [N, HI] float32) of the indexer."""
    n = a.shape[0]
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.index_rope_dim

    def rot(x):                                   # [N, heads, DI]
        return jnp.concatenate(
            [_rot(x[..., :dr], pos, cfg.rope_theta),
             x[..., dr:].astype(jnp.float32)], axis=-1).astype(a.dtype)

    qi = rot((cq @ p["iq"]).reshape(n, hi, di))
    ki = rot(_layer_norm(a @ p["ik"], p["ik_norm.w"], p["ik_norm.b"],
                         cfg.rms_norm_eps)[:, None])[:, 0]
    w = jnp.dot(a, p["iw"], preferred_element_type=jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return qi, ki, w


def _absorb(q_nope, q_rope, p, at: _Attn, width=None):
    """Queries carried into the latent: [N, H, rank + dr], and zeros up to
    ``width`` (a page row's)."""
    w_uk = p["ukv"].reshape(at.rank, at.heads, at.dn + at.dv)[..., :at.dn]
    q_abs = jnp.einsum("nhd,chd->nhc", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_abs.astype(q_nope.dtype), q_rope], axis=-1)
    return q if width is None else jnp.pad(
        q, ((0, 0), (0, 0), (0, width - q.shape[-1])))


def _expand(o_lat, p, at: _Attn):
    """A mix of latent rows through each head's ``W_uv``: [N, H, dv]."""
    w_uv = p["ukv"].reshape(at.rank, at.heads, at.dn + at.dv)[..., at.dn:]
    return jnp.einsum("nhc,chv->nhv", o_lat, w_uv,
                      preferred_element_type=jnp.float32)


def _attn_out(h, o, gate, p):
    """Gate the heads' outputs, project, add."""
    o = (o.astype(jnp.float32) * gate[..., None]).astype(h.dtype)
    return h + o.reshape(o.shape[0], -1) @ p["o"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gated_mlp(b, w1, w2):
    u, v = jnp.split(b @ w1, 2, axis=-1)
    return (_silu(u) * v) @ w2


def _ffn(h, p, valid, counts, cfg, dense, hit_at):
    """The layer's second half: the dense MLP, or this chip's routed
    experts and the shared one. The held experts that got a row of a
    ``valid`` token are added to the counts' entry ``hit_at``."""
    b = _rms(h, p["norm.w"], cfg.rms_norm_eps)
    if dense:
        return h + _gated_mlp(b, p["w1"], p["w2"]), counts
    n = cfg.n_held + 1
    with jax.named_scope("moe"):
        with jax.named_scope("moe_experts"):
            routed, tally = moe.routed_experts(
                b, p["router"], p["w1"], p["w2"], top_k=cfg.experts_per_token,
                held=cfg.experts_held, counts=counts[:n], valid=valid,
                scoring="sigmoid", bias=p["bias"],
                scale=cfg.routed_scaling_factor)
        shared = _gated_mlp(b, p["shared.w1"], p["shared.w2"])
    hit = jnp.sum(tally[:n - 1] > counts[:n - 1], dtype=counts.dtype)
    return h + routed + shared, jnp.concatenate(
        [tally, counts[n:].at[hit_at].add(hit)])


def _count_keys(counts, cfg, sight, attended, decode=False):
    n = cfg.n_held + 1
    attended = jnp.sum(attended).astype(counts.dtype)
    counts = counts.at[n + _SCORED].add(jnp.sum(sight).astype(counts.dtype))
    counts = counts.at[n + _ATTENDED].add(attended)
    return counts.at[n + _ATTENDED_DECODE].add(attended) if decode \
        else counts


def _logits(params, h, cfg):
    h = _rms(h, params["norm_f.w"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"].T, preferred_element_type=jnp.float32)


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token.

    ids : [B] int32; cache : ``k_pages`` [n_full, P, page, rank + dr] the
    latent rows, ``v_pages`` [n_full, P, page, DI] the index keys,
    ``page_table`` [B, pages], ``lengths`` [B], ``state`` = the sliding
    layers' rings, ``counts`` (optional); slot_mask : [B] bool — an
    inactive slot writes to the trash page, leaves its rings alone and is
    not counted. Returns (logits [B, V] f32, new cache)."""
    table, pos = cache["page_table"], cache["lengths"]
    lat, kix = cache["k_pages"], cache["v_pages"]
    rings = list(cache["state"])
    counts = cache.get("counts")
    if counts is None:
        counts = jnp.zeros(step_counts(cfg), jnp.int32)
    eps, topk = cfg.rms_norm_eps, cfg.index_topk
    h = params["embed"][ids]
    n_full = n_swa = 0
    for i, kind in enumerate(cfg.layer_types):
        p, at = _sub(params, f"L{i}.a."), cfg.attn(i)
        a = _rms(h, p["norm.w"], eps)
        cq, q_nope, q_rope, row, gate = _qkv(a, p, pos, at, eps)
        if kind == "full_attention":
            k, n_full = n_full, n_full + 1
            q_lat = _absorb(q_nope, q_rope, p, at, lat.shape[3])
            with jax.named_scope("indexer"):
                qi, ki, w = _index_inputs(a, cq, p, pos, cfg)
            page, off = pa.token_page_coords(table, pos, slot_mask,
                                             lat.shape[2])
            lat = lat.at[k, page, off].set(
                _page_row(row, lat.shape[3]).astype(lat.dtype))
            kix = kix.at[k, page, off].set(ki.astype(kix.dtype))
            qpos = jnp.where(slot_mask, pos, -1)[:, None]
            with jax.named_scope("select"):
                sel, ok = mla.index_select(qi[:, None], w[:, None], kix, k,
                                           table, qpos, topk,
                                           block=mla.DECODE_SELECT_BLOCK)
            with jax.named_scope("mla"):
                o_lat = mla.latent_attention(
                    q_lat[:, None], lat, k, sel, ok, rank=at.rank,
                    scale=at.scale)[:, 0]
            counts = _count_keys(counts, cfg, qpos + 1, ok, decode=True)
        else:
            k, n_swa = n_swa, n_swa + 1
            with jax.named_scope("window_mla"):
                o_lat, rings[k] = mla.window_latent_decode(
                    _absorb(q_nope, q_rope, p, at), row, rings[k], pos,
                    slot_mask,
                    window=cfg.sliding_window, rank=at.rank, scale=at.scale)
        h = _attn_out(h, _expand(o_lat, p, at), gate, p)
        h, counts = _ffn(h, _sub(params, f"L{i}.f."), slot_mask, counts, cfg,
                         i < cfg.first_dense, _HIT_DECODE)
    new_cache = dict(k_pages=lat, v_pages=kix, page_table=table,
                     lengths=jnp.where(slot_mask, pos + 1, pos),
                     state=tuple(rings), counts=counts)
    return _logits(params, h, cfg), new_cache


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, state, slot, counts=None):
    """One chunk of ONE slot's prompt: ``ids`` [C] padded, ``start`` its
    first token's position, ``valid`` its true token count, ``page_table``
    the slot's page row, ``slot`` where its rings live. Returns (logits [V]
    f32 of the last valid token, k_pages, v_pages, *rings) and, when
    ``counts`` came, the counts vector after them.

    A full layer attends in the per-head form under a mask
    (`kernels/mla.py::latent_prefill`): the causal one while the whole chunk
    lies among the first ``index_topk`` positions (every key in sight is
    attended, so the indexer's choice is known and its scores are not
    made), the selection's from then on; either way the chunk's latent rows
    and index keys are written first. (Decode gathers each query's picked
    rows and attends them in the absorbed form.)"""
    lat, kix = k_pages, v_pages
    rings = list(state)
    t = ids.shape[0]
    i_tok = jnp.arange(t)
    live = i_tok < valid
    pos = start + i_tok
    qpos = jnp.where(live, pos, -1)
    tally = jnp.zeros(step_counts(cfg), jnp.int32) if counts is None \
        else counts
    eps, topk = cfg.rms_norm_eps, cfg.index_topk
    h = params["embed"][ids]
    n_full = n_swa = 0
    for i, kind in enumerate(cfg.layer_types):
        p, at = _sub(params, f"L{i}.a."), cfg.attn(i)
        a = _rms(h, p["norm.w"], eps)
        cq, q_nope, q_rope, row, gate = _qkv(a, p, pos, at, eps)
        if kind == "full_attention":
            k, n_full = n_full, n_full + 1
            with jax.named_scope("indexer"):
                qi, ki, w = _index_inputs(a, cq, p, pos, cfg)
            page, off = pa.chunk_page_coords(page_table, start, valid, t,
                                             lat.shape[2])
            lat = lat.at[k, page, off].set(
                _page_row(row, lat.shape[3]).astype(lat.dtype))
            kix = kix.at[k, page, off].set(ki.astype(kix.dtype))

            def attend(select, lat=lat, q_nope=q_nope, q_rope=q_rope, p=p,
                       at=at, k=k):
                with jax.named_scope("mla"):
                    return mla.latent_prefill(
                        q_nope, q_rope, lat, k, page_table, qpos, p["ukv"],
                        rank=at.rank, rope=at.dr, dv=at.dv, scale=at.scale,
                        select=select)

            def sparse(kix=kix, qi=qi, w=w, k=k, attend=attend):
                with jax.named_scope("select"):
                    select = mla.index_threshold(qi, w, kix, k, page_table,
                                                 qpos, topk)
                return attend(select)

            o, attended = jax.lax.cond(start + t <= topk,
                                       lambda: attend(None), sparse)
            tally = _count_keys(tally, cfg, qpos + 1, attended)
        else:
            k, n_swa = n_swa, n_swa + 1
            with jax.named_scope("window_mla"):
                o, rings[k] = mla.window_latent_prefill(
                    q_nope, q_rope, row, rings[k], slot, start, valid,
                    p["ukv"], window=cfg.sliding_window, rank=at.rank,
                    dv=at.dv, scale=at.scale)
        h = _attn_out(h, o, gate, p)
        h, tally = _ffn(h, _sub(params, f"L{i}.f."), live, tally, cfg,
                        i < cfg.first_dense, _HIT_PREFILL)
    last = h[jnp.clip(valid - 1, 0, t - 1)]
    out = (_logits(params, last, cfg), lat, kix, *rings)
    return out if counts is None else (*out, tally)


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 state, slot, counts=None):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, state=state,
                              slot=slot, counts=counts)


# ------------------------------------------------------------------- model

class Dots3NoteForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays. ``engine_family`` tells `DecodeEngine` how to run
    it (inference/family.py)."""

    def __init__(self, cfg: Dots3NoteConfig, params: dict):
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


def family(cfg: Dots3NoteConfig):
    """What `DecodeEngine` takes from this family (inference/family.py): a
    full layer's page row is ONE latent row and an index key, no K and V;
    the sliding layers' rings are ``window`` state."""
    import sys
    from paddle_tpu.inference.family import ModelFamily
    return ModelFamily(
        name="dots3_note", steps=sys.modules[__name__],
        params=lambda m: dict(m.params), table_key="embed",
        kv_layers=len(cfg.full_layers), kv_heads=1,
        head_dim=cfg.latent_width,
        max_positions=cfg.max_position_embeddings,
        state=lambda slots, page, dtype: state_arrays(cfg, slots, page,
                                                      dtype),
        window_tokens=cfg.sliding_window, step_counts=step_counts(cfg),
        on_counts=lambda grown: count_step(cfg, grown),
        page_rows=(("latent", cfg.latent_width),
                   ("index_key", cfg.index_head_dim)))
