"""GigaChat3.5 (``gigachat3_5``) for the serving engine: gated delta-rule
linear attention in three layers of four, latent (MLA) attention over the
WHOLE context in the fourth, sandwich norms, and clamped sigmoid-routed
experts beside a shared one, as ONE CHIP'S SHARE of an expert-parallel
deployment.

Layer ``i`` (``layer_types[i]`` is ``linear_attention`` or
``full_attention``; the first ``first_dense`` layers have a dense MLP, the
others routed experts)::

    N_w(x) = x / rms(x) * 2 sigmoid(w)          (zero-centred gated norm)
    x += N_post1(mixer_i(N_pre1(x)));  x += N_post2(ffn_i(N_pre2(x)))
    logits = N_f(x) head^T                      (untied head)

    linear   [q | k | v | z] = a W_qkvz;  [b | g] = a W_ba
             [q | k | v] = silu(conv4([q | k | v])) depthwise, causal, no
             bias, over the last 3 inputs carried per sequence;
             q = q / |q| / sqrt(dk), k = k / |k| per key head; key head j
             serves value heads j * r .. j * r + r - 1 (r = value heads /
             key heads); per value head the delta rule
             (kernels/deltanet.py) with beta = sigmoid(b), log decay
             -exp(A_log) softplus(g + dt_bias);
             y = (o / rms(o) * (1 + w_o)) * gate_scale * sigmoid(z) a head;
             W_out
    full     cq = N(a W_dq);  [q_nope | q_rope]_j = cq W_uq
             [ckv | k_rope] = a W_dkv;  ckv = N(ckv)
             q_rope, k_rope rotated in INTERLEAVED pairs at YaRN's
             frequencies; the page row of a token is [ckv | k_rope | 0...]
             causal softmax over ALL earlier tokens (no indexer), scale
             (dn + dr)^-1/2 * mscale^2; decode in the absorbed form over
             pages read whole (`mla.latent_decode_paged`), a chunk per head
             (`mla.latent_prefill`); y = (att * sigmoid(a W_g)) W_o, the
             gate elementwise
    experts  sc = sigmoid(b W_r) float32 over ALL ``n_routed_experts``; the
             ``experts_per_token`` largest of sc + bias; weights sc / sum of
             the chosen * routed_scaling_factor; this chip adds the terms
             of the experts ``experts_held = (lo, hi)`` it holds
             (kernels/moe.py) and the shared expert's
    mlp      W_2 (silu(min(b W_g, limit)) * clip(b W_u, -limit, limit)), the
             dense MLP, every expert and the shared expert alike

The equations, and what the published config leaves open, are the
benchmark's plain reference's (``benchmarks/reference/gigachat35.py``),
which the CPU tests hold this file to. Like the other hybrids this file is
PURE step functions over one flat dict of arrays, named by layer:
``embed``, ``head``, ``norm_f.w``, ``L<i>.n.*`` (the layer's four norms),
``L<i>.d.*`` (a linear layer's mixer) or ``L<i>.a.*`` (a full layer's),
``L<i>.f.*`` (its MLP or its router, HELD experts and shared expert). The
stack is unrolled, every layer's leaves arrays of their own
(`models/dots3note.py` says why).

Per-sequence state (docs/SERVING.md "Three kinds of state"), all of it in
ONE `DeviceCache`:

- paged, growing: ``k_pages`` ``[n_full, P, page, latent_width]``, a
  token's latent row; ``v_pages`` is empty (``page_rows`` of ONE part);
- recurrent: ``delta`` ``[n_linear, slots, value heads, dk, dv]`` float32,
  the delta rule's matrix state, a stack rewritten in place by layer, and
  ``conv.<i>`` ``[slots, 3 * conv_dim]`` float32, the convolution's last
  three inputs, ONE ARRAY A LINEAR LAYER (kernels/deltanet.py says why it
  is no stack); zero at a sequence's first chunk, carried across chunks
  and into decode.

Counts: each step adds to an int32 vector (inference/family.py
``step_counts``): one entry a held expert, all routing assignments, then
the (query, key) pairs the full layers attended in decode steps and in
chunks, the live tokens through a linear layer in decode steps and in
chunks, and the held experts a decode step's and a chunk's live tokens HIT.
`count_step` turns what reaches the host into ``engine.moe.*``,
``engine.latent.pairs.*`` and ``engine.deltanet.tokens.*`` counters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import deltanet, mla, moe, retention
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models.granitemoehybrid import (count_routing,  # noqa: F401
                                                expert_totals)
from paddle_tpu.observability import metrics

__all__ = ["GigaChat35Config", "GigaChat35ForCausalLM", "decode_step",
           "prefill_step", "prefill_chunk_step", "leaf_shapes",
           "init_params", "state_arrays", "tiny_config", "count_step",
           "family", "expert_totals"]

LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR,) * 3 + (FULL,)
LANES = 128          # a page row's width is a whole number of them


@dataclass(frozen=True)
class GigaChat35Config:
    vocab_size: int = 128256
    hidden_size: int = 7168
    layer_types: tuple = PERIOD * 10
    first_dense: int = 3                      # first_k_dense_replace
    intermediate_size: int = 18432            # the dense MLP's width
    moe_intermediate_size: int = 2048         # one expert's width
    n_routed_experts: int = 256               # the router's outputs
    experts_per_token: int = 8
    experts_held: tuple = (0, 256)            # [lo, hi) on this chip
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e5
    rope_factor: float = 8.0                  # rope_scaling (YaRN)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max: int = 32768
    rope_mscale_all_dim: float = 1.0
    linear_key_heads: int = 32
    linear_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    linear_gate_scale: float = 2.0            # linear_sigmoid_gate_scale
    linear_o_norm_eps: float = 1e-6
    norm_gate_scale: float = 2.0              # layernorm_gating_weight
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144

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
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError("linear_value_heads is no multiple of "
                             "linear_key_heads")

    @property
    def n_layers(self):
        return len(self.layer_types)

    @property
    def full_layers(self):
        return tuple(i for i, k in enumerate(self.layer_types) if k == FULL)

    @property
    def linear_layers(self):
        return tuple(i for i, k in enumerate(self.layer_types) if k == LINEAR)

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_width(self):
        """A full layer's page row: ``[ckv | k_rope]`` and zeros up to a
        whole number of the chip's `LANES` (`models/dots3note.py`)."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // LANES) * LANES

    @property
    def key_width(self):                      # all key heads of a linear layer
        return self.linear_key_heads * self.linear_key_head_dim

    @property
    def value_width(self):
        return self.linear_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):                       # [q | k | v]
        return 2 * self.key_width + self.value_width

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
    """The CPU tests' preset: one dense layer and one period (linear, full,
    linear, linear: the order the benchmark's cut keeps), every width ratio
    kept: two key heads to four value heads, 32 experts of which 4 held,
    2 a token, the clamp low enough to bite."""
    kw = dict(vocab_size=96, hidden_size=64, swiglu_limit=1.0,
              layer_types=(LINEAR, FULL, LINEAR, LINEAR, LINEAR),
              first_dense=1, intermediate_size=96, moe_intermediate_size=16,
              n_routed_experts=32, experts_per_token=2, experts_held=(0, 4),
              num_heads=4, q_lora_rank=16, kv_lora_rank=8,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
              rope_theta=1e3, rope_original_max=16, linear_key_heads=2,
              linear_value_heads=4, linear_key_head_dim=8,
              linear_value_head_dim=8, max_position_embeddings=4096)
    kw.update(over)
    return GigaChat35Config(**kw)


def leaf_shapes(cfg: GigaChat35Config) -> dict:
    """name -> shape of every parameter leaf (the reference's names)."""
    d = cfg.hidden_size
    out = {"embed": (cfg.vocab_size, d), "head": (cfg.vocab_size, d),
           "norm_f.w": (d,)}
    hv, dv = cfg.linear_value_heads, cfg.linear_value_head_dim
    for i, kind in enumerate(cfg.layer_types):
        out.update({f"L{i}.n.{k}": (d,)
                    for k in ("pre1", "post1", "pre2", "post2")})
        if kind == LINEAR:
            out.update({f"L{i}.d.{k}": v for k, v in {
                "qkvz": (d, cfg.conv_dim + cfg.value_width),
                "ba": (d, 2 * hv),
                "conv": (cfg.linear_conv_kernel, cfg.conv_dim),
                "A_log": (hv,), "dt_bias": (hv,), "o_norm.w": (dv,),
                "out": (cfg.value_width, d)}.items()})
        else:
            h, qr, r = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
            dn, dr, dvh = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
            out.update({f"L{i}.a.{k}": v for k, v in {
                "dq": (d, qr), "q_norm.w": (qr,), "uq": (qr, h * (dn + dr)),
                "dkv": (d, r + dr), "kv_norm.w": (r,),
                "ukv": (r, h * (dn + dvh)), "gate": (d, h * dvh),
                "o": (h * dvh, d)}.items()})
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


def init_params(cfg: GigaChat35Config, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> dict:
    """Seeded parameters for tests and examples: matrices N(0, std) (at the
    tiny preset's widths a larger ``std`` makes the mechanisms bite), the
    zero-centred norm weights N(0, std), the router's bias N(0, 0.005),
    ``A_log`` from ``A ~ U(0, 16)`` and ``dt_bias`` from ``dt`` log-uniform
    in [1e-3, 0.1] (the published initial ranges of this layer's family: a
    state then lives for tens to thousands of tokens)."""
    out = {}
    key = jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(".bias"):
            w = 0.005 * jax.random.normal(k, shape)
        elif name.endswith(".A_log"):
            w = jnp.log(jax.random.uniform(k, shape, minval=1e-3,
                                           maxval=16.0))
        elif name.endswith(".dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(1e-3), maxval=math.log(0.1)))
            w = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
        else:
            w = std * jax.random.normal(k, shape)
        out[name] = w.astype(dtype)
    return out


def state_arrays(cfg: GigaChat35Config, slots: int, page_size: int, dtype):
    """The per-slot state beside the page pool, as ``(name, kind, shape,
    dtype)`` in the order the step functions take and return it: the delta
    rule's matrix state, a stack over the linear layers, then the
    convolution's last inputs, an array a linear layer; all float32."""
    del page_size, dtype
    n = len(cfg.linear_layers)
    taps = (cfg.linear_conv_kernel - 1) * cfg.conv_dim
    return (("delta", "recurrent", deltanet.state_shape(
                n, slots, cfg.linear_value_heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim), jnp.float32),
            *((f"conv.{i}", "recurrent", (slots, taps), jnp.float32)
              for i in cfg.linear_layers))


# the vector a step adds to: one entry a held expert and all routing
# assignments (`kernels/moe.py`'s), then these
(_PAIRS_DECODE, _PAIRS_PREFILL, _DELTA_DECODE, _DELTA_PREFILL, _HIT_DECODE,
 _HIT_PREFILL) = range(6)
_COUNTERS = ("engine.latent.pairs.decode", "engine.latent.pairs.prefill",
             "engine.deltanet.tokens.decode",
             "engine.deltanet.tokens.prefill",
             "engine.moe.experts_hit.decode", "engine.moe.experts_hit.prefill")


def step_counts(cfg: GigaChat35Config) -> int:
    """Entries of the vector a step adds to."""
    return cfg.n_held + 1 + len(_COUNTERS)


def count_step(cfg: GigaChat35Config, grown: np.ndarray):
    """What the counts vector grew by between two readbacks: the routing
    counts as the other families with held experts keep them
    (`count_routing`), and the `_COUNTERS`: (query, key) pairs the full
    layers attended, live tokens through a linear layer (summed over the
    layers; dead slots and padding not counted) and held experts hit
    (summed over the expert layers), each by the decode steps and by the
    chunks."""
    n = cfg.n_held + 1
    count_routing(cfg, grown[:n])
    for name, add in zip(_COUNTERS, grown[n:]):
        metrics.counter(name).inc(int(add))


# ------------------------------------------------------------------ layers

def _norm(x, w, cfg):
    """The zero-centred gated norm: ``x / rms(x) * 2 sigmoid(w)``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + cfg.rms_norm_eps)
    return (y * (cfg.norm_gate_scale
                 * jax.nn.sigmoid(w.astype(jnp.float32)))).astype(x.dtype)


def _sub(params, prefix):
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gated_mlp(b, w1, w2, limit):
    u, v = jnp.split(b @ w1, 2, axis=-1)
    u, v = jnp.minimum(u, limit), jnp.clip(v, -limit, limit)
    return (_silu(u) * v) @ w2


def _ffn(b, p, valid, counts, cfg, dense, hit_at):
    """The layer's second half on its normed input: the dense MLP, or this
    chip's routed experts and the shared one. The held experts that got a
    row of a ``valid`` token are added to the counts' entry ``hit_at``."""
    limit = cfg.swiglu_limit
    if dense:
        return _gated_mlp(b, p["w1"], p["w2"], limit), counts
    n = cfg.n_held + 1
    with jax.named_scope("moe"):
        with jax.named_scope("moe_experts"):
            routed, tally = moe.routed_experts(
                b, p["router"], p["w1"], p["w2"], top_k=cfg.experts_per_token,
                held=cfg.experts_held, counts=counts[:n], valid=valid,
                scoring="sigmoid", bias=p["bias"],
                scale=cfg.routed_scaling_factor, limit=limit)
        shared = _gated_mlp(b, p["shared.w1"], p["shared.w2"], limit)
    hit = jnp.sum(tally[:n - 1] > counts[:n - 1], dtype=counts.dtype)
    return routed + shared, jnp.concatenate(
        [tally, counts[n:].at[hit_at].add(hit)])


def _delta_inputs(a, p, cfg):
    """(conv input [N, conv_dim], z [N, Hv, dv], beta [N, Hv] f32, log
    decay [N, Hv] f32) of a linear layer's projections."""
    n = a.shape[0]
    hv = cfg.linear_value_heads
    qkvz = a @ p["qkvz"]
    ba = jnp.dot(a, p["ba"], preferred_element_type=jnp.float32)
    log_g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(jnp.float32))
    z = qkvz[:, cfg.conv_dim:].reshape(n, hv, cfg.linear_value_head_dim)
    return qkvz[:, :cfg.conv_dim], z, jax.nn.sigmoid(ba[:, :hv]), log_g


def _delta_operands(xc, cfg):
    """The convolution's output (f32, before the activation) -> (q, k [N,
    Hv, dk] f32 normalised, per VALUE head; v [N, Hv, dv] f32)."""
    n = xc.shape[0]
    hk, dk = cfg.linear_key_heads, cfg.linear_key_head_dim
    x = _silu(xc)
    q, k, v = jnp.split(x, [cfg.key_width, 2 * cfg.key_width], axis=-1)

    def unit(u):
        u = u.reshape(n, hk, dk)
        u = u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(u, cfg.linear_value_heads // hk, axis=1)

    return unit(q) * dk ** -0.5, unit(k), v.reshape(
        n, cfg.linear_value_heads, cfg.linear_value_head_dim)


def _delta_out(o, z, p, cfg, dtype):
    """The heads' gated norm, then the out-projection."""
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.linear_o_norm_eps)
    y = y * (1.0 + p["o_norm.w"].astype(jnp.float32)) \
        * (cfg.linear_gate_scale * jax.nn.sigmoid(z.astype(jnp.float32)))
    return y.reshape(y.shape[0], -1).astype(dtype) @ p["out"]


def _rot(x, pos, cfg):
    return retention.rotary_pairs(x, pos, cfg.inv_freq)


def _latent_qkv(a, p, pos, cfg):
    """A full layer's latent projections for ``N`` tokens ``a`` [N, d] (the
    normed input) at ``pos``: (q_nope [N, H, dn], q_rope [N, H, dr]
    rotated, row [N, rank + dr] = [ckv | rotated k_rope], gate [N, H, dv]
    float32)."""
    n = a.shape[0]
    h, r, dn = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    cq = _norm(a @ p["dq"], p["q_norm.w"], cfg)
    q = (cq @ p["uq"]).reshape(n, h, dn + cfg.qk_rope_head_dim)
    q_rope = _rot(q[..., dn:], pos, cfg).astype(a.dtype)
    kv = a @ p["dkv"]
    ckv = _norm(kv[:, :r], p["kv_norm.w"], cfg)
    kr = _rot(kv[:, None, r:], pos, cfg)[:, 0].astype(a.dtype)
    gate = jax.nn.sigmoid(jnp.dot(a, p["gate"],
                                  preferred_element_type=jnp.float32))
    return q[..., :dn], q_rope, jnp.concatenate([ckv, kr], -1), \
        gate.reshape(n, h, cfg.v_head_dim)


def _absorb(q_nope, q_rope, p, cfg, width):
    """Queries carried into the latent: [N, H, width] = [q_abs | q_rope |
    0...]."""
    r, h, dn = cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
    w_uk = p["ukv"].reshape(r, h, dn + cfg.v_head_dim)[..., :dn]
    q_abs = jnp.einsum("nhd,chd->nhc", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_abs.astype(q_nope.dtype), q_rope], axis=-1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))


def _expand(o_lat, p, cfg):
    """A mix of latent rows through each head's ``W_uv``: [N, H, dv]."""
    r, h, dn = cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
    w_uv = p["ukv"].reshape(r, h, dn + cfg.v_head_dim)[..., dn:]
    return jnp.einsum("nhc,chv->nhv", o_lat, w_uv,
                      preferred_element_type=jnp.float32)


def _attn_out(o, gate, p, dtype):
    o = (o.astype(jnp.float32) * gate).astype(dtype)
    return o.reshape(o.shape[0], -1) @ p["o"]


def _page_row(row, width):
    return jnp.pad(row, ((0, 0), (0, width - row.shape[-1])))


def _add(counts, cfg, at, n):
    return counts.at[cfg.n_held + 1 + at].add(jnp.asarray(n, counts.dtype))


def _logits(params, h, cfg):
    h = _norm(h, params["norm_f.w"], cfg)
    return jnp.dot(h, params["head"].T, preferred_element_type=jnp.float32)


# ---------------------------------------------------------- step functions

def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step: every slot advances one token.

    ids : [B] int32; cache : ``k_pages`` [n_full, P, page, latent_width] the
    latent rows, ``v_pages`` the engine's empty one (passed through),
    ``page_table`` [B, pages], ``lengths`` [B], ``state`` = (delta, a conv
    array a linear layer), ``counts`` (optional); slot_mask : [B] bool: an inactive slot writes to
    the trash page, leaves its state alone and is not counted. Returns
    (logits [B, V] f32, new cache)."""
    table, pos = cache["page_table"], cache["lengths"]
    lat = cache["k_pages"]
    delta, *conv = cache["state"]
    counts = cache.get("counts")
    if counts is None:
        counts = jnp.zeros(step_counts(cfg), jnp.int32)
    live = jnp.sum(slot_mask, dtype=jnp.int32)
    h = params["embed"][ids]
    n_full = n_lin = 0
    for i, kind in enumerate(cfg.layer_types):
        norms = _sub(params, f"L{i}.n.")
        a = _norm(h, norms["pre1"], cfg)
        if kind == LINEAR:
            k, n_lin = n_lin, n_lin + 1
            p = _sub(params, f"L{i}.d.")
            x, z, beta, log_g = _delta_inputs(a, p, cfg)
            with jax.named_scope("conv"):
                xc, conv[k] = deltanet.conv_update(conv[k], x, p["conv"],
                                                   slot_mask)
            q, kk, v = _delta_operands(xc, cfg)
            with jax.named_scope("deltanet"):
                o, delta = deltanet.deltanet_update(
                    delta, log_g, beta, q, kk, v, slot_mask, layer=k)
            y = _delta_out(o, z, p, cfg, h.dtype)
            counts = _add(counts, cfg, _DELTA_DECODE, live)
        else:
            k, n_full = n_full, n_full + 1
            p = _sub(params, f"L{i}.a.")
            q_nope, q_rope, row, gate = _latent_qkv(a, p, pos, cfg)
            page, off = pa.token_page_coords(table, pos, slot_mask,
                                             lat.shape[2])
            lat = lat.at[k, page, off].set(
                _page_row(row, lat.shape[3]).astype(lat.dtype))
            qpos = jnp.where(slot_mask, pos, -1)
            with jax.named_scope("mla"):
                o_lat = mla.latent_decode_paged(
                    _absorb(q_nope, q_rope, p, cfg, lat.shape[3]), lat, k,
                    table, qpos, rank=cfg.kv_lora_rank, scale=cfg.attn_scale)
            y = _attn_out(_expand(o_lat, p, cfg), gate, p, h.dtype)
            counts = _add(counts, cfg, _PAIRS_DECODE, jnp.sum(qpos + 1))
        h = h + _norm(y, norms["post1"], cfg)
        y, counts = _ffn(_norm(h, norms["pre2"], cfg),
                         _sub(params, f"L{i}.f."), slot_mask, counts, cfg,
                         i < cfg.first_dense, _HIT_DECODE)
        h = h + _norm(y, norms["post2"], cfg)
    new_cache = dict(cache, k_pages=lat,
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
    lat = k_pages
    delta, *conv = state
    t = ids.shape[0]
    i_tok = jnp.arange(t)
    live = i_tok < valid
    pos = start + i_tok
    qpos = jnp.where(live, pos, -1)
    fresh = start == 0
    tally = jnp.zeros(step_counts(cfg), jnp.int32) if counts is None \
        else counts
    h = params["embed"][ids]
    n_full = n_lin = 0
    for i, kind in enumerate(cfg.layer_types):
        norms = _sub(params, f"L{i}.n.")
        a = _norm(h, norms["pre1"], cfg)
        if kind == LINEAR:
            k, n_lin = n_lin, n_lin + 1
            p = _sub(params, f"L{i}.d.")
            x, z, beta, log_g = _delta_inputs(a, p, cfg)
            with jax.named_scope("conv"):
                xc, conv[k] = deltanet.conv_chunk(conv[k], x, p["conv"],
                                                  slot, fresh, valid)
            q, kk, v = _delta_operands(xc, cfg)
            with jax.named_scope("deltanet"):
                o, delta = deltanet.deltanet_chunk(
                    delta, log_g, beta, q, kk, v, slot, fresh, valid,
                    layer=k)
            y = _delta_out(o, z, p, cfg, h.dtype)
            tally = _add(tally, cfg, _DELTA_PREFILL, valid)
        else:
            k, n_full = n_full, n_full + 1
            p = _sub(params, f"L{i}.a.")
            q_nope, q_rope, row, gate = _latent_qkv(a, p, pos, cfg)
            page, off = pa.chunk_page_coords(page_table, start, valid, t,
                                             lat.shape[2])
            lat = lat.at[k, page, off].set(
                _page_row(row, lat.shape[3]).astype(lat.dtype))
            with jax.named_scope("mla"):
                o, pairs = mla.latent_prefill(
                    q_nope, q_rope, lat, k, page_table, qpos, p["ukv"],
                    rank=cfg.kv_lora_rank, rope=cfg.qk_rope_head_dim,
                    dv=cfg.v_head_dim, scale=cfg.attn_scale)
            y = _attn_out(o, gate, p, h.dtype)
            tally = _add(tally, cfg, _PAIRS_PREFILL, pairs)
        h = h + _norm(y, norms["post1"], cfg)
        y, tally = _ffn(_norm(h, norms["pre2"], cfg),
                        _sub(params, f"L{i}.f."), live, tally, cfg,
                        i < cfg.first_dense, _HIT_PREFILL)
        h = h + _norm(y, norms["post2"], cfg)
    last = h[jnp.clip(valid - 1, 0, t - 1)]
    out = (_logits(params, last, cfg), lat, v_pages, delta, *conv)
    return out if counts is None else (*out, tally)


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 state, slot, counts=None):
    """A whole prompt in one bucket: the chunk that starts at 0."""
    return prefill_chunk_step(params, ids, jnp.int32(0), length, page_table,
                              k_pages, v_pages, cfg=cfg, state=state,
                              slot=slot, counts=counts)


# ------------------------------------------------------------------- model

class GigaChat35ForCausalLM:
    """The model object the serving engine is handed: a configuration and
    the parameter arrays. ``engine_family`` tells `DecodeEngine` how to run
    it (inference/family.py)."""

    def __init__(self, cfg: GigaChat35Config, params: dict):
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


def family(cfg: GigaChat35Config):
    """What `DecodeEngine` takes from this family (inference/family.py): a
    full layer's page row is ONE latent row (no second part: ``v_pages`` is
    empty), and beside the pool ``recurrent`` state of two kinds a linear
    layer: its slab of the matrix state's stack and a convolution array of
    its own."""
    import sys
    from paddle_tpu.inference.family import ModelFamily
    return ModelFamily(
        name="gigachat3_5", steps=sys.modules[__name__],
        params=lambda m: dict(m.params), table_key="embed",
        kv_layers=len(cfg.full_layers), kv_heads=1,
        head_dim=cfg.latent_width,
        max_positions=cfg.max_position_embeddings,
        state=lambda slots, page, dtype: state_arrays(cfg, slots, page,
                                                      dtype),
        step_counts=step_counts(cfg),
        on_counts=lambda grown: count_step(cfg, grown),
        page_rows=(("latent", cfg.latent_width),))
