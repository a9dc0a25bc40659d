"""GPT-2 family, TPU-first.

Counterpart of the reference's fleet GPT fixture
(`python/paddle/fluid/tests/unittests/auto_parallel_gpt_model.py`) and the
PaddleNLP GPT-345M hybrid-parallel config (BASELINE.md item 5). Design:

- TP via the fleet mpu layers (full logical weights + 'mp' shardings; GSPMD
  inserts the collectives the reference codes as `_c_identity`/`_mp_allreduce`).
- Sequence parallelism: activations carry a ('dp', 'sp') batch/sequence sharding
  constraint between blocks — beyond the reference (SURVEY.md §5.7).
- Attention = scaled_dot_product_attention -> Pallas flash kernel on TPU.
- Whole train step is meant to run under `paddle_tpu.jit.to_static` (one donated
  XLA program; the analog of CS5's run_program).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.fleet.meta_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    _constrain,
)
from paddle_tpu.distributed.mesh import get_mesh
from paddle_tpu.framework.param_attr import ParamAttr
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability import metrics


@dataclass
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to a TPU-friendly multiple
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02
    use_flash: bool = True
    seq_parallel: bool = False       # constrain activations over the 'sp' axis
    sp_attention: str = "ring"       # "ring" | "ulysses" | "none" — context-
                                     # parallel attention when sp > 1 (beyond
                                     # the reference, SURVEY §5.7)
    recompute: bool = False          # rematerialize each block (jax.checkpoint)
    recompute_granularity: str = "full"  # "full" | "mlp" | "mlp_up" (ref GPT
                                     # impls' recompute_granularity). "mlp"
                                     # remats ln_2+MLP; "mlp_up" only the
                                     # up-proj+gelu. Memory savers both —
                                     # measured speed LOSSES on the
                                     # bandwidth-bound single-chip step
                                     # (PERF.md r5), so default "full"
    fused_ce: bool = True            # lm-head+CE as one op (kernels/fused_ce.py)


# cache-priming sentinel: generate()'s first step passes this instead of
# zero-length [B, 0, H, Dh] tensors (zero-size device buffers crash/hang
# some PJRT transports); attention returns fresh K/V as the cache
INIT_CACHE = "init"


# --------------------------------------------------------------------------
# Pure decode math over the served weight layout (`serving_params`: the
# state_dict's block leaves stacked by layer). `fast_generate`, the
# paged `decode_step`/`prefill_step` (inference/engine.py), and the sampled
# `generate` path all run THESE functions, so their numerics agree by
# construction — token-identical output across cache layouts is the
# contract the parity tests enforce.

def _deq(v):
    """Weight-only int8 serving (paddle_tpu/quantization/serving.py): a
    params leaf may be a QuantizedLeaf (int8 + per-channel scale) —
    dequantize AT USE, inside whatever program is tracing. Float leaves
    pass through untouched, so the same decode math serves both."""
    return v.dequant() if hasattr(v, "dequant") else v


_SERVED_BLOCK = "blocks."       # + a BLOCK_SUFFIXES entry: indexed by layer

# the block's Python body ran under a trace / a layer's application of it
# was bound (docs/OBSERVABILITY.md): 1 and nl for every step program
_BLOCK_TRACES = metrics.counter("model.block_traces")
_BLOCK_CALLS = metrics.counter("model.block_calls")


def _ln_ref(x, w, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + 1e-5)
    return (y * w + b).astype(x.dtype)


def _block_stack(p, x, nl, nh, dh, attend, carry):
    """All nl transformer blocks over x ([..., H], H = nh*dh): ``(x,
    carry)``. ``attend(layer, q, k, v, carry) -> (att, carry)`` gets [...,
    nh, dh] q/k/v and returns the attention context in x.dtype with q's
    shape — the ONLY thing that differs between the dense-cache and
    paged-cache decode paths — and ``carry`` is whatever it rewrites (the
    K and V pools, an int8 pool's scales).

    The layers differ only by their weights, so the block is ONE traced
    function called nl times: ``layer`` is a traced int32 that indexes the
    vector stacks and the pools, and a layer's four matrices (the model's
    own arrays, or `QuantizedLeaf`s widened here, a layer at a time) are
    its arguments. A program traces and lowers a layer's code once, not
    once a layer (24 times for GPT-2 medium: 32 s of a 57 s warm start;
    PERF.md, PR 39). The compiler inlines the calls before any other pass,
    so each layer's matrices stay operands of their own (`serving_params`
    says why that matters) and the compiled program is the unrolled one."""
    lead = x.shape[:-1]
    served = {s: p[_SERVED_BLOCK + s] for s in BLOCK_SUFFIXES}
    # a tuple holds a leaf a layer (`serving_params`: the matrices)
    per_layer = [s for s, leaf in served.items() if isinstance(leaf, tuple)]

    @jax.jit
    def block(x, layer, mats, carry):
        _BLOCK_TRACES.inc()

        def get(suffix):
            if suffix in mats:
                return _deq(mats[suffix])
            return served[suffix][layer]
        hpre = _ln_ref(x, get("ln_1.weight"), get("ln_1.bias"))
        qkv = hpre @ get("attn.qkv_proj.weight") + get("attn.qkv_proj.bias")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        att, carry = attend(layer, q.reshape(*lead, nh, dh),
                            k.reshape(*lead, nh, dh),
                            v.reshape(*lead, nh, dh), carry)
        att = att.reshape(*lead, nh * dh)
        att = att @ get("attn.out_proj.weight") + get("attn.out_proj.bias")
        x = x + att
        hpre = _ln_ref(x, get("ln_2.weight"), get("ln_2.bias"))
        m = hpre @ get("mlp.fc_in.weight") + get("mlp.fc_in.bias")
        m = jax.nn.gelu(m, approximate=True)
        m = m @ get("mlp.fc_out.weight") + get("mlp.fc_out.bias")
        return x + m, carry

    _BLOCK_CALLS.inc(nl)
    for i in range(nl):
        x, carry = block(x, jnp.int32(i),
                         {s: served[s][i] for s in per_layer}, carry)
    return x, carry


def _final_logits(p, x):
    x = _ln_ref(x, p["gpt.ln_f.weight"], p["gpt.ln_f.bias"])
    return (x @ _deq(p["gpt.wte.weight"]).T).astype(jnp.float32)


def _causal_attend(scale, cmask, dtype):
    """Prefill attention over the prompt itself (dense f32 softmax)."""
    def attend(i, q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
        sc = jnp.where(cmask[None, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr,
                          v.astype(jnp.float32)).astype(dtype)
    return attend


def _make_sampler(temperature, top_k):
    """Greedy / temperature / top-k sampling on [B, V] f32 logits with a
    threaded PRNG key. Temperature scales BEFORE the top-k mask (so the
    kth-logit cutoff is applied on the tempered distribution), and the key
    splits once per sampled token — both `generate` and `fast_generate`
    thread keys identically, so a shared seed reproduces the same tokens
    on either path."""
    def sample(logits, key):
        if temperature != 1.0:
            logits = logits / temperature
        if top_k:
            vals, _ = jax.lax.top_k(logits, top_k)
            kth = vals[:, -1][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_k or temperature != 1.0:
            key, sub = jax.random.split(key)
            return jax.random.categorical(sub, logits, axis=-1), key
        return jnp.argmax(logits, axis=-1), key
    return sample


def decode_step(params, ids, cache, slot_mask, *, cfg):
    """One fixed-shape batched decode step over a PAGED KV cache.

    The serving engine's inner loop (inference/engine.py): B slots advance
    one token in one device call. Nothing here depends on which slots are
    live — ``slot_mask`` only routes dead slots' cache writes to the trash
    page and freezes their lengths — so slots can join/retire between steps
    with zero recompiles (continuous batching).

    params    : `serving_params` arrays (the `fast_generate` weight layout)
    ids       : [B] int32 — current token per slot
    cache     : dict with
                  k_pages/v_pages : [nl, num_pages, page_size, nh * dh]
                                    — heads merged into the lane axis, the
                                    layout the attention kernels read
                                    (kernels/paged_attention.py); a layer
                                    is addressed by index and never
                                    sliced out of the stack
                  page_table      : [B, pages_per_slot] int32
                  lengths         : [B] int32 tokens already cached
                  k_scale/v_scale : OPTIONAL [nl, num_pages, page_size, nh]
                                    f32 — present iff the pool is int8
                                    (EngineConfig.kv_dtype="int8"): writes
                                    quantize per-head abs-max, reads
                                    dequantize after the page gather/DMA
    slot_mask : [B] bool — active slots
    returns   : (logits [B, V] f32, new cache with lengths advanced)
    """
    from paddle_tpu.kernels import paged_attention as pa
    nl, nh = cfg.num_layers, cfg.num_heads
    dh = cfg.hidden_size // nh
    kc, vc = cache["k_pages"], cache["v_pages"]
    ks, vs = cache.get("k_scale"), cache.get("v_scale")
    page_table, lengths = cache["page_table"], cache["lengths"]
    ps = kc.shape[2]
    # write position = current length; clamp only to keep gathers in range
    # for retired slots sitting at capacity
    pos = jnp.clip(lengths, 0, params["gpt.wpe.weight"].shape[0] - 1)
    x = params["gpt.wte.weight"][ids] + params["gpt.wpe.weight"][pos]

    def attend(i, q, k, v, pools):
        kc, vc, ks, vs = pools
        page, off = pa.token_page_coords(page_table, pos, slot_mask, ps)
        if ks is not None:
            k, sk = pa.quantize_kv(k)
            v, sv = pa.quantize_kv(v)
            ks = ks.at[i, page, off].set(sk)
            vs = vs.at[i, page, off].set(sv)
        kc = kc.at[i, page, off].set(pa.kv_rows(k, kc))
        vc = vc.at[i, page, off].set(pa.kv_rows(v, vc))
        att = pa.paged_attention(q, kc, vc, page_table, pos,
                                 k_scale=ks, v_scale=vs, layer=i)
        return att, (kc, vc, ks, vs)

    x, (kc, vc, ks, vs) = _block_stack(params, x, nl, nh, dh, attend,
                                       (kc, vc, ks, vs))
    logits = _final_logits(params, x)
    new_cache = dict(k_pages=kc, v_pages=vc, page_table=page_table,
                     lengths=jnp.where(slot_mask, lengths + 1, lengths))
    if ks is not None:
        new_cache.update(k_scale=ks, v_scale=vs)
    return logits, new_cache


def prefill_step(params, ids, length, page_table, k_pages, v_pages, *, cfg,
                 k_scale=None, v_scale=None):
    """Bucketed single-sequence prefill into the paged cache.

    ids is PADDED to its bucket length S (a small power-of-two set, so
    prefill compiles O(buckets) programs); ``length`` is the true prompt
    length. One dense causal pass computes the prompt's K/V, scatters
    positions < length into the slot's pages (padding lands on the trash
    page), and returns the last REAL token's logits so the engine can
    sample the first generated token.

    With ``k_scale``/``v_scale`` (int8 pool) the writes quantize per-head
    abs-max AND the prompt's own causal attention runs over the
    quantize-dequantize round trip of K/V — every later read conditions on
    the quantized cache, so one-shot, chunked, prefix-hit and handoff
    prefills stay token-identical to each other (tests/test_quantization).

    returns : (logits [V] f32, k_pages, v_pages[, k_scale, v_scale])
    """
    from paddle_tpu.kernels import paged_attention as pa
    nl, nh = cfg.num_layers, cfg.num_heads
    dh = cfg.hidden_size // nh
    scale = 1.0 / (dh ** 0.5)
    ps = k_pages.shape[2]
    s = ids.shape[0]
    x = params["gpt.wte.weight"][ids][None] + \
        params["gpt.wpe.weight"][None, :s]               # [1, S, H]
    cmask = jnp.tril(jnp.ones((s, s), bool))
    causal = _causal_attend(scale, cmask, x.dtype)
    # registry-routed impl for the one-shot prefill's attention
    # (kernels/registry.py, FLAGS_tpu_prefill_impl): the xla arm is the
    # dense causal pass over the prompt's own K/V; the pallas arm reads
    # back the pages just written (start=0, valid=length), which is only
    # numerics-preserving when the pool dtype carries the compute dtype
    # (or the pool is int8, where the xla arm already attends the
    # quantize-dequantize round trip) — the ``parity`` ctx drops the
    # pallas candidate otherwise
    quant = k_scale is not None
    impl = pa.prefill_impl(
        s, page_table.shape[0], ps, nh, dh, x.dtype, quant=quant,
        parity=quant or k_pages.dtype == x.dtype,
        num_pages=k_pages.shape[1])

    def attend(i, q, k, v, pools):
        k_pages, v_pages, k_scale, v_scale = pools
        page, off = pa.prompt_page_coords(page_table, length, s, ps)
        if k_scale is not None:
            qk, sk = pa.quantize_kv(k[0])
            qv, sv = pa.quantize_kv(v[0])
            k_pages = k_pages.at[i, page, off].set(pa.kv_rows(qk, k_pages))
            v_pages = v_pages.at[i, page, off].set(pa.kv_rows(qv, v_pages))
            k_scale = k_scale.at[i, page, off].set(sk)
            v_scale = v_scale.at[i, page, off].set(sv)
            k = pa.dequantize_window(qk, sk)[None].astype(x.dtype)
            v = pa.dequantize_window(qv, sv)[None].astype(x.dtype)
        else:
            k_pages = k_pages.at[i, page, off].set(pa.kv_rows(k[0], k_pages))
            v_pages = v_pages.at[i, page, off].set(pa.kv_rows(v[0], v_pages))
        pools = k_pages, v_pages, k_scale, v_scale
        if impl == "pallas":
            # length-aware: the page walk stops at ceil(length/page_size),
            # not at the pow-2 bucket the queries are padded to
            return pa._prefill_impl_call(
                "pallas", q, k_pages, v_pages, page_table, jnp.int32(0),
                length, i, k_scale=k_scale,
                v_scale=v_scale).astype(x.dtype), pools
        return causal(i, q, k, v), pools

    x, (k_pages, v_pages, k_scale, v_scale) = _block_stack(
        params, x, nl, nh, dh, attend, (k_pages, v_pages, k_scale, v_scale))
    last = x[0, jnp.clip(length - 1, 0, s - 1)]
    logits = _final_logits(params, last)
    if k_scale is not None:
        return logits, k_pages, v_pages, k_scale, v_scale
    return logits, k_pages, v_pages


def prefill_chunk_step(params, ids, start, valid, page_table, k_pages,
                       v_pages, *, cfg, k_scale=None, v_scale=None):
    """One CHUNK of a decode-priority chunked prefill into the paged cache.

    The engine splits a long prompt into fixed-size chunks interleaved
    between decode steps (`EngineConfig.prefill_chunk_tokens`), so a long
    prompt no longer stalls every in-flight decode for its full prefill
    wall. ``ids`` is ONE chunk padded to the fixed chunk length C (one
    compiled program per chunk size — AOT like every other engine program);
    ``start`` is the absolute position of ``ids[0]``; ``valid`` is the true
    token count in this chunk.

    Writes the chunk's K/V into the slot's pages (padding and overflow land
    on the trash page), then attends the chunk's queries over ALL cached
    positions — previous chunks AND the current one — via the paged gather,
    masked by absolute position (query at position p sees keys 0..p). Same
    f32 masked-softmax numerics as `decode_step`, so chunked prefill is
    token-identical to the one-shot `prefill_step` path.

    returns : (logits [V] f32 of the chunk's LAST valid token — only
               meaningful on the final chunk — , k_pages, v_pages)
    """
    from paddle_tpu.kernels import paged_attention as pa
    nl, nh = cfg.num_layers, cfg.num_heads
    dh = cfg.hidden_size // nh
    ps = k_pages.shape[2]
    c = ids.shape[0]
    pos = start + jnp.arange(c)
    wpe = params["gpt.wpe.weight"]
    x = params["gpt.wte.weight"][ids][None] + \
        wpe[jnp.clip(pos, 0, wpe.shape[0] - 1)][None]        # [1, C, H]

    def attend(i, q, k, v, pools):
        k_pages, v_pages, k_scale, v_scale = pools
        page, off = pa.chunk_page_coords(page_table, start, valid, c, ps)
        if k_scale is not None:
            k, sk = pa.quantize_kv(k[0])
            v, sv = pa.quantize_kv(v[0])
            k_scale = k_scale.at[i, page, off].set(sk)
            v_scale = v_scale.at[i, page, off].set(sv)
        else:
            k, v = k[0], v[0]
        k_pages = k_pages.at[i, page, off].set(pa.kv_rows(k, k_pages))
        v_pages = v_pages.at[i, page, off].set(pa.kv_rows(v, v_pages))
        # ragged prefill attention over the paged cache — previous chunks
        # AND the current one, absolute-position masked. Registry-routed
        # (kernels/registry.py): xla gathers the full window, pallas
        # streams only ceil((start+valid)/page_size) pages per (q block,
        # head) cell
        att = pa.prefill_attention(
            q, k_pages, v_pages, page_table, start, valid,
            k_scale=k_scale, v_scale=v_scale, layer=i).astype(x.dtype)
        return att, (k_pages, v_pages, k_scale, v_scale)

    x, (k_pages, v_pages, k_scale, v_scale) = _block_stack(
        params, x, nl, nh, dh, attend, (k_pages, v_pages, k_scale, v_scale))
    last = x[0, jnp.clip(valid - 1, 0, c - 1)]
    logits = _final_logits(params, last)
    if k_scale is not None:
        return logits, k_pages, v_pages, k_scale, v_scale
    return logits, k_pages, v_pages


def verify_step(params, tok_seq, draft_len, cache, slot_mask, *, cfg,
                sampler=None, keys=None, sample_state=None):
    """Speculative-decode VERIFY: score k+1 positions per slot in ONE
    fixed-shape step over the paged gather.

    The engine drafts up to k tokens per slot (self-drafting n-gram
    proposer, `inference/engine.py`); this program writes all k+1 tokens'
    K/V into the slot's pages, computes logits at every position in one
    batched pass, and accepts the longest draft prefix that matches what
    plain decode would have emitted — plus ONE corrected token from the
    first mismatching position. Rejected tokens need no device rollback:
    the host rolls the slot's length back and every later step rewrites
    those positions before any query attends them (page-granular rollback
    is free by construction of the write-before-attend cache discipline).

    tok_seq   : [B, K+1] int32 — column 0 is each slot's CURRENT token
                (same semantics as `decode_step`'s ids), columns 1..K the
                drafted continuation (padding past ``draft_len``)
    draft_len : [B] int32 — true drafted tokens per slot (0..K; 0 degrades
                to exactly `decode_step` emitting one token)
    cache     : as `decode_step` (k_pages/v_pages/page_table/lengths)
    slot_mask : [B] bool — inactive slots write to TRASH_PAGE and emit 0
    sampler   : optional `_make_sampler` fn for sampled verification;
                greedy argmax when None (the engine's mode)
    keys      : with ``sampler``, [B, 2] uint32 per-slot PRNG keys; the key
                chain is split once per position EXACTLY as `fast_generate`
                splits once per emitted token, and the returned keys are
                each slot's chain advanced by its n_emitted splits — so
                sampled speculative decode is bit-identical to plain
                sampled decode (parity-tested incl. top-k)
    sample_state : the FUSED per-slot sampler (kernels/sampling.py, the
                engine's sampling mode): a ``(keys [B, 2] uint32,
                temperatures [B] f32, top_ks [B] i32)`` triple. Same key
                discipline as ``sampler``/``keys`` but with DYNAMIC
                per-slot params riding program inputs — one compiled
                verify program serves every request's sampling knobs
                (greedy slots run the argmax arm, chains untouched).
                Mutually exclusive with ``sampler``
    returns   : (emitted [B, K+1] int32 — positions < n_emitted are the
                 step's output tokens —, n_emitted [B] int32 in 0..K+1,
                 new cache with lengths advanced by n_emitted[, new_keys])

    Acceptance is EXACT, not approximate: emitted tokens are precisely the
    tokens the non-speculative loop would produce, because position i's
    logits condition on drafts 1..i and are only consumed when every one of
    those drafts equals the token the model itself emitted at that slot.
    """
    from paddle_tpu.kernels import paged_attention as pa
    nl, nh = cfg.num_layers, cfg.num_heads
    dh = cfg.hidden_size // nh
    scale = 1.0 / (dh ** 0.5)
    kc, vc = cache["k_pages"], cache["v_pages"]
    ks, vs = cache.get("k_scale"), cache.get("v_scale")
    page_table, lengths = cache["page_table"], cache["lengths"]
    ps = kc.shape[2]
    b, kp1 = tok_seq.shape
    offs = jnp.arange(kp1)
    pos = lengths[:, None] + offs[None, :]                     # [B, K+1]
    valid = slot_mask[:, None] & (offs[None, :] <= draft_len[:, None])
    wpe = params["gpt.wpe.weight"]
    x = params["gpt.wte.weight"][tok_seq] + \
        wpe[jnp.clip(pos, 0, wpe.shape[0] - 1)]                # [B, K+1, H]

    def attend(i, q, k, v, pools):
        kc, vc, ks, vs = pools
        page, off = pa.verify_page_coords(page_table, pos, valid, ps)
        if ks is not None:
            k, sk = pa.quantize_kv(k)
            v, sv = pa.quantize_kv(v)
            ks = ks.at[i, page, off].set(sk)
            vs = vs.at[i, page, off].set(sv)
        kc = kc.at[i, page, off].set(pa.kv_rows(k, kc))
        vc = vc.at[i, page, off].set(pa.kv_rows(v, vc))
        kk = pa.gather_kv(kc, page_table, i, nh).astype(jnp.float32)
        vv = pa.gather_kv(vc, page_table, i, nh).astype(jnp.float32)
        if ks is not None:                                 # [B, Lmax, nh, dh]
            kk = kk * pa.gather_scales(ks, page_table, i)[..., None]
            vv = vv * pa.gather_scales(vs, page_table, i)[..., None]
        lmax = kk.shape[1]
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kk)
        # absolute-position causality: query at position p sees keys 0..p —
        # within-window future drafts mask out exactly like unwritten pages
        mask = jnp.arange(lmax)[None, None, :] <= pos[:, :, None]
        sc = jnp.where(mask[:, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", pr, vv).astype(x.dtype)
        return att, (kc, vc, ks, vs)

    x, (kc, vc, ks, vs) = _block_stack(params, x, nl, nh, dh, attend,
                                       (kc, vc, ks, vs))
    logits = _final_logits(params, x)                          # [B, K+1, V]

    if sampler is not None and sample_state is not None:
        raise ValueError("verify_step takes sampler= OR sample_state=, "
                         "not both")
    new_keys = None
    if sampler is None and sample_state is None:
        out = jnp.argmax(logits, axis=-1).astype(tok_seq.dtype)
    elif sample_state is not None:
        # the fused per-slot sampler: dynamic (temperature, top_k) ride
        # program inputs, so one warm program serves every request's
        # sampling knobs (kernels/sampling.py — bit-identical to the
        # static `sampler` path for matching params)
        from paddle_tpu.kernels.sampling import sample_one
        keys, temps, topks = sample_state

        def fchain(key, lg, t, tk):    # one slot: [K+1, V] logits
            def one(k_, l_):
                tok, k2 = sample_one(l_, k_, t, tk)
                return k2, (tok, k2)
            _, (toks, keys_after) = jax.lax.scan(one, key, lg)
            return toks, keys_after
        out, keys_after = jax.vmap(fchain)(keys, logits, temps, topks)
        out = out.astype(tok_seq.dtype)
    else:
        def chain(key, lg):            # one slot: [K+1, V] logits
            def one(k_, l_):
                t, k2 = sampler(l_[None], k_)
                return k2, (t[0], k2)
            _, (toks, keys_after) = jax.lax.scan(one, key, lg)
            return toks, keys_after
        out, keys_after = jax.vmap(chain)(keys, logits)
        out = out.astype(tok_seq.dtype)

    # the ONE accept-test implementation (kernels/sampling.py): longest
    # draft prefix matching the model's own emissions + 1 corrected token
    from paddle_tpu.kernels.sampling import accept_drafts
    n_emitted = accept_drafts(tok_seq[:, 1:], out, draft_len, slot_mask)
    new_cache = dict(k_pages=kc, v_pages=vc, page_table=page_table,
                     lengths=jnp.where(slot_mask, lengths + n_emitted,
                                       lengths))
    if ks is not None:
        new_cache.update(k_scale=ks, v_scale=vs)
    if sampler is None and sample_state is None:
        return out, n_emitted, new_cache
    new_keys = jnp.take_along_axis(
        keys_after, jnp.maximum(n_emitted - 1, 0)[:, None, None], axis=1)[:, 0]
    # an inactive slot emitted nothing: its chain must not move at all
    new_keys = jnp.where((n_emitted > 0)[:, None], new_keys, keys)
    return out, n_emitted, new_cache, new_keys


def _fused_ce_impl(cfg) -> str:
    """Registry-routed LM-head CE selection (`kernels/registry.py`,
    op ``fused_ce``): "fused" = fused_linear_cross_entropy (one op with a
    custom VJP, autodiff never sees the [N, V] logits; its forward is a
    Pallas kernel where the shapes fit, `kernels/fused_ce.py`), "dense" =
    logits + log-softmax. The fused arm is viable only without an mp axis (the
    vocab is sharded under mp and only the parallel CE is correct);
    ``cfg.fused_ce=False`` forces dense. Counted per trace in
    ``kernel.dispatch.fused_ce.{fused|dense}``."""
    # `kernels/fused_ce.py` registers the op it implements
    from paddle_tpu.kernels import fused_ce, registry  # noqa: F401
    mesh = get_mesh()
    mp = 1 if mesh is None else mesh.shape.get("mp", 1)
    return registry.dispatch(
        "fused_ce", forced="fused" if cfg.fused_ce else "dense",
        ctx={"mp": mp}, require_viable=True)


def _sp_constrain(x, cfg):
    """[B, S, H] activations: batch over dp, sequence over sp."""
    if not cfg.seq_parallel or get_mesh() is None:
        return x
    return _constrain(x, PartitionSpec("dp", "sp", None))


# --------------------------------------------------------------------------
# Scanned layer stack (training hot path).
#
# The Layer-based forward above unrolls all `nl` blocks into the traced
# graph, so XLA compile wall grows linearly with depth — the 8-device CPU
# dryrun times out before producing a step. Here the block weights live as
# STACKED [nl, ...] pytree leaves and the forward is ONE `jax.lax.scan`
# over them: the block body is traced/compiled once regardless of nl, so
# compile time is O(1) in depth. The `recompute`/`recompute_granularity`
# knobs map onto scan-level `jax.checkpoint` policies (full-block remat /
# save-everything-except the tagged MLP intermediates). Converters keep the
# per-layer state_dict layout as the checkpoint + decode/serving truth.

BLOCK_SUFFIXES = (
    "ln_1.weight", "ln_1.bias",
    "attn.qkv_proj.weight", "attn.qkv_proj.bias",
    "attn.out_proj.weight", "attn.out_proj.bias",
    "ln_2.weight", "ln_2.bias",
    "mlp.fc_in.weight", "mlp.fc_in.bias",
    "mlp.fc_out.weight", "mlp.fc_out.bias",
)

_BLOCK_PREFIX = "gpt.h."


def analytic_param_count(cfg) -> int:
    """Parameter count straight from the config (no weights needed):
    embeddings + per-block (qkv, proj, mlp up/down, 2 LNs) + final LN.
    Matches `sum(prod(p.shape) for p in model.parameters())` exactly —
    `tests/test_tracing.py` pins that."""
    h, i = cfg.hidden_size, cfg.intermediate_size
    per_block = (3 * h * h + 3 * h       # qkv
                 + h * h + h             # attn proj
                 + h * i + i             # mlp up
                 + i * h + h             # mlp down
                 + 4 * h)                # ln_1 + ln_2 (scale + bias)
    return (cfg.vocab_size * h                       # wte (tied lm head)
            + cfg.max_position_embeddings * h        # wpe
            + cfg.num_layers * per_block
            + 2 * h)                                 # final ln


def analytic_flops_per_token(cfg, seq_len: int) -> float:
    """Training FLOPs per token: the standard 6N matmul term (fwd + bwd)
    plus the attention score/context term 12·nl·h·S (QKᵀ and PV are each
    2·nl·h·S per token forward, ×3 for fwd+bwd) — the PaLM/Chinchilla
    accounting the `train.mfu` gauge uses (`train/scan_step.py`)."""
    return (6.0 * analytic_param_count(cfg)
            + 12.0 * cfg.num_layers * cfg.hidden_size * seq_len)


def _leaf_array(v):
    return v._data if hasattr(v, "_data") else jnp.asarray(v)


def stacked_num_layers(params):
    """Number of per-layer blocks present in a state_dict-layout dict."""
    idx = [int(k[len(_BLOCK_PREFIX):].split(".", 1)[0]) for k in params
           if k.startswith(_BLOCK_PREFIX)]
    if not idx:
        raise ValueError("no gpt.h.<i>.* leaves: not a GPT state dict")
    return 1 + max(idx)


def stack_gpt_params(params, mesh=None):
    """state_dict layout {name: array} -> {"blocks": {suffix: [nl, ...]},
    "top": {name: array}}.

    Per-leaf `mp`/`sp` shardings survive the restack: a layer weight placed
    as NamedSharding(mesh, spec) comes out as the stacked leaf sharded
    PartitionSpec(None, *spec) — the layer axis is never split, so each
    scan slice carries exactly the old per-layer placement and GSPMD
    inserts the same collectives it did for the unrolled graph."""
    from jax.sharding import NamedSharding
    arrs = {k: _leaf_array(v) for k, v in params.items()}
    nl = stacked_num_layers(arrs)
    blocks, top = {}, {}
    for suffix in BLOCK_SUFFIXES:
        leaves = [arrs[f"{_BLOCK_PREFIX}{i}.{suffix}"] for i in range(nl)]
        stacked = jnp.stack(leaves)
        sh = getattr(leaves[0], "sharding", None)
        if isinstance(sh, NamedSharding) and any(
                s is not None for s in sh.spec):
            stacked = jax.device_put(
                stacked, NamedSharding(mesh or sh.mesh,
                                       PartitionSpec(None, *sh.spec)))
        blocks[suffix] = stacked
    for k, v in arrs.items():
        if not k.startswith(_BLOCK_PREFIX):
            top[k] = v
    return {"blocks": blocks, "top": top}


def serving_params(params):
    """state_dict layout -> what every decode path reads (`_block_stack`):
    ONE flat dict, the top leaves under their own names and each block leaf
    under ``blocks.<suffix>``, indexed by layer. The 8 vectors (norms and
    biases) are stacked ``[nl, width]``; the 4 matrices stay a tuple of the
    model's own per-layer arrays. A launch costs the host a microsecond a
    leaf, so 12 leaves a layer become 4 (108 for 24 layers, not 292); a
    matrix stays an operand of its own because the TPU compiler prefetches
    whole operands into fast memory under the ops before, and a 24-layer
    stack is too large an operand to prefetch (PERF.md, PR 37: with the
    matrices stacked too the decode program ran 2.04 ms a step on the
    chip, not 1.85)."""
    arrs = {k: _leaf_array(v) for k, v in params.items()}
    nl = stacked_num_layers(arrs)
    out = {k: v for k, v in arrs.items() if not k.startswith(_BLOCK_PREFIX)}
    for suffix in BLOCK_SUFFIXES:
        leaves = tuple(arrs[f"{_BLOCK_PREFIX}{i}.{suffix}"]
                       for i in range(nl))
        out[_SERVED_BLOCK + suffix] = \
            leaves if leaves[0].ndim == 2 else jnp.stack(leaves)
    return out


def unstack_gpt_params(stacked):
    """Inverse of :func:`stack_gpt_params`: back to the per-layer
    state_dict layout (checkpoints, decode paths, Layer parameters)."""
    out = dict(stacked["top"])
    nl = next(iter(stacked["blocks"].values())).shape[0]
    for suffix, leaf in stacked["blocks"].items():
        for i in range(nl):
            out[f"{_BLOCK_PREFIX}{i}.{suffix}"] = leaf[i]
    return out


def _scan_remat_wrapper(cfg):
    """Map the model's recompute knobs onto a scan-level jax.checkpoint
    policy applied to the per-layer body:

    - ``recompute=True``            -> full-block remat (save only carries)
    - ``recompute_granularity="mlp"``    -> recompute ln_2 + the [N, 4H]
      up-projection in bwd (their activations are tagged and excluded from
      the saveable set)
    - ``recompute_granularity="mlp_up"`` -> recompute only up-proj+gelu
    - otherwise                      -> no remat (XLA keeps all residuals)
    """
    if cfg.recompute:
        return lambda body: jax.checkpoint(body, prevent_cse=False)
    gran = cfg.recompute_granularity
    if gran in ("mlp", "mlp_up"):
        names = ("mlp_up",) if gran == "mlp_up" else ("mlp_up", "mlp_ln")
        pol = jax.checkpoint_policies.save_anything_except_these_names(*names)
        return lambda body: jax.checkpoint(body, policy=pol,
                                           prevent_cse=False)
    return lambda body: body


def _fdropout(x, key, p):
    """upscale_in_train dropout on a raw array (paddle nn.Dropout default)."""
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))


def _scan_attend(cfg):
    """Training attention for the scan body on [B, S, nh, dh] q/k/v."""
    if cfg.use_flash:
        from paddle_tpu.kernels.flash_attention import flash_attention_fn
        return flash_attention_fn(causal=True)
    dh = cfg.hidden_size // cfg.num_heads
    scale = 1.0 / (dh ** 0.5)

    def dense(q, k, v):
        s = q.shape[1]
        cmask = jnp.tril(jnp.ones((s, s), bool))
        return _causal_attend(scale, cmask, q.dtype)(None, q, k, v)

    return dense


def scan_blocks(blocks, x, cfg, *, training=False, dropout_keys=None):
    """All nl transformer blocks over x as ONE lax.scan over the stacked
    leaves. `dropout_keys` is a [nl, 2] key array (attn-residual, mlp) when
    training with hidden_dropout > 0, else None."""
    from jax.ad_checkpoint import checkpoint_name
    nh = cfg.num_heads
    dh = cfg.hidden_size // nh
    mesh = get_mesh()
    attend = _scan_attend(cfg)
    p_drop = float(cfg.hidden_dropout) if training else 0.0

    def body(h, per_layer):
        lp, keys = per_layer if p_drop else (per_layer, None)
        lead = h.shape[:-1]
        hn = _ln_ref(h, lp["ln_1.weight"], lp["ln_1.bias"])
        # matmul leaves may be QuantizedLeaf (stacked weight-only int8):
        # lax.scan slices the leaf's int8 values AND its per-layer scale
        # along the nl axis, so _deq sees one layer's pair here
        qkv = hn @ _deq(lp["attn.qkv_proj.weight"]) \
            + lp["attn.qkv_proj.bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        att = attend(q.reshape(*lead, nh, dh), k.reshape(*lead, nh, dh),
                     v.reshape(*lead, nh, dh))
        att = att.reshape(*lead, nh * dh)
        att = att @ _deq(lp["attn.out_proj.weight"]) \
            + lp["attn.out_proj.bias"]
        if p_drop:
            att = _fdropout(att, keys[0], p_drop)
        h = h + att
        hn = _ln_ref(h, lp["ln_2.weight"], lp["ln_2.bias"])
        hn = checkpoint_name(hn, "mlp_ln")
        up = jax.nn.gelu(hn @ _deq(lp["mlp.fc_in.weight"])
                         + lp["mlp.fc_in.bias"], approximate=True)
        up = checkpoint_name(up, "mlp_up")
        m = up @ _deq(lp["mlp.fc_out.weight"]) + lp["mlp.fc_out.bias"]
        if p_drop:
            m = _fdropout(m, keys[1], p_drop)
        h = h + m
        if cfg.seq_parallel and mesh is not None:
            from jax.sharding import NamedSharding
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, PartitionSpec("dp", "sp", None)))
        return h, None

    wrapped = _scan_remat_wrapper(cfg)(body) if training else body
    xs = (blocks, dropout_keys) if p_drop else blocks
    x, _ = jax.lax.scan(wrapped, x, xs)
    return x


def scan_hidden(stacked, ids, cfg, *, training=False, dropout_key=None):
    """[B, S] ids -> final-LN hidden states [B, S, H] via the scanned stack."""
    if training and cfg.attention_dropout:
        raise NotImplementedError(
            "scan path has no attention-dropout implementation; use the "
            "unrolled Layer forward (or set attention_dropout=0)")
    top, blocks = stacked["top"], stacked["blocks"]
    s = ids.shape[-1]
    x = top["gpt.wte.weight"][ids] + top["gpt.wpe.weight"][None, :s]
    keys = None
    if training and cfg.hidden_dropout:
        if dropout_key is None:
            raise ValueError("hidden_dropout > 0 needs a dropout_key")
        nl = next(iter(blocks.values())).shape[0]
        emb_key, lk = jax.random.split(dropout_key)
        x = _fdropout(x, emb_key, float(cfg.hidden_dropout))
        keys = jax.random.split(lk, (nl, 2))
    mesh = get_mesh()
    if cfg.seq_parallel and mesh is not None:
        from jax.sharding import NamedSharding
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec("dp", "sp", None)))
    x = scan_blocks(blocks, x, cfg, training=training, dropout_keys=keys)
    return _ln_ref(x, top["gpt.ln_f.weight"], top["gpt.ln_f.bias"])


def scan_logits(stacked, ids, cfg, *, training=False, dropout_key=None):
    """[B, S] ids -> [B, S, V] f32 logits (tied lm head, no fused CE)."""
    h = scan_hidden(stacked, ids, cfg, training=training,
                    dropout_key=dropout_key)
    return (h @ stacked["top"]["gpt.wte.weight"].T).astype(jnp.float32)


def scan_loss(stacked, ids, labels, cfg, *, loss_mask=None, training=True,
              dropout_key=None):
    """Scalar f32 causal-LM loss over the scanned stack — the same math as
    GPTForCausalLM.forward(labels=...) (fused LM-head CE when enabled and
    no mp axis; dense logits + log-softmax CE otherwise)."""
    h = scan_hidden(stacked, ids, cfg, training=training,
                    dropout_key=dropout_key)
    wte = stacked["top"]["gpt.wte.weight"]
    use_fused = _fused_ce_impl(cfg) == "fused"
    if use_fused:
        from paddle_tpu.kernels.fused_ce import fused_linear_cross_entropy
        n = h.shape[0] * h.shape[1]
        loss = fused_linear_cross_entropy(h.reshape(n, -1), wte,
                                          labels.reshape(-1))
    else:
        logits = (h @ wte.T).astype(jnp.float32)
        logp = jax.nn.log_softmax(
            logits.reshape(-1, logits.shape[-1]), axis=-1)
        li = labels.reshape(-1).astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, li[:, None], axis=-1)[:, 0]
    if loss_mask is not None:
        m = loss_mask.reshape(-1).astype(jnp.float32)
        return (loss * m).sum() / m.sum()
    return loss.mean()


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        winit = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        self.qkv_proj = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, weight_attr=winit,
            gather_output=False)
        self.out_proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, weight_attr=winit,
            input_is_parallel=True)
        self.attn_drop_p = cfg.attention_dropout
        self.resid_drop = nn.Dropout(cfg.hidden_dropout)

    def forward(self, x, cache=None):
        B, S = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)                       # [B, S, 3H] (mp-sharded)
        # split the columns first, then view each third by heads: a
        # [B, S, 3, nh, dh] view of the whole makes XLA keep q, k and v
        # sequence-minor and relay them for every consumer that is not
        heads = [B, S, self.num_heads, self.head_dim]
        q, k, v = (t.reshape(heads) for t in qkv.split(3, axis=-1))
        if cache == INIT_CACHE:
            # prime an empty cache WITHOUT a zero-length [B, 0, ...] tensor:
            # concat-with-empty is a no-op anyway
            cache = (k, v)
        elif cache is not None:
            pk, pv = cache
            k = paddle.concat([pk, k], axis=1)
            v = paddle.concat([pv, v], axis=1)
            cache = (k, v)
        drop = self.attn_drop_p if self.training else 0.0
        if self.cfg.seq_parallel and cache is None:
            # one authoritative gate (raises on misconfiguration rather than
            # silently gathering full K/V): F.sequence_parallel_attention
            out = F.sequence_parallel_attention(
                q, k, v, is_causal=True, impl=self.cfg.sp_attention,
                dropout_p=drop, training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, dropout_p=drop, is_causal=True,
                training=self.training)
        out = out.reshape([B, S, -1])
        out = self.out_proj(out)
        out = self.resid_drop(out)
        return out if cache is None else (out, cache)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        winit = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        self.fc_in = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size,
                                          weight_attr=winit, gather_output=False)
        self.fc_out = RowParallelLinear(cfg.intermediate_size, cfg.hidden_size,
                                        weight_attr=winit, input_is_parallel=True)
        self.drop = nn.Dropout(cfg.hidden_dropout)

    def forward(self, x):
        return self.drop(self.fc_out(F.gelu(self.fc_in(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        if cfg.recompute_granularity not in ("full", "mlp", "mlp_up"):
            raise ValueError(
                f"recompute_granularity={cfg.recompute_granularity!r}: "
                "expected 'full', 'mlp', or 'mlp_up'")
        self.cfg = cfg
        self.ln_1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = GPTMLP(cfg)

    def forward(self, x, cache=None):
        if cache is None:
            x = x + self.attn(self.ln_1(x))
        else:
            a, cache = self.attn(self.ln_1(x), cache)
            x = x + a
        gran = self.cfg.recompute_granularity
        if (gran in ("mlp", "mlp_up") and self.training
                and cache is None and not self.cfg.recompute):
            from paddle_tpu.distributed.fleet.recompute import recompute
            if gran == "mlp":
                x = x + recompute(lambda t: self.mlp(self.ln_2(t)), x)
            else:
                # remat only up-proj+gelu: bwd re-runs ONE matmul instead of
                # reloading the [N, 4H] intermediate from HBM
                m = self.mlp
                g = recompute(
                    lambda t: F.gelu(m.fc_in(t), approximate=True),
                    self.ln_2(x))
                x = x + m.drop(m.fc_out(g))
        else:
            x = x + self.mlp(self.ln_2(x))
        x = _sp_constrain(x, self.cfg)
        return x if cache is None else (x, cache)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        winit = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                          weight_attr=winit)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                weight_attr=winit)
        self.drop = nn.Dropout(cfg.hidden_dropout)
        self.h = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids=None, caches=None):
        S = input_ids.shape[1]
        if position_ids is None:
            past = (0 if caches is None or caches == INIT_CACHE
                    else caches[0][0].shape[1])
            position_ids = paddle.arange(past, past + S, dtype="int64")
            position_ids = position_ids.unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        x = _sp_constrain(x, self.cfg)
        if caches == INIT_CACHE:
            caches = [INIT_CACHE] * len(self.h)
        new_caches = [] if caches is not None else None
        use_remat = self.cfg.recompute and self.training and caches is None
        for i, block in enumerate(self.h):
            if caches is None:
                if use_remat:
                    from paddle_tpu.distributed.fleet.recompute import recompute
                    x = recompute(block, x)
                else:
                    x = block(x)
            else:
                x, c = block(x, caches[i])
                new_caches.append(c)
        x = self.ln_f(x)
        return x if caches is None else (x, new_caches)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        # every parameter is drawn by an eager op of its own: 0.8 s of a
        # warm start at gpt2-medium's 292, 11 s of a cold one (PERF.md,
        # PR 38), also where the caller then writes its own weights over them
        with metrics.span("model.init:GPTForCausalLM", cat="startup",
                          layers=cfg.num_layers):
            self.gpt = GPTModel(cfg)

    def engine_family(self):
        """What `DecodeEngine` takes from this model (inference/family.py):
        this module's step functions over the state_dict's arrays stacked
        by layer (`serving_params`: the engine's own copy of the block
        weights), one page-pool row per layer, and no state beside the
        pool."""
        import sys
        from paddle_tpu.inference.family import ModelFamily
        cfg = self.cfg

        def quantize(params, weight_dtype):
            from paddle_tpu.quantization.serving import quantize_gpt_params
            return quantize_gpt_params(params, weight_dtype)
        return ModelFamily(
            name="gpt", steps=sys.modules[__name__],
            params=lambda m: serving_params(m.state_dict()),
            table_key="gpt.wte.weight", kv_layers=cfg.num_layers,
            kv_heads=cfg.num_heads,
            head_dim=cfg.hidden_size // cfg.num_heads,
            max_positions=cfg.max_position_embeddings,
            quantize=quantize)

    def forward(self, input_ids, labels=None, loss_mask=None):
        h = self.gpt(input_ids)
        # tied lm head: logits = h @ wte^T (vocab-sharded over mp like the
        # reference's parallel lm head + ParallelCrossEntropy); the
        # fused-vs-dense choice is registry-routed (kernels/registry.py)
        use_fused = (labels is not None
                     and _fused_ce_impl(self.cfg) == "fused")
        if use_fused:
            from paddle_tpu.core.autograd import apply
            from paddle_tpu.kernels.fused_ce import fused_linear_cross_entropy
            n = h.shape[0] * h.shape[1]
            loss = apply(
                lambda hh, ww, ll: fused_linear_cross_entropy(
                    hh.reshape(n, -1), ww, ll.reshape(-1)),
                h, self.gpt.wte.weight, labels,
                op_name="fused_linear_cross_entropy")
            logits = None
        else:
            logits = paddle.matmul(h, self.gpt.wte.weight, transpose_y=True)
        if labels is None:
            return logits
        if not use_fused:
            loss = F.cross_entropy(
                logits.reshape([-1, self.cfg.vocab_size]).astype("float32"),
                labels.reshape([-1]), reduction="none")
        if loss_mask is not None:
            m = loss_mask.reshape([-1]).astype("float32")
            loss = (loss * m).sum() / m.sum()
        else:
            loss = loss.mean()
        return logits, loss

    @paddle.no_grad()
    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, seed=0):
        """Greedy/sampled decode with KV caches — EAGER loop (one dispatch
        per token, growing cache shapes). Debug/reference path; production
        decode should use :meth:`fast_generate` (single compiled program,
        identical output).

        Sampling runs the SAME sampler as `fast_generate` (temperature
        before the top-k mask, one key split per token from
        ``PRNGKey(seed)``), so a shared seed reproduces identical tokens on
        both paths — parity-tested in tests/test_models.py. The old
        `paddle.multinomial` draw was nondeterministic w.r.t. this seed and
        masked AFTER softmax, which silently disagreed with the compiled
        path."""
        self.eval()
        x = input_ids
        caches = None
        out_ids = [x]
        cur = x
        sample = _make_sampler(float(temperature), int(top_k))
        key = jax.random.PRNGKey(seed)
        for _ in range(max_new_tokens):
            if caches is None:
                h, caches = self.gpt(cur, caches=INIT_CACHE)
            else:
                h, caches = self.gpt(cur, caches=caches)
            logits = paddle.matmul(h[:, -1], self.gpt.wte.weight,
                                   transpose_y=True)
            nxt_arr, key = sample(logits._data.astype(jnp.float32), key)
            nxt = paddle.Tensor(nxt_arr[:, None].astype(x._data.dtype),
                                _internal=True)
            out_ids.append(nxt)
            cur = nxt
        return paddle.concat(out_ids, axis=1)

    @paddle.no_grad()
    def fast_generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                      top_k=0, seed=0):
        """TPU-native autoregressive decode: ONE compiled program.

        `generate` re-dispatches per token with GROWING cache shapes — on
        TPU every step recompiles (shapes changed) and pays the dispatch
        round-trip, so decode runs at Python speed. This path is the
        XLA-idiomatic design (the role the reference fills with fused
        decoding kernels, `incubate/nn/FusedMultiTransformer` /
        `fused_multi_transformer_op.cu`): prefill AND the decode loop live
        in one jitted program — a STATIC [B, S0+N, H, Dh] KV cache written
        in place per step (`dynamic_update_slice`), the loop as
        `lax.scan`, sampling (greedy / temperature / top-k) inside the
        scan with a threaded PRNG key. Greedy output is parity-tested
        against `generate` (tests/test_models.py).

        The compiled executable is cached per (B, S0, N, temperature,
        top_k, dtype) signature; weights enter as explicit inputs, so
        training between calls does NOT stale the cache."""
        self.eval()
        cfg = self.cfg
        B, S0 = int(input_ids.shape[0]), int(input_ids.shape[1])
        N = int(max_new_tokens)
        if N < 1:
            return input_ids
        L = S0 + N
        if L > cfg.max_position_embeddings:
            raise ValueError(
                f"fast_generate: prompt {S0} + max_new_tokens {N} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings} — "
                "positions past the table would silently clamp")
        nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        nl = cfg.num_layers
        params = serving_params(self.state_dict())
        cdtype = params["gpt.wte.weight"].dtype

        sig = (B, S0, N, float(temperature), int(top_k), str(cdtype))
        cache = getattr(self, "_fast_decode_cache", None)
        if cache is None:
            cache = self._fast_decode_cache = {}
        if sig not in cache and len(cache) >= 8:
            # bound the per-model executable cache: serving loops with
            # naturally varying prompt lengths should BUCKET/pad S0; this
            # eviction (oldest-first) keeps the worst case from growing
            # without bound
            cache.pop(next(iter(cache)))
        jitted = cache.get(sig)
        compiled_now = jitted is None
        if jitted is None:
            scale = 1.0 / (dh ** 0.5)
            sample = _make_sampler(float(temperature), int(top_k))

            def run(p, ids, key_data):
                key = jax.random.wrap_key_data(key_data)
                kc = jnp.zeros((nl, B, L, nh, dh), cdtype)
                vc = jnp.zeros((nl, B, L, nh, dh), cdtype)

                # ---- prefill: full causal pass over the prompt, filling
                # the cache prefix (dense f32-softmax attention — the
                # inference shapes are small; decode reuses the same math)
                x = p["gpt.wte.weight"][ids] + \
                    p["gpt.wpe.weight"][None, :S0]          # [B, S0, H]
                cmask = jnp.tril(jnp.ones((S0, S0), bool))
                causal = _causal_attend(scale, cmask, x.dtype)

                def at(i, pos=0):
                    # position ``pos`` of layer ``i`` of the dense cache:
                    # indices of one type (the layer's is traced int32)
                    return tuple(jnp.asarray(v, jnp.int32)
                                 for v in (i, 0, pos, 0, 0))

                def attend_prefill(i, q, k, v, kv):
                    kc, vc = kv
                    kc = jax.lax.dynamic_update_slice(kc, k[None], at(i))
                    vc = jax.lax.dynamic_update_slice(vc, v[None], at(i))
                    return causal(i, q, k, v), (kc, vc)

                x, (kc, vc) = _block_stack(p, x, nl, nh, dh, attend_prefill,
                                           (kc, vc))
                logits0 = _final_logits(p, x[:, -1])
                first, key = sample(logits0, key)
                first = first.astype(ids.dtype)

                # ---- decode: lax.scan, one token per step
                def step(carry, t):
                    kc, vc, tok, key = carry
                    pos = S0 + t
                    x = p["gpt.wte.weight"][tok] + \
                        p["gpt.wpe.weight"][pos][None, :]    # [B, H]

                    def attend(i, q, k, v, kv):
                        kc, vc = kv
                        kc = jax.lax.dynamic_update_slice(
                            kc, k[None, :, None], at(i, pos))
                        vc = jax.lax.dynamic_update_slice(
                            vc, v[None, :, None], at(i, pos))
                        sc = jnp.einsum("bhd,blhd->bhl",
                                        q.astype(jnp.float32) * scale,
                                        kc[i].astype(jnp.float32))
                        mask = jnp.arange(L) <= pos
                        sc = jnp.where(mask[None, None], sc, -1e30)
                        pr = jax.nn.softmax(sc, axis=-1)
                        att = jnp.einsum(
                            "bhl,blhd->bhd", pr,
                            vc[i].astype(jnp.float32)).astype(q.dtype)
                        return att, (kc, vc)

                    x, (kc, vc) = _block_stack(p, x, nl, nh, dh, attend,
                                               (kc, vc))
                    logits = _final_logits(p, x)
                    nxt, key = sample(logits, key)
                    nxt = nxt.astype(tok.dtype)
                    return (kc, vc, nxt, key), nxt

                if N == 1:
                    return first[:, None]
                (_, _, _, _), toks = jax.lax.scan(
                    step, (kc, vc, first, key), jnp.arange(N - 1))
                return jnp.concatenate([first[:, None], toks.T], axis=1)

            jitted = jax.jit(run)
            cache[sig] = jitted
            metrics.counter("generate.compile_count").inc()

        key = jax.random.PRNGKey(seed)
        # decode telemetry: the program is monolithic (prefill + scan in one
        # executable), so the host-visible split is the compile call vs the
        # steady call; block_until_ready makes the steady figure real device
        # time (callers consume the tokens immediately anyway).
        # ms/token ≈ decode_seconds / N once N amortizes the prefill.
        t0 = time.perf_counter()
        toks = jitted(params, input_ids._data,
                      jax.random.key_data(key))
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t0
        metrics.counter("generate.calls").inc()
        metrics.counter("generate.tokens").inc(B * N)
        if compiled_now:
            # first execution of this signature: XLA compile dominates
            metrics.histogram("generate.compile_seconds").observe(dt)
        else:
            metrics.histogram("generate.decode_seconds").observe(dt)
            metrics.gauge("generate.tokens_per_s").set(B * N / dt if dt > 0
                                                       else 0.0)
            metrics.add_span("generate.decode", t0, dt, cat="generate")
        return paddle.concat(
            [input_ids, paddle.Tensor(toks, _internal=True)], axis=1)


class GPTEmbeddingPipe(nn.Layer):
    """wte + wpe + dropout as the pipeline's first entry, SHARED with the
    tied LM head (ref `pp_layers.py:520` shared-weight descs). The reference
    all-reduces the shared weight's grad between first/last stages; here both
    uses live in ONE XLA program, so autograd sums the two contributions and
    GSPMD moves whatever bytes the sharding requires — the sync is derived,
    not hand-coded."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        winit = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                          weight_attr=winit)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                weight_attr=winit)
        self.drop = nn.Dropout(cfg.hidden_dropout)

    def forward(self, input_ids):
        S = input_ids.shape[1]
        pos = paddle.arange(0, S, dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        return _sp_constrain(x, self.cfg)


def _lm_head_forward(embed_layer, h):
    """Tied head: logits = h @ wte^T (the SharedLayerDesc forward_func)."""
    return paddle.matmul(h, embed_layer.wte.weight, transpose_y=True)


class GPTForCausalLMPipe(nn.Layer):
    """GPT through PipelineLayer — the flagship pipelined config (ref
    PaddleNLP GPTForCausalLMPipe over `pp_layers.py:209`): tied input/output
    embeddings via SharedLayerDesc, dropout>0 supported inside stages (the
    engine threads per-(stage, micro) functional keys), and the 'pp' axis
    composes with dp/mp/sp on one mesh (stacked block params keep their 'mp'
    sub-shardings; dp/sp ride GSPMD's auto axes through the manual-pp
    shard_map)."""

    def __init__(self, cfg: GPTConfig, num_stages=1, micro_batches=1,
                 seg_method="uniform", num_virtual_pipeline_stages=1):
        super().__init__()
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer, SharedLayerDesc)
        self.cfg = cfg
        descs = [
            SharedLayerDesc("embed", GPTEmbeddingPipe, cfg),
            *[LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)],
            LayerDesc(nn.LayerNorm, cfg.hidden_size),
            SharedLayerDesc("embed", GPTEmbeddingPipe, cfg,
                            forward_func=_lm_head_forward),
        ]
        self.pipeline = PipelineLayer(
            descs, num_stages=num_stages, micro_batches=micro_batches,
            seg_method=seg_method,
            num_virtual_pipeline_stages=num_virtual_pipeline_stages)

    def forward(self, input_ids, labels=None, loss_mask=None):
        logits = self.pipeline(input_ids)
        if labels is None:
            return logits
        loss = F.cross_entropy(
            logits.reshape([-1, self.cfg.vocab_size]).astype("float32"),
            labels.reshape([-1]), reduction="none")
        if loss_mask is not None:
            m = loss_mask.reshape([-1]).astype("float32")
            loss = (loss * m).sum() / m.sum()
        else:
            loss = loss.mean()
        return logits, loss


def gpt2_small(**kwargs):
    return GPTForCausalLM(GPTConfig(**kwargs))


def gpt2_345m(**kwargs):
    cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                    intermediate_size=4096, **kwargs)
    return GPTForCausalLM(cfg)
