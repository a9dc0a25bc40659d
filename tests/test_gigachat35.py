"""GigaChat3.5 (``gigachat3_5``) through the engine's model seam, at the tiny
preset (one dense layer and one period: linear, full, linear, linear,
linear; hidden 64, two key heads to four value heads of 8, 4 latent heads,
32 router outputs of which 4 experts are held, 2 a token, the clamp at 1
so that it bites; page 4, chunk 8, sequences of some 50 tokens), on the CPU
in float32, held to the benchmark's plain reference
(benchmarks/reference/gigachat35.py, which imports nothing of paddle_tpu,
runs the delta rule as its token recurrence and attends per head with a
full softmax).

- the step functions' logits, prefill chunks then decode through the latent
  pool, the matrix state and the convolution state, against the
  reference's full forward with the same share of the experts; controls
  that fail the tolerance: fp8 arithmetic, a carried state dropped at a
  chunk boundary, ``beta`` 0, the delta term left out, the clamp left out,
  the half-form rotation at plain ``theta``;
- the kernels: the chunked delta form against the token recurrence at
  boundaries that do and do not divide the sequence, both arms of the
  decode update, the paged absorbed decode against the per-head form, the
  interleaved YaRN rotation, the clamp in both arms of ``moe_experts``;
- the shares add up: eight chips' routed parts and the shared expert once
  are the uncut layer;
- the seam: ONE page part beside two recurrent arrays and step counts;
- the engine: greedy tokens, counts on the tokens' readback, no
  recompilation, every refusal of a model with recurrent state.
"""
import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.inference.cache import DeviceCache  # noqa: E402
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from paddle_tpu.inference.errors import (RecurrentStateUnsupported,  # noqa: E402
                                         from_wire)
from paddle_tpu.inference.family import family_of  # noqa: E402
from paddle_tpu.kernels import deltanet, mla, moe, retention  # noqa: E402
from paddle_tpu.models import gigachat35 as gm  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import gigachat35 as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
# float32 on both sides; what differs is the form (the chunked delta rule
# against the token recurrence, absorbed against per head, a paged walk
# against one softmax) and the order of sums. The largest sound reading
# over the cases below is 5e-6 of the largest logit: the tolerance is 8x
# that. The weakest control reads 0.05 (the clamp), every other over 0.7
TOL = 4e-5


def ref_config(cfg, held=None):
    """The reference's view of a program configuration: the published
    keys and the share."""
    lo, hi = held or cfg.experts_held
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.n_layers,
        full_attention_layers=list(cfg.full_layers),
        vocab_size=cfg.vocab_size, first_k_dense_replace=cfg.first_dense,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        router_outputs=cfg.n_routed_experts, n_routed_experts=hi - lo,
        experts_first=lo, num_experts_per_tok=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        swiglu_limit=cfg.swiglu_limit, n_shared_experts=1, n_group=1,
        norm_topk_prob=True, layernorm_type="pre_post", rope_interleave=True,
        rope_scaling=dict(
            type="yarn", factor=cfg.rope_factor,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            original_max_position_embeddings=cfg.rope_original_max,
            mscale=1, mscale_all_dim=cfg.rope_mscale_all_dim),
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta,
        linear_num_key_heads=cfg.linear_key_heads,
        linear_num_value_heads=cfg.linear_value_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        linear_conv_kernel_dim=cfg.linear_conv_kernel,
        linear_sigmoid_gate_scale=cfg.linear_gate_scale,
        linear_attn_o_norm_eps=cfg.linear_o_norm_eps,
        layernorm_gating_weight=cfg.norm_gate_scale,
        rms_norm_eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def tiny():
    cfg = gm.tiny_config()
    # std 0.2: at these widths attention is far from uniform, the gated
    # MLPs reach their clamp, and every part moves the logits by far more
    # than the tolerance
    return cfg, gm.init_params(cfg, seed=7, std=0.2)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=False, inflight=2,
              min_bucket=8)
    kw.update(over)
    return DecodeEngine(gm.GigaChat35ForCausalLM(cfg, params),
                        EngineConfig(**kw))


def _reference_logits(cfg, params, ids, precision="f32", drop_at=None):
    """Every sequence padded to MAX_SEQ (causal: the tail is inert), so the
    reference compiles once a precision."""
    padded = np.zeros(MAX_SEQ, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(ref.logits(params, jnp.asarray(padded), ref_config(cfg),
                                 precision, drop_at=drop_at))[:len(ids)]


@functools.lru_cache(maxsize=None)
def _steps(cfg, slot):
    chunk = jax.jit(lambda *a, state, counts: gm.prefill_chunk_step(
        *a, cfg=cfg, state=state, slot=jnp.int32(slot), counts=counts))
    decode = jax.jit(lambda p, ids, cache, act: gm.decode_step(
        p, ids, cache, act, cfg=cfg))
    return chunk, decode


def step_logits(cfg, params, prompt, n_decode, slot=1, chunk=CHUNK):
    """Logits the step functions give for ``prompt`` prefilled in chunks
    and ``n_decode`` greedy tokens decoded, in slot ``slot`` of SLOTS:
    ``[n_decode + 1, V]``, the tokens, and the counts the steps added up.
    The pool and the state start DIRTY: whatever the last sequence left
    must not show."""
    maxp = MAX_SEQ // PAGE
    pages = 1 + SLOTS * maxp
    kc = jnp.full((len(cfg.full_layers), pages, PAGE, cfg.latent_width), 2.0,
                  jnp.float32)
    vc = jnp.zeros((0, 1, PAGE, 0), jnp.float32)
    state = tuple(jnp.zeros(s, d) + 3.0 for _, _, s, d in
                  gm.state_arrays(cfg, SLOTS, PAGE, jnp.float32))
    counts = jnp.zeros(gm.step_counts(cfg), jnp.int32)
    row = np.arange(1 + slot * maxp, 1 + (slot + 1) * maxp, dtype=np.int32)
    table = np.zeros((SLOTS, maxp), np.int32)
    table[slot] = row
    chunk_fn, decode = _steps(cfg, slot)
    for start in range(0, len(prompt), chunk):
        ids = np.zeros(chunk, np.int32)
        part = prompt[start:start + chunk]
        ids[:len(part)] = part
        lg, kc, vc, *state, counts = chunk_fn(
            params, jnp.asarray(ids), jnp.int32(start), jnp.int32(len(part)),
            jnp.asarray(row), kc, vc, state=tuple(state), counts=counts)
    out, toks = [np.asarray(lg)], []
    active = np.zeros(SLOTS, bool)
    active[slot] = True
    length = len(prompt)
    for _ in range(n_decode):
        toks.append(int(out[-1].argmax()))
        ids = np.zeros(SLOTS, np.int32)
        ids[slot] = toks[-1]
        lengths = np.zeros(SLOTS, np.int32)
        lengths[slot] = length
        cache = dict(k_pages=kc, v_pages=vc, page_table=jnp.asarray(table),
                     lengths=jnp.asarray(lengths), state=tuple(state),
                     counts=counts)
        lg, cache = decode(params, jnp.asarray(ids), cache,
                           jnp.asarray(active))
        kc, vc, state, counts = (cache["k_pages"], cache["v_pages"],
                                 cache["state"], cache["counts"])
        out.append(np.asarray(lg[slot]))
        length += 1
    return np.stack(out), toks, np.asarray(counts)


def _gap(cfg, params, prog_params, prompt, n_decode, prog_cfg=None,
         precision="f32", chunk=CHUNK):
    """Largest |logit| difference between the program's prefill-then-decode
    logits and the reference's full forward over the same tokens, as a
    share of the reference's largest |logit|. A ``drop_state`` control
    forgets the state at the prompt's last chunk boundary."""
    with jax.default_matmul_precision("highest"):
        got, toks, _ = step_logits(prog_cfg or cfg, prog_params, prompt,
                                   n_decode, chunk=chunk)
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = _reference_logits(cfg, params, ids, precision,
                             (len(prompt) - 1) // chunk * chunk)
    want = want[len(prompt) - 1:]
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("n_prompt", [5, 8, 21, 40],
                         ids=["short", "one-chunk", "three-chunks-ragged",
                              "five-chunks-full"])
def test_step_logits_match_the_reference(tiny, n_prompt):
    """Prefill in chunks of 8 (the delta rule's chunked form with the state
    and the convolution's inputs carried across chunks, latent attention
    per head over the pages), then 10 decode steps (the delta update, the
    paged absorbed form), against the reference's one full forward with no
    cache and the token recurrence."""
    cfg, params = tiny
    assert _gap(cfg, params, params, _prompt(n_prompt, n_prompt), 10) < TOL


def test_chunked_prefill_is_unchunked_prefill(tiny):
    """One bucket of 64 against chunks of 8: the same logits and the same
    counts, but the experts a chunk hit, which are counted a CALL."""
    cfg, params = tiny
    prompt = _prompt(37, 2)
    with jax.default_matmul_precision("highest"):
        whole, _, c_whole = step_logits(cfg, params, prompt, 4, chunk=64)
        parts, _, c_parts = step_logits(cfg, params, prompt, 4)
    assert np.abs(whole - parts).max() / np.abs(whole).max() < TOL
    n = cfg.n_held + 1
    hit = n + gm._HIT_PREFILL
    assert np.delete(c_whole, hit).tolist() == np.delete(c_parts, hit).tolist()
    assert 0 < c_whole[hit] < c_parts[hit]
    # (query, key) pairs of the one full layer; live tokens through the
    # four linear layers: the prompt's by chunks, the 4 decoded by steps
    n_lin = len(cfg.linear_layers)
    assert c_parts[n:n + 4].tolist() == [
        sum(t + 1 for t in range(37, 41)), sum(t + 1 for t in range(37)),
        4 * n_lin, 37 * n_lin]


def test_every_mechanism_moves_the_logits(tiny):
    """The tolerance means something only if each part shows: zeroing one
    leaf moves the logits by far more."""
    cfg, params = tiny
    prompt = _prompt(21, 3)
    for leaf in ("L0.d.out", "L1.a.o", "L1.a.gate", "L3.d.conv", "L2.d.ba",
                 "L0.f.w2", "L3.f.w2", "L3.f.shared.w2"):
        broken = dict(params, **{leaf: jnp.zeros_like(params[leaf])})
        assert _gap(cfg, params, broken, prompt, 6) > 50 * TOL, leaf
    # and a router's bias that decides the routing alone
    broken = dict(params, **{"L1.f.bias": jnp.arange(
        cfg.n_routed_experts, dtype=jnp.float32)})
    assert _gap(cfg, params, broken, prompt, 6) > 50 * TOL


@pytest.mark.parametrize("control", ["fp8", "drop_state", "beta0",
                                     "no_delta", "no_clamp", "half_rope"])
def test_a_model_one_step_off_fails_the_tolerance(tiny, control):
    """What the comparison is for. Against the sound program: the
    reference with every product's operands in fp8; with the linear
    layers' carried state (matrix and convolution inputs) forgotten at the
    prompt's last chunk boundary; with ``beta`` forced to 0; with the read
    of the state by the key left out of the write; with ``swiglu_limit``
    left out; with the half-form rotation at plain ``theta``."""
    cfg, params = tiny
    gap = _gap(cfg, params, params, _prompt(40, 5), 10, precision=control)
    assert gap > 1000 * TOL, gap


# ------------------------------------------------------------ the kernels

def _recurrence(s0, g, beta, q, k, v):
    """The delta rule a token at a time in float64: (state, o [T, H, dv])."""
    s, outs = np.asarray(s0, np.float64), []
    for t in range(len(g)):
        s = np.exp(g[t])[:, None, None] * s
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + k[t][:, :, None] * u[:, None, :]
        outs.append(np.einsum("hkv,hk->hv", s, q[t]))
    return s, np.stack(outs)


def _delta_case(t, h=4, dk=8, dv=8, seed=0):
    rs = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    # neighbouring keys correlated, as the convolution leaves them
    k = rs.randn(t, h, dk)
    k = unit(k + 0.8 * np.roll(k, 1, axis=0)).astype(np.float32)
    return dict(
        g=(-np.abs(rs.randn(t, h)) * 0.3).astype(np.float32),
        beta=(1 / (1 + np.exp(-rs.randn(t, h)))).astype(np.float32),
        q=unit(rs.randn(t, h, dk)).astype(np.float32), k=k,
        v=rs.randn(t, h, dv).astype(np.float32),
        s0=rs.randn(h, dk, dv).astype(np.float32))


@pytest.mark.parametrize("t,chunk,sub", [(128, 64, 16), (150, 48, 16),
                                         (100, 40, 16), (30, 8, 64)],
                         ids=["divides", "ragged-tail", "sub-does-not-divide",
                              "chunk-under-sub"])
def test_chunked_delta_form_is_the_token_recurrence(t, chunk, sub):
    """``t`` tokens in launches of ``chunk`` cut into sub-chunks of ``sub``
    (or taken whole where ``sub`` does not divide the launch), the last
    launch padded past its valid tokens, from a CARRIED state that is not
    zero, in slot 2 of layer 1 of a stack: the read-outs and the closing
    state are the token recurrence's, and nothing else of the stack is
    touched."""
    c = _delta_case(t)
    s_want, o_want = _recurrence(c["s0"], c["g"], c["beta"], c["q"], c["k"],
                                 c["v"])
    state = jnp.zeros((2, 3, 4, 8, 8), jnp.float32).at[1, 2].set(c["s0"])
    outs = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, t, chunk):
            n = min(chunk, t - start)

            def part(x):
                x = x[start:start + n]
                return np.concatenate(
                    [x, np.ones((chunk - n,) + x.shape[1:], x.dtype)])
            o, state = deltanet.deltanet_chunk(
                state, part(c["g"]), part(c["beta"]), part(c["q"]),
                part(c["k"]), part(c["v"]), 2, False, n, layer=1, sub=sub)
            outs.append(np.asarray(o)[:n])
    o = np.concatenate(outs)
    assert np.abs(o - o_want).max() / np.abs(o_want).max() < 2e-6
    assert np.abs(np.asarray(state[1, 2]) - s_want).max() \
        / np.abs(s_want).max() < 2e-6
    assert float(jnp.abs(state[0]).max()) == 0.0
    assert float(jnp.abs(state[1, :2]).max()) == 0.0
    # a fresh chunk reads the slot's old state as zero
    with jax.default_matmul_precision("highest"):
        o, _ = deltanet.deltanet_chunk(
            state, c["g"][:8], c["beta"][:8], c["q"][:8], c["k"][:8],
            c["v"][:8], 2, True, 8, layer=1)
    _, o0 = _recurrence(np.zeros_like(c["s0"]), c["g"][:8], c["beta"][:8],
                        c["q"][:8], c["k"][:8], c["v"][:8])
    assert np.abs(np.asarray(o) - o0).max() < 2e-6


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_delta_decode_update_is_the_token_recurrence(impl):
    """Five tokens for three slots of which one is dead, at the published
    head widths (the Pallas arm, interpreted here, takes 128-lane tiles):
    the live slots' read-outs and states are the recurrence's, the dead
    slot's state is the same bits, the other layer untouched."""
    c = _delta_case(5, h=8, dk=128, dv=128, seed=1)
    s_want, o_want = _recurrence(c["s0"], c["g"], c["beta"], c["q"], c["k"],
                                 c["v"])
    state = jnp.zeros((2, 3, 8, 128, 128), jnp.float32).at[1, :].set(c["s0"])
    active = jnp.asarray([True, False, True])
    outs = []
    for t in range(5):
        args = [jnp.broadcast_to(c[n][t][None], (3,) + c[n][t].shape)
                for n in ("g", "beta", "q", "k", "v")]
        y, state = deltanet.deltanet_update(state, *args, active, layer=1,
                                            impl=impl)
        outs.append(np.asarray(y[2]))
    assert np.abs(np.stack(outs) - o_want).max() / np.abs(o_want).max() < 2e-6
    for slot in (0, 2):
        assert np.abs(np.asarray(state[1, slot]) - s_want).max() < 2e-5
    assert (np.asarray(state[1, 1]) == c["s0"]).all()
    assert float(jnp.abs(state[0]).max()) == 0.0


def test_the_paged_absorbed_decode_is_the_per_head_form():
    """Four slots with contexts of 1, 9, 30 and 0 (dead) tokens scattered
    over a pool's pages, walked 8 keys at a time with a trip count by the
    longest: the absorbed form's mix through each head's W_uv is the
    per-head softmax attention over the expanded keys and values."""
    rs = np.random.RandomState(3)
    h, r, dr, dn, dv, ps, w = 4, 8, 4, 8, 8, 4, 16
    lens = [1, 9, 30, 0]
    maxp = 8
    pool = np.zeros((2, 1 + 4 * maxp, ps, w), np.float32)
    table = np.zeros((4, maxp), np.int32)
    pages = rs.permutation(np.arange(1, 1 + 4 * maxp)).reshape(4, maxp)
    rows = []
    for b, n in enumerate(lens):
        table[b] = pages[b]
        lat = rs.randn(32, r + dr).astype(np.float32)
        rows.append(lat)
        for t in range(32):          # rows past the length are garbage
            pool[1, pages[b, t // ps], t % ps, :r + dr] = lat[t]
    pool[1, 0] = 7.0                 # the trash page is never attended
    w_ukv = rs.randn(r, h, dn + dv).astype(np.float32)
    q_nope = rs.randn(4, h, dn).astype(np.float32)
    q_rope = rs.randn(4, h, dr).astype(np.float32)
    q_abs = np.einsum("bhd,chd->bhc", q_nope, w_ukv[..., :dn])
    q = np.concatenate([q_abs, q_rope, np.zeros((4, h, w - r - dr))],
                       -1).astype(np.float32)
    qpos = np.asarray([n - 1 for n in lens], np.int32)
    with jax.default_matmul_precision("highest"):
        o_lat = np.asarray(mla.latent_decode_paged(
            jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(table),
            jnp.asarray(qpos), rank=r, scale=0.3, key_block=8))
    got = np.einsum("bhc,chv->bhv", o_lat, w_ukv[..., dn:])
    for b, n in enumerate(lens):
        if n == 0:
            assert np.abs(o_lat[b]).max() == 0.0
            continue
        lat = rows[b][:n]
        kv = np.einsum("sc,chd->shd", lat[:, :r], w_ukv)
        sc = (np.einsum("hd,shd->hs", q_nope[b], kv[..., :dn])
              + np.einsum("hr,sr->hs", q_rope[b], lat[:, r:])) * 0.3
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        want = np.einsum("hs,shv->hv", pr, kv[..., dn:])
        assert np.abs(got[b] - want).max() < 2e-5, b


def test_the_interleaved_yarn_rotation_is_the_references():
    """Pairs (2i, 2i + 1) at YaRN's frequencies: those that turn often
    over the original context keep the plain frequency, those that turn
    seldom have it divided by the factor, a ramp between; a score depends
    on the distance alone; the attention scale carries mscale squared."""
    cfg = gm.GigaChat35Config()
    inv = cfg.inv_freq
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(inv[-4:], plain[-4:] / 8, rtol=1e-6)
    assert (np.diff(inv / plain) <= 1e-7).all() and inv[16] < plain[16]
    s = ref.sizes(dict(ref_config(cfg), num_hidden_layers=4,
                       full_attention_layers=[3]))
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(s)), inv,
                               rtol=1e-6)
    m = 0.1 * np.log(8.0) + 1
    assert abs(cfg.attn_scale - m * m / np.sqrt(192)) < 1e-9
    assert abs(ref.softmax_scale(s) - cfg.attn_scale) < 1e-9
    rs = np.random.RandomState(0)
    x = rs.randn(6, 2, 64).astype(np.float32)
    pos = np.asarray([0, 1, 5, 100, 4000, 30000], np.int32)
    got = np.asarray(retention.rotary_pairs(jnp.asarray(x), jnp.asarray(pos),
                                            inv))
    ang = pos[:, None].astype(np.float64) * inv[None].astype(np.float64)
    a, b = x[..., 0::2], x[..., 1::2]
    c, sn = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.stack([a * c - b * sn, b * c + a * sn], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=5e-3)   # f32 angles at 3e4
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-5)
    # relative: q at 107 against k at 100 is q at 7 against k at 0
    q, k = x[0:1], x[1:2]
    rot = lambda v, p: np.asarray(retention.rotary_pairs(  # noqa: E731
        jnp.asarray(v), jnp.asarray([p], jnp.int32), inv))
    np.testing.assert_allclose((rot(q, 107) * rot(k, 100)).sum(),
                               (rot(q, 7) * rot(k, 0)).sum(), atol=2e-4)


@pytest.mark.parametrize("arm", ["dense", "grouped"])
def test_the_clamp_is_in_both_arms_and_off_by_default(arm):
    """``limit`` clamps the activated half from above and the linear half
    both ways, in either arm, as the reference's gated MLP does; without
    it the call is what it was."""
    rs = np.random.RandomState(4)
    d, f, e = 16, 8, 8
    x = jnp.asarray(3 * rs.randn(12, d), jnp.float32)
    router = jnp.asarray(rs.randn(d, e), jnp.float32)
    w1 = jnp.asarray(rs.randn(4, d, 2 * f), jnp.float32)
    w2 = jnp.asarray(rs.randn(4, f, d), jnp.float32)
    kw = dict(top_k=2, held=(2, 6), scoring="sigmoid", scale=2.5, impl=arm)
    with jax.default_matmul_precision("highest"):
        free = moe.routed_experts(x, router, w1, w2, **kw)
        clamped = moe.routed_experts(x, router, w1, w2, limit=1.5, **kw)
        wide = moe.routed_experts(x, router, w1, w2, limit=1e9, **kw)
        idx, gates = moe.route(x, router, 2, "sigmoid", None, 2.5)
        want = np.zeros((12, d), np.float32)
        for t in range(12):
            for j in range(2):
                ex = int(idx[t, j]) - 2
                if 0 <= ex < 4:
                    u, v = np.split(np.asarray(x[t] @ w1[ex]), 2)
                    u, v = np.minimum(u, 1.5), np.clip(v, -1.5, 1.5)
                    want[t] += float(gates[t, j]) * np.asarray(
                        (u / (1 + np.exp(-u)) * v) @ np.asarray(w2[ex]))
    np.testing.assert_allclose(clamped, want, atol=2e-4)
    np.testing.assert_allclose(wide, free, atol=1e-5)
    assert float(jnp.abs(clamped - free).max()) > 1.0


def test_the_shares_add_up(tiny):
    """Eight chips, each with 4 of the 32 routed experts: their routed
    parts and the shared expert ONCE are the uncut reference layer, and the
    program's kernel, either arm, gives each chip's part."""
    cfg, _ = tiny
    e, n = cfg.n_routed_experts, 8
    full = gm.init_params(dataclasses.replace(cfg, experts_held=(0, e)),
                          seed=11, std=0.3)
    p = {k[len("L2.f."):]: v for k, v in full.items()
         if k.startswith("L2.f.")}
    b = jnp.asarray(np.random.RandomState(1).randn(24, cfg.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.sizes(ref_config(cfg, (0, e)))
        shared = ref.gated(b, p["shared.w1"], p["shared.w2"], whole, "f32")
        uncut = ref.experts(b, p, whole, "f32")
        total, alive = shared, 0
        for lo in range(0, e, e // n):
            hi = lo + e // n
            mine = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi])
            part = ref.experts(b, mine, ref.sizes(ref_config(cfg, (lo, hi))),
                               "f32") - shared
            total = total + part
            alive += float(jnp.abs(part).max()) > 1e-3
            for arm in ("dense", "grouped") if lo in (0, e - e // n) else ():
                got = moe.routed_experts(
                    b, p["router"], mine["w1"], mine["w2"],
                    top_k=cfg.experts_per_token, held=(lo, hi),
                    scoring="sigmoid", bias=p["bias"],
                    scale=cfg.routed_scaling_factor, limit=cfg.swiglu_limit,
                    impl=arm)
                np.testing.assert_allclose(got, part, atol=5e-6)
    assert alive == n
    np.testing.assert_allclose(total, uncut, atol=5e-6)


# --------------------------------------------------------------- the seam

def test_one_page_part_beside_recurrent_state_of_two_kinds_a_layer():
    """At the published widths and the benchmark's cut (one full layer,
    four linear ones): a token costs the pool ONE latent row of 640 values
    (1,280 B in bfloat16; the equations use 576) and nothing else, the
    second pool is empty; a slot keeps 17,563,648 B of recurrent state,
    whatever its length; the gauges say so; and what a prefill step
    returns goes back where it came from: pools, then state, in order."""
    cfg = gm.GigaChat35Config(
        vocab_size=256, layer_types=(gm.LINEAR, gm.FULL) + (gm.LINEAR,) * 3,
        first_dense=1, experts_held=(0, 16))
    fam = gm.family(cfg)
    assert fam.page_rows == (("latent", 640),) and fam.kv_layers == 1
    assert fam.step_counts == 16 + 1 + 6
    ecfg = EngineConfig(page_size=16, max_slots=2, max_seq_len=64)
    cache = DeviceCache.allocate(fam, ecfg, 9, jnp.bfloat16)
    assert cache.k.shape == (1, 9, 16, 640) and cache.v.shape == (0, 1, 16, 0)
    assert cache.bytes_per_token == 1280
    assert [a.shape for a in cache.state] == [(4, 2, 64, 128, 128)] \
        + [(2, 3 * 16384)] * 4
    assert all(a.dtype == jnp.float32 for a in cache.state)
    assert metrics.gauge("engine.state_bytes_per_slot").value == 17563648
    assert metrics.gauge("engine.cache_bytes.state").value == 2 * 17563648
    assert metrics.gauge("engine.cache_bytes.paged.latent").value == \
        9 * 16 * 640 * 2
    assert metrics.gauge("engine.cache_bytes.window").value == 0
    k2, v2 = cache.k + 1, cache.v
    s2 = tuple(a + i + 1 for i, a in enumerate(cache.state))
    after = cache.after_prefill(k2, v2, *s2)
    assert after.k is k2 and after.v is v2 and after.state == s2
    view = cache.step_view(jnp.zeros((2, 4), jnp.int32),
                           jnp.zeros(2, jnp.int32))
    assert view["state"] == cache.state and view["v_pages"].size == 0


# -------------------------------------------------------------- the engine

_COUNTED = ("engine.moe.assignments", "engine.moe.assignments_held",
            "engine.latent.pairs.decode", "engine.latent.pairs.prefill",
            "engine.deltanet.tokens.decode", "engine.deltanet.tokens.prefill",
            "engine.moe.experts_hit.decode", "engine.moe.experts_hit.prefill",
            "engine.d2h_transfers", "engine.steps", "engine.prefill_launches",
            "engine.state_resets", "engine.state_carries")
# tiny: 32 router outputs at 2 a token make the dense arm waste 16x, so the
# registry takes ``grouped`` for these few tokens
_BUILT = ("moe_experts.grouped", "mla_attention.xla", "mla_decode_paged.xla",
          "deltanet_update.xla", "deltanet_chunk.xla", "rotary.xla")


@pytest.fixture(scope="module")
def served(tiny):
    """One warm engine: what building it counted, then three requests of
    different lengths through it."""
    cfg, params = tiny
    built = {k: metrics.counter(f"kernel.dispatch.{k}").value for k in _BUILT}
    eng = _engine(cfg, params)
    eng.warmup(prompt_lens=[5, 9, 37])
    built = {k: metrics.counter(f"kernel.dispatch.{k}").value - v
             for k, v in built.items()}
    prompts = [_prompt(37, 11), _prompt(5, 12), _prompt(20, 13)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    return eng, prompts, reqs, built


def _is_greedy(cfg, params, prompt, req):
    out = np.asarray(req.result())
    assert out[:len(prompt)].tolist() == prompt.tolist()
    lg = _reference_logits(cfg, params, out)[len(prompt) - 1:-1]
    assert lg.argmax(-1).tolist() == out[len(prompt):].tolist()


def test_engine_serves_greedy_tokens_of_the_reference(tiny, served):
    """Three requests of different lengths share the batch (chunked with a
    ragged tail, one-shot, chunked); each one's tokens are the reference's
    greedy continuation of its own prompt."""
    cfg, params = tiny
    eng, prompts, reqs, _ = served
    assert eng._fam.name == "gigachat3_5"
    assert family_of(gm.GigaChat35ForCausalLM(cfg, params)).name == \
        eng._fam.name
    assert eng.kv_bytes_per_token == cfg.latent_width * 4
    for p, r in zip(prompts, reqs):
        _is_greedy(cfg, params, p, r)


def test_a_reused_slot_serves_like_a_fresh_one(tiny, served):
    """Five more requests than slots through the same engine: each starts
    in a slot whose state and pages another sequence left, and each is
    still the reference's greedy continuation of its own prompt."""
    cfg, params = tiny
    eng = served[0]
    resets = metrics.counter("engine.state_resets").value
    prompts = [_prompt(n, 30 + n) for n in (26, 11, 9, 33, 17)]
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        _is_greedy(cfg, params, p, r)
    assert metrics.counter("engine.state_resets").value == resets + 5


def test_counts_ride_the_tokens_readback_and_nothing_recompiles(tiny, served):
    """The routing, pair and token counts of every token the engine
    computed reach the host with the tokens (no readback of their own),
    each new kernel is counted where it is built, and a warm engine
    compiles nothing more whatever joins and retires."""
    cfg, params = tiny
    eng, _, _, built = served
    assert eng._tok_dev.shape == (SLOTS + cfg.n_held + 7,)
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill_chunk"]
    assert all(v > 0 for v in built.values()), built
    n = metrics.counter("engine.compile_count").value
    c0 = {k: metrics.counter(k).value for k in _COUNTED}
    harvests0 = len(metrics.spans("engine.harvest"))
    launches0 = len(metrics.spans("engine.prefill_launch"))
    prompts = [_prompt(37, 41), _prompt(9, 42), _prompt(5, 43)]
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    for _ in range(3):
        eng.step()
    reqs.append(eng.submit(_prompt(16, 99), max_new_tokens=7))
    prompts.append(_prompt(16, 99))
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert metrics.counter("engine.compile_count").value == n
    grew = {k: metrics.counter(k).value - c0[k] for k in _COUNTED}
    # tokens through the stack: every prompt token once, and each generated
    # token but a request's last (sampled and never fed back)
    computed = sum(len(p) + 7 - 1 for p in prompts)
    n_moe = cfg.n_layers - cfg.first_dense
    assert grew["engine.moe.assignments"] == \
        computed * cfg.experts_per_token * n_moe
    held = grew["engine.moe.assignments_held"]
    assert 0.03 < held / grew["engine.moe.assignments"] < 0.3   # 4 of 32
    fed = [len(p) + i for p in prompts for i in range(7 - 1)]
    assert grew["engine.latent.pairs.decode"] == sum(t + 1 for t in fed)
    assert grew["engine.latent.pairs.prefill"] == \
        sum(t + 1 for p in prompts for t in range(len(p)))
    n_lin = len(cfg.linear_layers)
    assert grew["engine.deltanet.tokens.decode"] == len(fed) * n_lin
    assert grew["engine.deltanet.tokens.prefill"] == \
        sum(len(p) for p in prompts) * n_lin
    hit = grew["engine.moe.experts_hit.decode"] \
        + grew["engine.moe.experts_hit.prefill"]
    assert grew["engine.moe.experts_hit.decode"] > 0
    assert grew["engine.moe.experts_hit.prefill"] > 0
    assert hit <= held
    assert grew["engine.d2h_transfers"] == \
        len(metrics.spans("engine.harvest")) - harvests0
    # a sequence's first launch resets its slot's state, the later chunks
    # of its prompt carry it: 37 is five chunks of 8, 9 and 16 two, 5 one
    launches = metrics.spans("engine.prefill_launch")[launches0:]
    carried = [s for s in launches if (s.args or {}).get("state_carried")]
    assert grew["engine.state_resets"] == 4
    assert grew["engine.state_carries"] == len(carried) == 4 + 1 + 0 + 1


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("knob", [dict(prefix_cache=True),
                                  dict(speculate_k=2),
                                  dict(kv_host_tier_bytes=1 << 20),
                                  dict(kv_disk_tier_bytes=1 << 20)],
                         ids=["prefix_cache", "speculate_k", "host_tier",
                              "disk_tier"])
def test_configuration_refuses_what_pages_alone_cannot_restore(tiny, knob):
    cfg, params = tiny
    with pytest.raises(RecurrentStateUnsupported):
        _engine(cfg, params, **knob)


@pytest.mark.parametrize("call", ["prefill_export", "submit_prefill_stream",
                                  "import_request", "submit_import",
                                  "drain_migrate"])
def test_calls_refuse_what_pages_alone_cannot_restore(served, call):
    eng = served[0]
    with pytest.raises(RecurrentStateUnsupported) as e:
        if call == "prefill_export":
            eng.prefill_export(_prompt(9, 1))
        elif call == "submit_prefill_stream":
            eng.submit_prefill_stream(_prompt(9, 1))
        elif call == "import_request":
            eng.import_request(object())
        elif call == "submit_import":
            eng.submit_import(object())
        else:
            eng.drain(migrate=True)
    wire = f"{type(e.value).__name__}: {e.value}"
    assert isinstance(from_wire(wire), RecurrentStateUnsupported)
    assert "gigachat3_5" in str(e.value)


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        gm.tiny_config(experts_held=(30, 34))
    with pytest.raises(ValueError, match="linear_value_heads"):
        gm.tiny_config(linear_key_heads=3)
    with pytest.raises(ValueError, match="layer_types"):
        gm.tiny_config(layer_types=("sliding_attention",))
