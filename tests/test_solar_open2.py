"""Solar Open 2 (``solar_open2``) through the engine's model seam, at the tiny
preset (one period: softmax, linear, linear, linear; hidden 64, 8 query
heads over 2 key-value heads of 8, 4 linear heads of 8, 32 router outputs
of which 4 experts are held, 2 a token; page 4, chunk 8, sequences of some
50 tokens), on the CPU in float32, held to the benchmark's plain reference
(benchmarks/reference/solar_open2.py, which imports nothing of paddle_tpu,
runs the per-channel delta rule as its token recurrence and attends with a
plain masked softmax).

- the step functions' logits, prefill chunks of several sizes then decode
  through the K/V pages, the matrix state and the convolution state,
  against the reference's full forward with the same share of the experts;
  each layer kind alone; controls that fail the tolerance: fp8 arithmetic,
  a scalar decay a head, ``beta`` without its factor 2, a carried state
  dropped at a chunk boundary;
- the kernels: the per-channel chunk form against the token recurrence
  (strong decay, ``beta`` near 2, padding, a channel at 2 nats a token
  through a 512-token chunk), both arms of the per-channel decode update,
  the per-channel path with every channel alike against the scalar path;
- the shares add up: eight chips' routed parts and the shared expert once
  are the uncut layer;
- the seam: twin K and V pools beside two recurrent arrays a linear layer
  and step counts;
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
from paddle_tpu.kernels import deltanet, moe  # noqa: E402
from paddle_tpu.models import solar_open2 as sm  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import solar_open2 as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
# float32 on both sides; what differs is the form (the chunked delta rule
# against the token recurrence, a paged walk against one softmax) and the
# order of sums. The largest sound reading over the cases below is 4e-6 of
# the largest logit: the tolerance is 10x that. The weakest control reads
# 0.03 (beta without its factor 2)
TOL = 4e-5


def ref_config(cfg, held=None):
    """The reference's view of a program configuration: the published
    keys and the share."""
    lo, hi = held or cfg.experts_held
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        gqa_layers=list(cfg.gqa_layers), vocab_size=cfg.vocab_size,
        first_k_dense_replace=0,
        moe_intermediate_size=cfg.moe_intermediate_size,
        router_outputs=cfg.n_routed_experts, n_routed_experts=hi - lo,
        experts_first=lo, num_experts_per_tok=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor, n_shared_experts=1,
        norm_topk_prob=True, use_rope=False, use_gqa_gate=True,
        kda_use_full_proj=False, kda_allow_neg_eigval=True,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        linear_attn_config=dict(
            short_conv_kernel_size=cfg.linear_conv_kernel,
            head_dim=cfg.linear_head_dim, num_heads=cfg.linear_heads,
            num_kv_heads=None),
        rms_norm_eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def tiny():
    cfg = sm.tiny_config()
    # std 0.2: at these widths attention is far from uniform and every
    # part moves the logits by far more than the tolerance
    return cfg, sm.init_params(cfg, seed=7, std=0.2)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=False, inflight=2,
              min_bucket=8)
    kw.update(over)
    return DecodeEngine(sm.SolarOpen2ForCausalLM(cfg, params),
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
    chunk = jax.jit(lambda *a, state, counts: sm.prefill_chunk_step(
        *a, cfg=cfg, state=state, slot=jnp.int32(slot), counts=counts))
    decode = jax.jit(lambda p, ids, cache, act: sm.decode_step(
        p, ids, cache, act, cfg=cfg))
    return chunk, decode


def step_logits(cfg, params, prompt, n_decode, slot=1, chunk=CHUNK):
    """Logits the step functions give for ``prompt`` prefilled in chunks
    and ``n_decode`` greedy tokens decoded, in slot ``slot`` of SLOTS:
    ``[n_decode + 1, V]``, the tokens, and the counts the steps added up.
    The pools and the state start DIRTY: whatever the last sequence left
    must not show."""
    maxp = MAX_SEQ // PAGE
    pages = 1 + SLOTS * maxp
    shape = (len(cfg.softmax_layers), pages, PAGE, cfg.kv_width)
    kc, vc = jnp.full(shape, 2.0, jnp.float32), jnp.full(shape, -1.0,
                                                         jnp.float32)
    state = tuple(jnp.zeros(s, d) + 3.0 for _, _, s, d in
                  sm.state_arrays(cfg, SLOTS, PAGE, jnp.float32))
    counts = jnp.zeros(sm.step_counts(cfg), jnp.int32)
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


@pytest.mark.parametrize("n_prompt,chunk", [(5, 8), (8, 8), (21, 8), (40, 8),
                                            (37, 16), (50, 32)],
                         ids=["short", "one-chunk", "three-chunks-ragged",
                              "five-chunks-full", "chunks-of-16",
                              "chunks-of-32"])
def test_step_logits_match_the_reference(tiny, n_prompt, chunk):
    """Prefill in chunks (the per-channel chunk form with the state and
    the convolutions' inputs carried across chunks, grouped-query attention
    over the pages), then 10 decode steps (the per-channel update, the
    paged walk), against the reference's one full forward with no cache
    and the token recurrence."""
    cfg, params = tiny
    assert _gap(cfg, params, params, _prompt(n_prompt, n_prompt), 10,
                chunk=chunk) < TOL


@pytest.mark.parametrize("kind", ["softmax", "linear"])
def test_each_layer_kind_alone_matches_the_reference(kind):
    """A stack of ONE kind of layer (two of them), so that neither kind can
    hide behind the other: gated no-position grouped-query attention over
    pages; the per-channel delta rule with its convolutions and gates."""
    cfg = sm.tiny_config(num_layers=2,
                         gqa_layers=(0, 1) if kind == "softmax" else ())
    params = sm.init_params(cfg, seed=3, std=0.2)
    assert _gap(cfg, params, params, _prompt(29, 8), 8) < TOL


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
    hit = n + sm._HIT_PREFILL
    assert np.delete(c_whole, hit).tolist() == np.delete(c_parts, hit).tolist()
    assert 0 < c_whole[hit] < c_parts[hit]
    # (query, key) pairs of the one softmax layer; live tokens through the
    # three linear layers: the prompt's by chunks, the 4 decoded by steps
    n_lin = len(cfg.linear_layers)
    assert c_parts[n:n + 4].tolist() == [
        sum(t + 1 for t in range(37, 41)), sum(t + 1 for t in range(37)),
        4 * n_lin, 37 * n_lin]


def test_every_mechanism_moves_the_logits(tiny):
    """The tolerance means something only if each part shows: zeroing one
    leaf moves the logits by far more."""
    cfg, params = tiny
    prompt = _prompt(21, 3)
    for leaf in ("L0.a.o", "L0.a.gate", "L1.d.out", "L3.d.conv", "L2.d.fb",
                 "L2.d.b", "L1.d.gb", "L0.f.w2", "L3.f.w2",
                 "L3.f.shared.w2"):
        broken = dict(params, **{leaf: jnp.zeros_like(params[leaf])})
        assert _gap(cfg, params, broken, prompt, 6) > 25 * TOL, leaf
    # and a router's bias that decides the routing alone
    broken = dict(params, **{"L1.f.bias": jnp.arange(
        cfg.n_routed_experts, dtype=jnp.float32)})
    assert _gap(cfg, params, broken, prompt, 6) > 25 * TOL


@pytest.mark.parametrize("control", ["fp8", "scalar_decay", "beta_half",
                                     "drop_state"])
def test_a_model_one_step_off_fails_the_tolerance(tiny, control):
    """What the comparison is for. Against the sound program: the
    reference with every product's operands in fp8; with each head's
    channel decays replaced by their mean (the scalar-gated rule); with
    ``beta = sigmoid`` (no factor 2); with the linear layers' carried state
    (matrix and convolution inputs) forgotten at the prompt's last chunk
    boundary."""
    cfg, params = tiny
    gap = _gap(cfg, params, params, _prompt(40, 5), 10, precision=control)
    assert gap > 500 * TOL, gap


# ------------------------------------------------------------ the kernels

def _recurrence(s0, g, beta, q, k, v):
    """The per-channel delta rule a token at a time in float64: (state, o
    [T, H, dv]); g [T, H, dk]."""
    s, outs = np.asarray(s0, np.float64), []
    for t in range(len(g)):
        s = np.exp(g[t].astype(np.float64))[:, :, None] * s
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + k[t][:, :, None] * u[:, None, :]
        outs.append(np.einsum("hkv,hk->hv", s, q[t]))
    return s, np.stack(outs)


def _kda_case(t, h=4, dk=8, dv=8, seed=0, strong=()):
    """Keys correlated with their neighbours, as the convolution leaves
    them; log decays a channel between -1e-3 and -1.6, ``strong`` (head,
    channel) pairs at 2 nats a token; ``beta`` in (0, 2), half of them over
    1.8."""
    rs = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    k = rs.randn(t, h, dk)
    k = unit(k + 0.8 * np.roll(k, 1, axis=0)).astype(np.float32)
    g = -np.exp(rs.uniform(np.log(1e-3), np.log(1.6), (t, h, dk)))
    for hd, ch in strong:
        g[:, hd, ch] = -2.0
    return dict(
        g=g.astype(np.float32),
        beta=(2 / (1 + np.exp(-rs.randn(t, h) - 2.5))).astype(np.float32),
        q=unit(rs.randn(t, h, dk)).astype(np.float32), k=k,
        v=rs.randn(t, h, dv).astype(np.float32),
        s0=rs.randn(h, dk, dv).astype(np.float32))


@pytest.mark.parametrize("t,chunk,sub", [(128, 64, 64), (150, 48, 16),
                                         (100, 40, 16), (30, 8, 64)],
                         ids=["divides", "ragged-tail", "sub-does-not-divide",
                              "chunk-under-sub"])
def test_per_channel_chunk_form_is_the_token_recurrence(t, chunk, sub):
    """``t`` tokens in launches of ``chunk`` cut into sub-chunks of ``sub``
    (or taken whole where ``sub`` does not divide the launch), the last
    launch padded past its valid tokens, from a CARRIED state that is not
    zero, in slot 2 of layer 1 of a stack, with decays a channel over three
    orders, two channels at 2 nats a token and ``beta`` up to 2: the
    read-outs and the closing state are the token recurrence's, and nothing
    else of the stack is touched."""
    c = _kda_case(t, strong=((0, 3), (2, 7)))
    assert c["beta"].max() > 1.9
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
    assert np.isfinite(o).all()
    assert np.abs(o - o_want).max() / np.abs(o_want).max() < 5e-6
    assert np.abs(np.asarray(state[1, 2]) - s_want).max() \
        / np.abs(s_want).max() < 5e-6
    assert float(jnp.abs(state[0]).max()) == 0.0
    assert float(jnp.abs(state[1, :2]).max()) == 0.0
    # a fresh chunk reads the slot's old state as zero
    with jax.default_matmul_precision("highest"):
        o, _ = deltanet.deltanet_chunk(
            state, c["g"][:8], c["beta"][:8], c["q"][:8], c["k"][:8],
            c["v"][:8], 2, True, 8, layer=1)
    _, o0 = _recurrence(np.zeros_like(c["s0"]), c["g"][:8], c["beta"][:8],
                        c["q"][:8], c["k"][:8], c["v"][:8])
    assert np.abs(np.asarray(o) - o0).max() < 5e-6


def test_a_channel_at_two_nats_a_token_through_a_512_token_chunk():
    """The published head width, ONE launch of 512 tokens of which 500 are
    valid: a channel that loses 2 nats a token (``e^-1000`` over the chunk,
    ``e^-128`` over a sub-chunk: either would be 0 or infinity in one
    factor) beside channels that hardly decay. Inside a block of 16 rows
    the largest number exponentiated is 32, under `deltanet.MAX_EXP`, and
    the result is the token recurrence's."""
    c = _kda_case(512, h=2, dk=128, dv=128, seed=4, strong=((0, 5), (1, 77)))
    n = 500
    s_want, o_want = _recurrence(c["s0"], *(c[x][:n] for x in
                                            ("g", "beta", "q", "k", "v")))
    state = jnp.zeros((1, 1, 2, 128, 128), jnp.float32).at[0, 0].set(c["s0"])
    with jax.default_matmul_precision("highest"):
        o, state = deltanet.deltanet_chunk(
            state, c["g"], c["beta"], c["q"], c["k"], c["v"], 0, False, n,
            layer=0)
    o = np.asarray(o)[:n]
    assert np.isfinite(o).all() and np.isfinite(np.asarray(state)).all()
    assert 16 * 2.0 < deltanet.MAX_EXP
    assert np.abs(o - o_want).max() / np.abs(o_want).max() < 5e-6
    assert np.abs(np.asarray(state[0, 0]) - s_want).max() \
        / np.abs(s_want).max() < 5e-6


def test_a_decay_past_the_bound_stays_finite():
    """A channel that loses 12 nats a token (192 inside a block of 16, past
    `MAX_EXP`): nothing overflows, a token still reads its own write
    whole, and what is read low are pairs whose weight is under ``e^-12``:
    the result is the recurrence's to that."""
    c = _kda_case(64, h=1, dk=16, dv=8, seed=6)
    c["g"][:, 0, 3] = -12.0
    _, o_want = _recurrence(c["s0"], c["g"], c["beta"], c["q"], c["k"],
                            c["v"])
    state = jnp.zeros((1, 1, 1, 16, 8), jnp.float32).at[0, 0].set(c["s0"])
    with jax.default_matmul_precision("highest"):
        o, state = deltanet.deltanet_chunk(
            state, c["g"], c["beta"], c["q"], c["k"], c["v"], 0, False, 64,
            layer=0)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(state)).all()
    assert np.abs(np.asarray(o) - o_want).max() / np.abs(o_want).max() < 1e-4


def test_every_channel_alike_is_the_scalar_path():
    """The per-channel contract given ONE decay a head on all its channels
    is the scalar contract: the chunk form and both arms of the update."""
    c = _kda_case(96, h=8, dk=128, dv=128, seed=2)
    gs = c["g"][:, :, 0]
    gc = np.broadcast_to(gs[..., None], c["g"].shape)
    state = jnp.zeros((1, 2, 8, 128, 128), jnp.float32).at[0, 1].set(c["s0"])
    with jax.default_matmul_precision("highest"):
        o1, s1 = deltanet.deltanet_chunk(state, gs, c["beta"], c["q"],
                                         c["k"], c["v"], 1, False, 90,
                                         layer=0)
        o2, s2 = deltanet.deltanet_chunk(state, gc, c["beta"], c["q"],
                                         c["k"], c["v"], 1, False, 90,
                                         layer=0)
    assert np.abs(np.asarray(o1 - o2)).max() / np.abs(np.asarray(o1)).max() \
        < 2e-6
    assert np.abs(np.asarray(s1 - s2)).max() < 2e-5
    active = jnp.asarray([True, True])
    for impl in ("xla", "pallas"):
        args = [jnp.asarray(c[n][:2]) for n in ("beta", "q", "k", "v")]
        y1, t1 = deltanet.deltanet_update(s1, jnp.asarray(gs[:2]), *args,
                                          active, layer=0, impl=impl)
        y2, t2 = deltanet.deltanet_update(s1, jnp.asarray(gc[:2]), *args,
                                          active, layer=0, impl=impl)
        np.testing.assert_allclose(y1, y2, atol=1e-6)
        np.testing.assert_allclose(t1, t2, atol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_per_channel_decode_update_is_the_token_recurrence(impl):
    """Five tokens for three slots of which one is dead, at the published
    head widths (the Pallas arm, interpreted here, takes 128-lane tiles):
    the live slots' read-outs and states are the recurrence's, the dead
    slot's state is the same bits, the other layer untouched; the two arms
    are counted apart from the scalar rule's."""
    c = _kda_case(5, h=8, dk=128, dv=128, seed=1, strong=((3, 9),))
    s_want, o_want = _recurrence(c["s0"], c["g"], c["beta"], c["q"], c["k"],
                                 c["v"])
    state = jnp.zeros((2, 3, 8, 128, 128), jnp.float32).at[1, :].set(c["s0"])
    active = jnp.asarray([True, False, True])
    mine = metrics.counter(f"kernel.dispatch.kda_update.{impl}")
    scalar = metrics.counter(f"kernel.dispatch.deltanet_update.{impl}")
    before = mine.value, scalar.value
    outs = []
    for t in range(5):
        args = [jnp.broadcast_to(c[n][t][None], (3,) + c[n][t].shape)
                for n in ("g", "beta", "q", "k", "v")]
        y, state = deltanet.deltanet_update(state, *args, active, layer=1,
                                            impl=impl)
        outs.append(np.asarray(y[2]))
    assert (mine.value, scalar.value) == (before[0] + 5, before[1])
    assert np.abs(np.stack(outs) - o_want).max() / np.abs(o_want).max() < 2e-6
    for slot in (0, 2):
        assert np.abs(np.asarray(state[1, slot]) - s_want).max() < 2e-5
    assert (np.asarray(state[1, 1]) == c["s0"]).all()
    assert float(jnp.abs(state[0]).max()) == 0.0


def test_the_update_arms_agree_per_channel():
    """The XLA arm against the Pallas arm in interpret mode, per channel,
    ``beta`` near 2, a dead slot between live ones."""
    c = _kda_case(4, h=16, dk=128, dv=128, seed=9)
    state = jnp.asarray(np.random.RandomState(1).randn(3, 4, 16, 128, 128),
                        jnp.float32)
    active = jnp.asarray([True, True, False, True])
    args = [jnp.asarray(c[n]) for n in ("g", "beta", "q", "k", "v")]
    yx, sx = deltanet.deltanet_update(state, *args, active, layer=2,
                                      impl="xla")
    yp, sp = deltanet.deltanet_update(state, *args, active, layer=2,
                                      impl="pallas", interpret=True)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(yx)[live], np.asarray(yp)[live],
                               atol=2e-5)
    np.testing.assert_allclose(sx, sp, atol=2e-5)
    assert (np.asarray(sp[2, 2]) == np.asarray(state[2, 2])).all()
    assert (np.asarray(sp[:2]) == np.asarray(state[:2])).all()


@pytest.mark.parametrize("start", [0, 8, 56, 152])
def test_a_long_grouped_prefill_row_is_walked_by_blocks(start, monkeypatch):
    """A grouped chunk over a page row longer than `pa.LONG_ROW` takes its
    keys a block a turn, only as many turns as reach the chunk's last
    position (the pages past it hold numbers that would wreck a sum that
    touched them), and reads what the one-shot form reads; a row no longer
    than `LONG_ROW` keeps the one-shot form."""
    from paddle_tpu.kernels import paged_attention as pa
    rs = np.random.RandomState(0)
    nkv, g, dh, ps, c, per_slot = 2, 4, 8, 4, 8, 40        # 160 positions
    pool = 1 + 3 * per_slot
    k = rs.randn(2, pool, ps, nkv * dh).astype(np.float32)
    v = rs.randn(2, pool, ps, nkv * dh).astype(np.float32)
    row = rs.permutation(np.arange(1, pool))[:per_slot].astype(np.int32)
    q = jnp.asarray(rs.randn(1, c, nkv * g, dh), jnp.float32)
    args = (jnp.asarray(k), jnp.asarray(v), jnp.asarray(row),
            jnp.int32(start), jnp.int32(c), 1)
    one = pa._xla_prefill_attention(q, *args)              # 160 <= LONG_ROW
    assert "while" not in str(jax.make_jaxpr(
        lambda q_: pa._xla_prefill_attention(q_, *args))(q))
    monkeypatch.setattr(pa, "LONG_ROW", 64)
    monkeypatch.setattr(pa, "WALK_BLOCK", 32)
    past = row[(start + c - 1) // 32 * 8 + 8:]     # pages of later turns
    k[1, past], v[1, past] = 1e30, 1e30
    walk = jax.jit(lambda q_, k_, v_: pa._xla_prefill_attention(
        q_, k_, v_, *args[2:]))(q, jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(walk, one, atol=2e-6)
    assert "while" in str(jax.make_jaxpr(
        lambda q_: pa._xla_prefill_attention(q_, *args))(q))


def test_the_shares_add_up(tiny):
    """Eight chips, each with 4 of the 32 routed experts: their routed
    parts and the shared expert ONCE are the uncut reference layer, and the
    program's kernel, either arm, gives each chip's part."""
    cfg, _ = tiny
    e, n = cfg.n_routed_experts, 8
    full = sm.init_params(dataclasses.replace(cfg, experts_held=(0, e)),
                          seed=11, std=0.3)
    p = {k[len("L2.f."):]: v for k, v in full.items()
         if k.startswith("L2.f.")}
    b = jnp.asarray(np.random.RandomState(1).randn(24, cfg.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.sizes(ref_config(cfg, (0, e)))
        shared = ref.gated(b, p["shared.w1"], p["shared.w2"], "f32")
        uncut = ref.experts(b, p, whole, "f32")
        total, alive = shared, 0
        for lo in range(0, e, e // n):
            hi = lo + e // n
            mine = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi])
            part = ref.experts(b, mine, ref.sizes(ref_config(cfg, (lo, hi))),
                               "f32", shared=False)
            total = total + part
            alive += float(jnp.abs(part).max()) > 1e-3
            for arm in ("dense", "grouped") if lo in (0, e - e // n) else ():
                got = moe.routed_experts(
                    b, p["router"], mine["w1"], mine["w2"],
                    top_k=cfg.experts_per_token, held=(lo, hi),
                    scoring="sigmoid", bias=p["bias"],
                    scale=cfg.routed_scaling_factor, impl=arm)
                np.testing.assert_allclose(got, part, atol=5e-6)
    assert alive == n
    np.testing.assert_allclose(total, uncut, atol=5e-6)


# --------------------------------------------------------------- the seam

def test_twin_kv_pools_beside_recurrent_state_of_two_kinds_a_layer():
    """At the published widths and the benchmark's cut (one softmax layer,
    three linear ones): a token costs the pools a K row and a V row of
    1,024 values each (4,096 B in bfloat16) and nothing else; a slot keeps
    13,467,648 B of recurrent state, whatever its length; the gauges say
    so; and what a prefill step returns goes back where it came from:
    pools, then state, in order."""
    cfg = sm.SolarOpen2Config(vocab_size=256, num_layers=4, gqa_layers=(0,),
                              experts_held=(0, 40))
    fam = sm.family(cfg)
    assert fam.page_rows == () and fam.kv_layers == 1
    assert (fam.kv_heads, fam.head_dim) == (8, 128)
    assert fam.step_counts == 40 + 1 + 6
    ecfg = EngineConfig(page_size=16, max_slots=2, max_seq_len=64)
    cache = DeviceCache.allocate(fam, ecfg, 9, jnp.bfloat16)
    assert cache.k.shape == cache.v.shape == (1, 9, 16, 1024)
    assert cache.bytes_per_token == 4096
    assert [a.shape for a in cache.state] == [(3, 2, 64, 128, 128)] \
        + [(2, 3 * 24576)] * 3
    assert all(a.dtype == jnp.float32 for a in cache.state)
    assert metrics.gauge("engine.state_bytes_per_slot").value == 13467648
    assert metrics.gauge("engine.cache_bytes.state").value == 2 * 13467648
    assert metrics.gauge("engine.cache_bytes.paged").value == \
        9 * 16 * 4096
    assert metrics.gauge("engine.cache_bytes.window").value == 0
    k2, v2 = cache.k + 1, cache.v + 2
    s2 = tuple(a + i + 1 for i, a in enumerate(cache.state))
    after = cache.after_prefill(k2, v2, *s2)
    assert after.k is k2 and after.v is v2 and after.state == s2
    view = cache.step_view(jnp.zeros((2, 4), jnp.int32),
                           jnp.zeros(2, jnp.int32))
    assert view["state"] == cache.state


def test_the_published_model_counts_to_its_name():
    """`leaf_shapes` at the published sizes, by parts: 250.29B, 14.74B
    active (the name's 250B-A15B), and the benchmark's cut 3,308,377,920."""
    def total(c):
        return sum(int(np.prod(s)) for s in sm.leaf_shapes(c).values())
    one = sm.SolarOpen2Config(num_layers=1, gqa_layers=(0,),
                              experts_held=(0, 1), vocab_size=1)
    lin = dataclasses.replace(one, gqa_layers=())
    ends = 2 * 4096 + 4096
    expert = 3 * 4096 * 1280
    assert total(one) - ends - expert == 126_099_776
    assert total(lin) - ends - expert == 154_788_352
    whole = 36 * 154_788_352 + 12 * 126_099_776 + 48 * 320 * expert \
        + 2 * 196608 * 4096 + 4096
    assert round(whole / 1e9, 2) == 250.29
    active = whole - 48 * 312 * expert
    assert round(active / 1e9, 2) == 14.74
    cut = sm.SolarOpen2Config(vocab_size=24576, num_layers=4, gqa_layers=(0,),
                              experts_held=(0, 40))
    assert total(cut) == 3_308_377_920


# -------------------------------------------------------------- the engine

_COUNTED = ("engine.moe.assignments", "engine.moe.assignments_held",
            "engine.gqa.pairs.decode", "engine.gqa.pairs.prefill",
            "engine.kda.tokens.decode", "engine.kda.tokens.prefill",
            "engine.moe.experts_hit.decode", "engine.moe.experts_hit.prefill",
            "engine.d2h_transfers", "engine.steps", "engine.prefill_launches",
            "engine.state_resets", "engine.state_carries")
# tiny: 32 router outputs at 2 a token make the dense arm waste 16x, so the
# registry takes ``grouped`` for these few tokens
_BUILT = ("moe_experts.grouped", "paged_attention.xla",
          "prefill_attention.xla", "kda_update.xla", "kda_chunk.xla")


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
    assert eng._fam.name == "solar_open2"
    assert family_of(sm.SolarOpen2ForCausalLM(cfg, params)).name == \
        eng._fam.name
    assert eng.kv_bytes_per_token == 2 * cfg.kv_width * 4
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
    assert grew["engine.moe.assignments"] == \
        computed * cfg.experts_per_token * cfg.num_layers
    held = grew["engine.moe.assignments_held"]
    assert 0.03 < held / grew["engine.moe.assignments"] < 0.3   # 4 of 32
    fed = [len(p) + i for p in prompts for i in range(7 - 1)]
    assert grew["engine.gqa.pairs.decode"] == sum(t + 1 for t in fed)
    assert grew["engine.gqa.pairs.prefill"] == \
        sum(t + 1 for p in prompts for t in range(len(p)))
    n_lin = len(cfg.linear_layers)
    assert grew["engine.kda.tokens.decode"] == len(fed) * n_lin
    assert grew["engine.kda.tokens.prefill"] == \
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
    assert "solar_open2" in str(e.value)


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        sm.tiny_config(experts_held=(30, 34))
    with pytest.raises(ValueError, match="num_heads"):
        sm.tiny_config(num_kv_heads=3)
    with pytest.raises(ValueError, match="gqa_layers"):
        sm.tiny_config(gqa_layers=(7,))
