"""Brumby (``brumby``) through the engine's model seam, at the tiny preset (3
layers, 4 query heads over 2 key-value heads of 8, so 5 cyclic diagonals;
chunk 16), on the CPU in float32, held to the benchmark's plain reference
(benchmarks/reference/brumby.py: the ATTENTION form, which imports nothing
of paddle_tpu and never forms a state).

- the retention ops: the recurrence (both arms of the decode update), the
  chunked form and the reference's attention form give the same y, with
  chunk boundaries at 1, C - 1, C and C + 1 tokens, a fresh chunk over a
  dirty slot, and gates that forget faster and slower than a chunk; ``phi``
  at the stored layout; every query head reads its own key-value head;
- the step functions' logits, prefilled whole, in chunks and token by token
  (rotary positions continue), against the reference's full forward;
  controls that fail the tolerance (the reference in fp8 and bf16, the state
  kept in bfloat16, positions that restart at a chunk, a dropped gate);
- the engine: greedy tokens, a reused slot, NO page pool (no allocator, no
  page, ``engine.pages_in_use`` 0) beside an unchanged GPT-2 engine, no
  recompilation, every refusal of a model with recurrent state.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.inference import cache as cache_mod  # noqa: E402
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from paddle_tpu.inference.errors import (RecurrentStateUnsupported,  # noqa: E402
                                         from_wire)
from paddle_tpu.kernels import retention as R  # noqa: E402
from paddle_tpu.models import brumby as bm  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import brumby as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 16, 3, 96
# float32 on both sides; what differs is the order of sums (the state form
# against the attention form: a sum over the feature map's terms against a
# squared dot product). The largest sound reading over the cases below is
# 1.6e-6 of the largest logit: the tolerance is 6x that. The weakest control
# (the state kept in bfloat16) reads 1.1e-3
TOL = 1e-5
# the ops alone, on unit-scale q, k, v: y is a weighted mean of v (|y| <= 3).
# The state form computes a weight (q . k)^2 as a sum of signed products, so
# where a token's weights add up to little (the first tokens of a sequence:
# one or two weights, each the square of a small dot product) the division
# amplifies float32's rounding: an error counts in full where the sum of
# weights is 1e-2 or more, and in proportion below. Sound readings: 2e-6
OP_TOL = 2e-5


def ref_config(cfg):
    """The reference's view of a program configuration."""
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        vocab_size=cfg.vocab_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        intermediate_size=cfg.intermediate_size,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        assumed=dict(power=2, retention_eps=cfg.retention_eps))


@pytest.fixture(scope="module")
def tiny():
    cfg = bm.tiny_config()
    # std 0.2: the mixer and the MLP move the logits by far more than the
    # tolerance
    return cfg, bm.init_params(cfg, seed=7, std=0.2)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=False, inflight=2,
              min_bucket=8)
    kw.update(over)
    return DecodeEngine(bm.BrumbyForCausalLM(cfg, params),
                        EngineConfig(**kw))


def _reference_logits(cfg, params, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, jnp.asarray(ids),
                                     ref_config(cfg), precision))


# ------------------------------------------------------------------ the ops

def _qkvg(t, heads, kv, hd, seed, rate):
    """Seeded q, k, v and gates that forget at about ``rate`` a token."""
    r = np.random.RandomState(seed)
    lg = -np.abs(r.randn(t, kv)) * rate
    return (jnp.asarray(r.randn(t, heads, hd), jnp.float32),
            jnp.asarray(r.randn(t, kv, hd), jnp.float32),
            jnp.asarray(r.randn(t, kv, hd), jnp.float32),
            jnp.asarray(lg, jnp.float32))


def _attention_form(q, k, v, lg, eps=1e-6):
    """The reference's step 3, a head at a time, with numpy's float64."""
    q, k, v, lg = (np.asarray(x, np.float64) for x in (q, k, v, lg))
    t, heads, hd = q.shape
    kv = k.shape[1]
    cum = np.cumsum(lg, 0)
    y, den = np.zeros((t, heads, hd)), np.zeros((t, heads))
    for i in range(heads):
        j = i // (heads // kv)
        w = (q[:, i] @ k[:, j].T / hd) ** 2 \
            * np.exp(cum[:, None, j] - cum[None, :, j])
        w = np.tril(w)
        y[:, i] = w @ v[:, j] / (w.sum(-1, keepdims=True) + eps)
        den[:, i] = w.sum(-1)
    return y, np.minimum(1.0, den / 1e-2)[..., None]


def _empty(layers, slots, kv, hd, fill=0.0):
    s, z = R.state_shapes(layers, slots, kv, hd)
    return jnp.full(s, fill, jnp.float32), jnp.full(z, fill, jnp.float32)


def test_phi_squares_the_dot_product_at_the_stored_layout():
    """``phi(u) . phi(w) = (u . w)^2`` over the (hd / 2 + 1) x hd stored
    terms: each pair once, the half-way diagonal's twice at half weight."""
    r = np.random.RandomState(0)
    for hd in (8, 16, 128):
        u, w = (jnp.asarray(r.randn(5, hd), jnp.float32) for _ in "uw")
        pu = R.phi(u)
        assert pu.shape == (5, hd // 2 + 1, hd)
        got = np.asarray((pu * R.phi(w)).sum((-1, -2)), np.float64)
        want = np.asarray((u * w).sum(-1), np.float64) ** 2
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert R.state_shapes(8, 16, 8, 128) == ((8, 16, 8, 65, 128, 128),
                                             (8, 16, 8, 8320))
    with pytest.raises(ValueError, match="even"):
        R.diagonals(7)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("rate", [0.15, 0.005], ids=["fast", "slow"])
def test_the_recurrence_is_the_attention_form(impl, rate):
    """Token by token through the decode update in one slot of three (the
    others inactive and untouched, bit for bit), against the attention
    form; gates that halve the state in two tokens, and in two hundred."""
    t, heads, kv, hd, slot = 21, 4, 2, 8, 2
    q, k, v, lg = _qkvg(t, heads, kv, hd, 3, rate)
    s, z = _empty(2, 3, kv, hd)
    s, z = s.at[:, 0].set(5.0), z.at[:, 0].set(2.0)     # another's state
    act = jnp.asarray([False, False, True])
    step = jax.jit(lambda s, z, lg, q, k, v: R.retention_update(
        s, z, lg, q, k, v, act, layer=jnp.int32(1), impl=impl,
        interpret=True))
    ys = []
    for i in range(t):
        put = lambda x: jnp.zeros((3,) + x.shape[1:]).at[slot].set(x[i])  # noqa
        y, s, z = step(s, z, put(lg), put(q), put(k), put(v))
        ys.append(np.asarray(y[slot]))
    want, weight = _attention_form(q, k, v, lg)
    assert (np.abs(np.stack(ys) - want) * weight).max() <= OP_TOL
    assert float(jnp.abs(s[1, 0] - 5.0).max()) == 0.0
    assert float(jnp.abs(z[1, 0] - 2.0).max()) == 0.0
    assert float(jnp.abs(s[0, 2]).max()) == 0.0         # the other layer


@pytest.mark.parametrize("rate", [0.15, 0.005], ids=["fast", "slow"])
@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 5])
def test_chunks_carry_the_state_the_recurrence_keeps(n, rate):
    """``n`` tokens in chunks of CHUNK (the last one padded with junk that
    must stay inert) from a DIRTY slot whose first chunk is fresh: the
    attention form's y, and the state the token-by-token update ends in."""
    heads, kv, hd, slot = 4, 2, 8, 1
    q, k, v, lg = _qkvg(n, heads, kv, hd, 10 + n, rate)
    s, z = _empty(1, 2, kv, hd, fill=7.0)
    chunk = jax.jit(lambda s, z, lg, q, k, v, fresh, valid:
                    R.retention_chunk(s, z, lg, q, k, v, jnp.int32(slot),
                                      fresh, valid, layer=jnp.int32(0)))
    ys = []
    for c0 in range(0, n, CHUNK):
        m = min(CHUNK, n - c0)
        pad = lambda x: jnp.pad(x[c0:c0 + m], ((0, CHUNK - m),)  # noqa: E731
                                + ((0, 0),) * (x.ndim - 1),
                                constant_values=1.7)
        y, s, z = chunk(s, z, pad(lg), pad(q), pad(k), pad(v), c0 == 0,
                        jnp.int32(m))
        ys.append(np.asarray(y[:m]))
    want, weight = _attention_form(q, k, v, lg)
    assert (np.abs(np.concatenate(ys) - want) * weight).max() <= OP_TOL
    s1, z1 = _empty(1, 2, kv, hd)
    act = jnp.asarray([False, True])
    for i in range(n):
        put = lambda x: jnp.zeros((2,) + x.shape[1:]).at[slot].set(x[i])  # noqa
        _, s1, z1 = R.retention_update(s1, z1, put(lg), put(q), put(k),
                                       put(v), act, layer=0, impl="xla")
    scale = float(jnp.abs(s1[0, slot]).max())
    assert float(jnp.abs(s[0, slot] - s1[0, slot]).max()) <= 1e-5 * scale
    assert float(jnp.abs(z[0, slot] - z1[0, slot]).max()) <= 1e-5 * scale
    assert float(jnp.abs(s[0, 0] - 7.0).max()) == 0.0   # the other slot


def test_query_heads_read_their_own_key_value_head():
    """Changing one key-value head's k and v moves exactly the query heads
    that read it, in both forms of the op."""
    t, heads, kv, hd = 9, 6, 3, 8
    q, k, v, lg = _qkvg(t, heads, kv, hd, 5, 0.05)
    k2, v2 = k.at[:, 1].add(1.0), v.at[:, 1].add(1.0)

    def chunked(k, v):
        s, z = _empty(1, 1, kv, hd)
        return R.retention_chunk(s, z, lg, q, k, v, jnp.int32(0), True,
                                 jnp.int32(t), layer=0)[0]

    def stepped(k, v):
        s, z = _empty(1, 1, kv, hd)
        out = []
        for i in range(t):
            y, s, z = R.retention_update(s, z, lg[i:i + 1], q[i:i + 1],
                                         k[i:i + 1], v[i:i + 1],
                                         jnp.asarray([True]), layer=0)
            out.append(y[0])
        return jnp.stack(out)

    for form in (chunked, stepped):
        moved = np.abs(np.asarray(form(k, v) - form(k2, v2))).max((0, 2))
        assert (moved[2:4] > 1e-2).all() and moved[:2].max() == 0.0 \
            and moved[4:].max() == 0.0, moved


def test_rotary_takes_absolute_positions():
    x = jnp.asarray(np.random.RandomState(1).randn(7, 6, 8), jnp.float32)
    pos = np.arange(7) + 100
    ang = pos[:, None] * 1e6 ** (-np.arange(4) * 2 / 8)          # [T, half]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = np.asarray(x[..., :4]), np.asarray(x[..., 4:])
    assert np.allclose(np.asarray(R.rotary(x, jnp.asarray(pos), 1e6)),
                       np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1),
                       atol=1e-5)
    assert np.allclose(np.asarray(R.rotary(x, jnp.zeros(7, jnp.int32), 1e6)),
                       np.asarray(x))
    # the reference's rotation at positions 0 .. T - 1
    assert np.allclose(np.asarray(R.rotary(x, jnp.arange(7), 1e6)),
                       np.asarray(ref.rope(x, 1e6)), atol=1e-6)


# -------------------------------------------------------- the step functions

def step_logits(cfg, params, prompt, n_decode, slot=1, chunk=CHUNK,
                token_by_token=False):
    """Logits the step functions give for ``prompt`` prefilled in chunks of
    ``chunk`` (or token by token through the decode step) and ``n_decode``
    greedy tokens decoded, in slot ``slot`` of SLOTS: ``[n_decode + 1, V]``
    (the last prompt position, then each decoded one) and the tokens."""
    empty = jnp.zeros((0, 1, PAGE, cfg.kv_width), jnp.float32)
    # a dirty slot: whatever the last sequence left must not show
    state = tuple(jnp.zeros(s, d) + 3.0 for _, _, s, d in
                  bm.state_arrays(cfg, SLOTS, PAGE, jnp.float32))
    row = jnp.zeros((0,), jnp.int32)
    active = np.zeros(SLOTS, bool)
    active[slot] = True
    decode = jax.jit(lambda p, ids, cache, act: bm.decode_step(
        p, ids, cache, act, cfg=cfg))

    def one(tok, length, state):
        ids = np.zeros(SLOTS, np.int32)
        ids[slot] = tok
        lengths = np.zeros(SLOTS, np.int32)
        lengths[slot] = length
        lg, c = decode(params, jnp.asarray(ids), dict(
            k_pages=empty, v_pages=empty,
            page_table=jnp.zeros((SLOTS, 0), jnp.int32),
            lengths=jnp.asarray(lengths), state=state), jnp.asarray(active))
        assert int(c["lengths"][slot]) == length + 1
        return np.asarray(lg[slot]), c["state"]

    if token_by_token:
        # the decode step cannot start a sequence (a chunk at 0 does): the
        # first token goes through a chunk of one
        n_first = 1
    else:
        n_first = len(prompt)
    chunk_fn = jax.jit(lambda *a, state: bm.prefill_chunk_step(
        *a, cfg=cfg, state=state, slot=jnp.int32(slot)))
    for start in range(0, n_first, chunk):
        ids = np.zeros(chunk, np.int32)
        part = prompt[start:min(start + chunk, n_first)]
        ids[:len(part)] = part
        lg, _, _, *state = chunk_fn(
            params, jnp.asarray(ids), jnp.int32(start), jnp.int32(len(part)),
            row, empty, empty, state=tuple(state))
        lg, state = np.asarray(lg), tuple(state)
    for i in range(n_first, len(prompt)):
        lg, state = one(int(prompt[i]), i, state)
    out, toks, length = [lg], [], len(prompt)
    for _ in range(n_decode):
        toks.append(int(out[-1].argmax()))
        lg, state = one(toks[-1], length, state)
        out.append(lg)
        length += 1
    return np.stack(out), toks


def _gap(cfg, params, prog_params, prompt, n_decode, prog_cfg=None, **kw):
    """Largest |program - reference| over the compared logits, as a share
    of the largest |reference logit|: the reference over ``params``, the
    program over ``prog_params``."""
    got, toks = step_logits(prog_cfg or cfg, prog_params, prompt, n_decode,
                            **kw)
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = _reference_logits(cfg, params, ids)[len(prompt) - 1:]
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("n_prompt", [5, 16, 37, 48],
                         ids=["short", "one_chunk", "ragged", "three_chunks"])
def test_step_logits_match_the_reference(tiny, n_prompt):
    """Prefill in chunks of 16, then 6 decode steps through the state,
    against the attention form's full forward."""
    cfg, params = tiny
    assert _gap(cfg, params, params, _prompt(n_prompt, n_prompt), 6) <= TOL


@pytest.mark.parametrize("how", ["whole", "chunks_of_7", "token_by_token"])
def test_positions_continue_across_chunks_and_into_decode(tiny, how):
    """One sequence prefilled whole (one chunk of 48), in ragged chunks and
    token by token through the decode step gives the reference's logits:
    the rotary position of a token is its place in the sequence, however
    it got there."""
    cfg, params = tiny
    kw = {"whole": dict(chunk=48), "chunks_of_7": dict(chunk=7),
          "token_by_token": dict(token_by_token=True)}[how]
    assert _gap(cfg, params, params, _prompt(40, 3), 5, **kw) <= TOL


def test_every_mechanism_moves_the_logits(tiny):
    """A leaf of each mechanism changed: the logits move by far more than
    the tolerance (a path that dropped one would not pass above)."""
    cfg, params = tiny
    prompt = _prompt(20, 9)
    base, _ = step_logits(cfg, params, prompt, 0)
    for leaf in ("l.q_norm.w", "l.k_norm.w", "l.gate.w", "l.gate.b",
                 "l.o.w", "l.mlp.w1", "head"):
        changed = dict(params)
        changed[leaf] = params[leaf] * 1.5 + 0.1
        got, _ = step_logits(cfg, changed, prompt, 0)
        assert np.abs(got - base).max() > 100 * TOL * np.abs(base).max(), leaf


@pytest.mark.parametrize("control", ["fp8", "bf16", "state_bf16"])
def test_a_lower_precision_fails_the_tolerance(tiny, control):
    """The reference itself in a lower precision, and its recurrence with
    the state rounded to bfloat16 a token, against the reference: over the
    tolerance the program is held to."""
    cfg, params = tiny
    ids = _prompt(43, 21)
    want = _reference_logits(cfg, params, ids)
    got = _reference_logits(cfg, params, ids, control)
    assert np.abs(got - want).max() / np.abs(want).max() > 20 * TOL


@pytest.mark.parametrize("control", ["state_bf16", "positions_restart",
                                     "no_gate", "fresh_every_chunk"])
def test_a_program_one_step_off_fails_the_tolerance(tiny, control,
                                                    monkeypatch):
    cfg, params = tiny
    prog_cfg, prog_params = cfg, params
    if control == "state_bf16":
        prog_cfg = dataclasses.replace(cfg, state_dtype="bfloat16")
    elif control == "no_gate":
        prog_params = dict(params, **{
            "l.gate.b": jnp.full_like(params["l.gate.b"], 30.0)})
    elif control == "positions_restart":
        real = R.rotary
        monkeypatch.setattr(
            R, "rotary", lambda x, pos, theta, **kw: real(
                x, pos - pos[0] if x.shape[0] > SLOTS else pos, theta, **kw))
    else:
        real = R.retention_chunk
        monkeypatch.setattr(
            R, "retention_chunk", lambda s, z, lg, q, k, v, slot, fresh,
            valid, **kw: real(s, z, lg, q, k, v, slot, True, valid, **kw))
    assert _gap(cfg, params, prog_params, _prompt(40, 23), 6,
                prog_cfg=prog_cfg) > 20 * TOL


# ------------------------------------------------------------ the engine

def test_engine_serves_greedy_tokens_of_the_reference(tiny):
    """Three requests of different lengths share the batch (one-shot,
    chunked, chunked with a ragged tail); each one's tokens are the
    reference's greedy continuation of its own prompt."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    prompts = [_prompt(37, 11), _prompt(5, 12), _prompt(20, 13)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        out = np.asarray(r.result())
        assert out[:len(p)].tolist() == p.tolist()
        lg = _reference_logits(cfg, params, out)[len(p) - 1:-1]
        assert lg.argmax(-1).tolist() == out[len(p):].tolist()


def test_a_reused_slot_serves_like_a_fresh_engine(tiny):
    """A freed slot's state reads as zero for the next sequence."""
    cfg, params = tiny
    a, b = _prompt(26, 31), _prompt(11, 32)
    eng = _engine(cfg, params, max_slots=1)
    ra = eng.submit(a, max_new_tokens=10)
    rb = eng.submit(b, max_new_tokens=10)
    eng.run_until_idle()
    fresh = _engine(cfg, params, max_slots=1)
    rf = fresh.submit(b, max_new_tokens=10)
    fresh.run_until_idle()
    assert ra.done and np.asarray(rb.result()).tolist() == \
        np.asarray(rf.result()).tolist()


def _gpt_engine():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                                 num_heads=2, max_position_embeddings=64))
    return DecodeEngine(m.eval(), EngineConfig(page_size=4, max_slots=2))


def test_a_family_without_a_pool_touches_no_page(tiny, monkeypatch):
    """``kv_layers == 0``: no pool is allocated, the engine has no
    allocator and no `PageAllocator` method runs for its sequences, its
    uploads carry a page table of no width, admission is bounded by slots
    alone (more requests than slots wait and are served), the published
    context is its limit at no cost, and the gauges say so; a GPT-2 engine
    in the same process allocates and frees its pages as before."""
    cfg, params = tiny
    gpt = _gpt_engine()
    gpt_shape = gpt._step_upload.shape
    calls = []
    for name in ("alloc", "free", "share", "reclaim"):
        real = getattr(cache_mod.PageAllocator, name)
        monkeypatch.setattr(
            cache_mod.PageAllocator, name,
            lambda self, *a, _real=real, _n=name: (calls.append(_n),
                                                   _real(self, *a))[1])
    resets0 = metrics.counter("engine.state_resets").value
    carries0 = metrics.counter("engine.state_carries").value
    eng = _engine(cfg, params, max_seq_len=None)
    assert eng.allocator is None and eng.pages_per_slot == 0
    assert eng.max_seq_len == cfg.max_position_embeddings == eng.slot_capacity
    assert eng._kc.size == 0 and eng._vc.size == 0
    assert eng._step_upload.shape == (SLOTS, 3)           # token, length, flags
    assert metrics.gauge("engine.cache_bytes.paged").value == 0
    s, z = R.state_shapes(cfg.num_layers, SLOTS, cfg.num_kv_heads,
                          cfg.head_dim)
    assert metrics.gauge("engine.cache_bytes.state").value == \
        4 * (np.prod(s) + np.prod(z))
    assert metrics.gauge("engine.state_bytes_per_slot").value == \
        4 * (np.prod(s) + np.prod(z)) // SLOTS
    prompts = [_prompt(n, 50 + n) for n in (37, 5, 20, 9, 33)]  # > SLOTS
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle()
    assert [len(r.result()) for r in reqs] == [len(p) + 8 for p in prompts]
    assert calls == []
    assert metrics.gauge("engine.pages_in_use").value == 0
    assert metrics.counter("engine.state_resets").value - resets0 == 5
    # chunks that did not start a sequence: 37 -> 2, 20 -> 1, 33 -> 2
    assert metrics.counter("engine.state_carries").value - carries0 == 5
    # a request past the published context is refused as ever
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(_prompt(9, 1), max_new_tokens=cfg.max_position_embeddings)
    # the pooled family beside it: same upload, pages taken and given back
    assert gpt._step_upload.shape == gpt_shape and gpt.allocator is not None
    r = gpt.submit(_prompt(9, 2) % 64, max_new_tokens=4)
    gpt.step()
    assert metrics.gauge("engine.pages_in_use").value > 0
    gpt.run_until_idle()
    assert len(r.result()) == 13 and "alloc" in calls and "free" in calls
    assert metrics.gauge("engine.pages_in_use").value == 0


def test_a_family_with_neither_pages_nor_state_is_refused(tiny):
    cfg, params = tiny
    model = bm.BrumbyForCausalLM(cfg, params)
    fam = model.engine_family()
    model.engine_family = lambda: dataclasses.replace(fam, state=None)
    with pytest.raises(ValueError, match="neither pages nor state"):
        DecodeEngine(model, EngineConfig(page_size=PAGE, max_slots=2))


def test_no_step_program_recompiles_and_each_op_is_counted(tiny):
    """Each new op is counted where a program is built (trace time), and a
    warm engine compiles nothing more whatever joins and retires
    (tests/test_no_retrace.py's rule)."""
    cfg, params = tiny
    built = {k: metrics.counter(f"kernel.dispatch.{k}").value for k in
             ("retention_update.xla", "retention_chunk.xla", "rotary.xla")}
    eng = _engine(cfg, params)
    eng.warmup(prompt_lens=[5, 9, 37])
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill", "prefill_chunk"]
    for k, v in built.items():
        assert metrics.counter(f"kernel.dispatch.{k}").value > v, k
    n = metrics.counter("engine.compile_count").value
    reqs = [eng.submit(_prompt(k, 60 + k), max_new_tokens=5)
            for k in (37, 5, 9, 17, 3)]
    for _ in range(3):
        eng.step()
    reqs.append(eng.submit(_prompt(16, 99), max_new_tokens=3))
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert metrics.counter("engine.compile_count").value == n


def test_an_engine_of_another_family_builds_no_retention_op():
    """What this family registers costs a process that does not serve it
    nothing: a GPT-2 engine's programs dispatch none of the three ops."""
    names = ("retention_update", "retention_chunk", "rotary")

    def built():
        return sum(v for k, v in metrics.snapshot()["counters"].items()
                   if k.startswith(tuple(f"kernel.dispatch.{n}."
                                         for n in names)))
    before = built()
    gpt = _gpt_engine()
    gpt.warmup(prompt_lens=[5])
    r = gpt.submit(_prompt(5, 3) % 64, max_new_tokens=3)
    gpt.run_until_idle()
    assert len(r.result()) == 8 and built() == before


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("knob", [dict(prefix_cache=True),
                                  dict(speculate_k=2),
                                  dict(kv_host_tier_bytes=1 << 20),
                                  dict(kv_disk_tier_bytes=1 << 20)],
                         ids=["prefix_cache", "speculate_k", "host_tier",
                              "disk_tier"])
def test_configuration_refuses_what_state_forbids(tiny, knob):
    cfg, params = tiny
    with pytest.raises(RecurrentStateUnsupported):
        _engine(cfg, params, **knob)


@pytest.mark.parametrize("call", ["prefill_export", "submit_prefill_stream",
                                  "import_request", "submit_import",
                                  "drain_migrate"])
def test_calls_refuse_what_state_forbids(tiny, call):
    cfg, params = tiny
    eng = _engine(cfg, params)
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
    assert "brumby" in str(e.value)
    r = eng.submit(_prompt(6, 2), max_new_tokens=3)
    eng.run_until_idle()
    assert len(r.result()) == 9


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="num_kv_heads"):
        bm.tiny_config(num_kv_heads=3)
    with pytest.raises(ValueError, match="even"):
        bm.tiny_config(head_dim=7)
    cfg = bm.tiny_config()
    with pytest.raises(KeyError, match="head"):
        bm.BrumbyForCausalLM(cfg, {k: v for k, v in bm.init_params(
            cfg).items() if k != "head"})
