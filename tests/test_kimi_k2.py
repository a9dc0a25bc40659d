"""Kimi K2 (``kimi_k2``) through the engine's model seam, at the tiny preset
(the dense layer and two expert layers; hidden 64, 4 latent heads of 8 + 4,
32 router outputs of which 4 experts are held, 2 a token; page 4, chunk 8,
sequences of some 50 tokens), on the CPU in float32, held to the
benchmark's plain reference (benchmarks/reference/kimi_k2.py, which imports
nothing of paddle_tpu and attends per head with a full softmax).

- the step functions' LOGITS, prefill chunks then decode through the latent
  pool, against the reference's full forward with the same share of the
  experts; the same for a sequence whose first three pages ANOTHER sequence
  wrote (a prefix hit: the tail starts at a page boundary that is no chunk
  boundary); controls that fail the tolerance: fp8 arithmetic, the shared
  context out of the tail's sight, the router's softmax, YaRN's softmax
  scale left out, the half-form rotation;
- the shares add up: 32 chips' routed parts and the shared expert once are
  the uncut layer;
- the engine WITH the prefix store, the first family of one page part and
  no state: a hit on pages a live owner holds, on pages that idled, after
  an eviction; the store's gauges over a pool with an empty ``v_pages``;
  the span ``engine.prefix_attach``; what refuses (hand-off, migration,
  tier spill by ``PageLayoutUnsupported``; an int8 pool and speculation by
  ``ValueError``); the wire.
"""
import dataclasses
import functools
import os
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from paddle_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from paddle_tpu.inference.errors import (PageLayoutUnsupported,  # noqa: E402
                                         from_wire)
from paddle_tpu.kernels import moe  # noqa: E402
from paddle_tpu.models import kimi_k2 as km  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import kimi_k2 as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
SHARED = 3 * PAGE       # a hit's three pages: no multiple of CHUNK
# float32 on both sides; what differs is the form (absorbed against per
# head, a paged walk with a carried softmax against one softmax, chunks
# against one pass) and the order of sums. The largest sound reading over
# the cases below is 1.3e-6 of the largest logit: the tolerance is 8x that.
# The weakest control (the router's softmax) reads 0.14, fp8 0.49, a tail
# that cannot see its context 0.54
TOL = 1e-5


def ref_config(cfg, held=None):
    """The reference's view of a program configuration: the published
    keys and the share."""
    lo, hi = held or cfg.experts_held
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        vocab_size=cfg.vocab_size, first_k_dense_replace=cfg.first_dense,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        router_outputs=cfg.n_routed_experts, n_routed_experts=hi - lo,
        experts_first=lo, num_experts_per_tok=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor, n_shared_experts=1,
        n_group=1, norm_topk_prob=True, scoring_func="sigmoid",
        moe_layer_freq=1,
        rope_scaling=dict(
            type="yarn", factor=cfg.rope_factor,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            original_max_position_embeddings=cfg.rope_original_max,
            mscale=1, mscale_all_dim=cfg.rope_mscale_all_dim),
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def tiny():
    cfg = km.tiny_config()
    # std 0.2: at these widths attention is far from uniform and every
    # part moves the logits by far more than the tolerance
    return cfg, km.init_params(cfg, seed=7, std=0.2)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _reference_logits(cfg, params, ids, precision="f32", hide=None):
    """Every sequence padded to MAX_SEQ (causal: the tail is inert), so the
    reference compiles once a precision."""
    padded = np.zeros(MAX_SEQ, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(ref.logits(params, jnp.asarray(padded), ref_config(cfg),
                                 precision, hide=hide))[:len(ids)]


@functools.lru_cache(maxsize=None)
def _steps(cfg):
    chunk = jax.jit(lambda *a, counts: km.prefill_chunk_step(
        *a, cfg=cfg, counts=counts))
    decode = jax.jit(lambda p, ids, cache, act: km.decode_step(
        p, ids, cache, act, cfg=cfg))
    return chunk, decode


class _Pool:
    """The step functions' side of a cache, by hand: a DIRTY latent pool
    (whatever the last sequence left must not show), the empty second
    pool, one page row a slot, the counts."""

    def __init__(self, cfg):
        self.cfg, self.maxp = cfg, MAX_SEQ // PAGE
        self.kc = jnp.full((cfg.num_layers, 1 + SLOTS * self.maxp, PAGE,
                            cfg.latent_width), 2.0, jnp.float32)
        self.vc = jnp.zeros((0, 1, PAGE, 0), jnp.float32)
        self.counts = jnp.zeros(km.step_counts(cfg), jnp.int32)

    def own_row(self, slot):
        return np.arange(1 + slot * self.maxp, 1 + (slot + 1) * self.maxp,
                         dtype=np.int32)

    def run(self, params, prompt, n_decode, slot, row=None, start=0,
            chunk=CHUNK):
        """``prompt`` prefilled in chunks from ``start`` on (the rows
        before it are what ``row``'s pages hold) and ``n_decode`` greedy
        tokens decoded in ``slot``: logits ``[n_decode + 1, V]`` and the
        tokens."""
        row = self.own_row(slot) if row is None else row
        table = np.zeros((SLOTS, self.maxp), np.int32)
        table[slot] = row
        chunk_fn, decode = _steps(self.cfg)
        for at in range(start, len(prompt), chunk):
            ids = np.zeros(chunk, np.int32)
            part = prompt[at:at + chunk]
            ids[:len(part)] = part
            lg, self.kc, self.vc, self.counts = chunk_fn(
                params, jnp.asarray(ids), jnp.int32(at),
                jnp.int32(len(part)), jnp.asarray(row), self.kc, self.vc,
                counts=self.counts)
        out, toks = [np.asarray(lg)], []
        active = np.zeros(SLOTS, bool)
        active[slot] = True
        for length in range(len(prompt), len(prompt) + n_decode):
            toks.append(int(out[-1].argmax()))
            ids = np.zeros(SLOTS, np.int32)
            ids[slot] = toks[-1]
            lengths = np.zeros(SLOTS, np.int32)
            lengths[slot] = length
            lg, cache = decode(params, jnp.asarray(ids), dict(
                k_pages=self.kc, v_pages=self.vc,
                page_table=jnp.asarray(table), lengths=jnp.asarray(lengths),
                counts=self.counts), jnp.asarray(active))
            self.kc, self.vc, self.counts = (
                cache["k_pages"], cache["v_pages"], cache["counts"])
            out.append(np.asarray(lg[slot]))
        return np.stack(out), toks


def _gap(cfg, params, got, prompt, toks, precision="f32", hide=None):
    """Largest |logit| difference between the program's logits (the
    prompt's last position, then each decoded token's) and the reference's
    full forward over the same tokens, as a share of its largest |logit|."""
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = _reference_logits(cfg, params, ids, precision, hide)
    want = want[len(prompt) - 1:]
    return float(np.abs(got - want).max() / np.abs(want).max())


def _cold_gap(cfg, params, prog_params, prompt, n_decode, **kw):
    with jax.default_matmul_precision("highest"):
        got, toks = _Pool(cfg).run(prog_params, prompt, n_decode, slot=1)
    return _gap(cfg, params, got, prompt, toks, **kw)


def _hit(cfg, params, n_own=9, n_decode=8):
    """Sequence A prefilled into slot 0; sequence B = A's first `SHARED`
    tokens + ``n_own`` of its own in slot 2, its page row A's first three
    pages then its own, prefilled from `SHARED` on (the engine's tail).
    Returns B's prompt, its logits and its tokens."""
    pool = _Pool(cfg)
    a = _prompt(29, 21)
    b = np.concatenate([a[:SHARED], _prompt(n_own, 22)])
    with jax.default_matmul_precision("highest"):
        pool.run(params, a, 2, slot=0)
        row = pool.own_row(2)
        row[:SHARED // PAGE] = pool.own_row(0)[:SHARED // PAGE]
        got, toks = pool.run(params, b, n_decode, slot=2, row=row,
                             start=SHARED)
    return b, got, toks


@pytest.mark.parametrize("n_prompt", [5, 8, 21, 40],
                         ids=["short", "one-chunk", "three-chunks-ragged",
                              "five-chunks-full"])
def test_step_logits_match_the_reference(tiny, n_prompt):
    """Prefill in chunks of 8 (latent attention per head over the pages),
    then 10 decode steps (the paged absorbed form), against the reference's
    one full forward with no cache."""
    cfg, params = tiny
    assert _cold_gap(cfg, params, params, _prompt(n_prompt, n_prompt),
                     10) < TOL


def test_a_tail_over_pages_another_sequence_wrote_matches_the_reference(tiny):
    """A prefix hit at the step functions: the tail's first query sits at
    12, a page boundary inside the second chunk of 8, over rows that
    sequence A wrote; A went on past them (its rows 12.. differ), and B's
    logits are the reference's for B's own tokens."""
    cfg, params = tiny
    b, got, toks = _hit(cfg, params)
    assert _gap(cfg, params, got, b, toks) < TOL


def test_every_mechanism_moves_the_logits(tiny):
    """The tolerance means something only if each part shows: zeroing one
    leaf moves the logits by far more."""
    cfg, params = tiny
    prompt = _prompt(21, 3)
    for leaf in ("L0.a.o", "L2.a.ukv", "L1.a.dkv", "L0.f.w2", "L1.f.w2",
                 "L2.f.shared.w2"):
        broken = dict(params, **{leaf: jnp.zeros_like(params[leaf])})
        assert _cold_gap(cfg, params, broken, prompt, 6) > 50 * TOL, leaf
    # and a router's bias that decides the routing alone
    broken = dict(params, **{"L1.f.bias": jnp.arange(
        cfg.n_routed_experts, dtype=jnp.float32)})
    assert _cold_gap(cfg, params, broken, prompt, 6) > 50 * TOL


@pytest.mark.parametrize("control", ["fp8", "softmax_router",
                                     "no_yarn_scale", "half_rope"])
def test_a_model_one_step_off_fails_the_tolerance(tiny, control):
    """What the comparison is for. Against the sound program: the
    reference with every product's operands in fp8; with a softmax over
    the chosen logits as the router's gates; with YaRN's softmax scale
    (m^2 = 2.0 at the published factor) left out; with the half-form
    rotation at plain ``theta``."""
    cfg, params = tiny
    gap = _cold_gap(cfg, params, params, _prompt(40, 5), 10,
                    precision=control)
    assert gap > 5000 * TOL, gap


def test_a_tail_that_cannot_see_its_context_fails_the_tolerance(tiny):
    """The control of a broken attach: the reference whose queries from
    `SHARED` on see no key before it, against the sound hit."""
    cfg, params = tiny
    b, got, toks = _hit(cfg, params)
    gap = _gap(cfg, params, got, b, toks, "no_context", hide=SHARED)
    assert gap > 5000 * TOL, gap


def test_the_shares_add_up(tiny):
    """32 chips, each with ONE of the 32 routed experts: their routed parts
    and the shared expert ONCE are the uncut reference layer, and the
    program's kernel, either arm, gives a chip's part."""
    cfg, _ = tiny
    e = cfg.n_routed_experts
    full = km.init_params(dataclasses.replace(cfg, experts_held=(0, e)),
                          seed=11, std=0.3)
    p = {k[len("L2.f."):]: v for k, v in full.items()
         if k.startswith("L2.f.")}
    b = jnp.asarray(np.random.RandomState(1).randn(96, cfg.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.sizes(ref_config(cfg, (0, e)))
        shared = ref.gated(b, p["shared.w1"], p["shared.w2"], "f32")
        uncut = ref.experts(b, p, whole, "f32")
        total, alive = shared, 0
        for lo in range(e):
            mine = dict(p, w1=p["w1"][lo:lo + 1], w2=p["w2"][lo:lo + 1])
            part = ref.experts(
                b, mine, ref.sizes(ref_config(cfg, (lo, lo + 1))),
                "f32") - shared
            total = total + part
            alive += float(jnp.abs(part).max()) > 1e-3
            for arm in ("dense", "grouped") if lo in (0, e - 1) else ():
                got = moe.routed_experts(
                    b, p["router"], mine["w1"], mine["w2"],
                    top_k=cfg.experts_per_token, held=(lo, lo + 1),
                    scoring="sigmoid", bias=p["bias"],
                    scale=cfg.routed_scaling_factor, impl=arm)
                np.testing.assert_allclose(got, part, atol=5e-6)
    assert alive >= e - 4             # 192 assignments over 32 experts
    np.testing.assert_allclose(total, uncut, atol=5e-6)


# ------------------------------------------------- the engine, prefix store

def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=True, inflight=2,
              min_bucket=8, num_pages=41)
    kw.update(over)
    return DecodeEngine(km.KimiK2ForCausalLM(cfg, params), EngineConfig(**kw))


_COUNTED = ("engine.prefix_hit", "engine.prefix_miss",
            "engine.prefix_pages_reused", "engine.prefix_evictions",
            "engine.compile_count", "engine.latent.pairs.prefill",
            "engine.moe.assignments")


def _grew(c0):
    return {k: metrics.counter(k).value - v for k, v in c0.items()}


def _counts():
    return {k: metrics.counter(k).value for k in _COUNTED}


@pytest.fixture(scope="module")
def served(tiny):
    """One warm engine with the prefix store on and 40 pages: ten a
    sequence of 40 tokens."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    eng.warmup(prompt_lens=[5, 29], tail_lens=[9])
    return eng


def _is_greedy(cfg, params, prompt, req):
    out = np.asarray(req.result())
    assert out[:len(prompt)].tolist() == prompt.tolist()
    lg = _reference_logits(cfg, params, out)[len(prompt) - 1:-1]
    assert lg.argmax(-1).tolist() == out[len(prompt):].tolist()


def test_engine_serves_a_hit_on_a_live_owners_pages(tiny, served):
    """The first family with ``page_rows`` and no state: nothing refuses
    ``prefix_cache=True``. A second request shares three pages of a first
    one's prompt while the first still decodes: it attaches them, prefills
    its own tail from 12 on, and both are the reference's greedy
    continuations."""
    cfg, params = tiny
    eng = served
    assert eng._fam.name == "kimi_k2" and not eng._stateful
    assert eng._vc.shape == (0, 1, PAGE, 0)
    assert eng.kv_bytes_per_token == cfg.num_layers * cfg.latent_width * 4
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill_chunk"]
    c0 = _counts()
    spans0 = len(metrics.spans("engine.prefix_attach"))
    a = _prompt(29, 21)
    b = np.concatenate([a[:SHARED], _prompt(9, 22)])
    ra = eng.submit(a, max_new_tokens=16)
    while not ra.generated:
        eng.step()
    rb = eng.submit(b, max_new_tokens=8)
    eng.step()
    assert rb.u_prefill_saved == SHARED
    shared = eng._slot_pages[eng._slot_req.index(rb)][:SHARED // PAGE]
    assert [eng.allocator.refcount(p) for p in shared] == [2, 2, 2]
    eng.run_until_idle()
    _is_greedy(cfg, params, a, ra)
    _is_greedy(cfg, params, b, rb)
    grew = _grew(c0)
    assert (grew["engine.prefix_hit"], grew["engine.prefix_miss"],
            grew["engine.prefix_pages_reused"]) == (1, 1, SHARED // PAGE)
    assert grew["engine.compile_count"] == 0
    # the tail alone was prefilled: queries 12..20 in each of 3 layers
    assert grew["engine.latent.pairs.prefill"] == cfg.num_layers * (
        sum(t + 1 for t in range(29)) + sum(t + 1 for t in range(SHARED, 21)))
    attach = metrics.spans("engine.prefix_attach")[spans0:]
    assert [s.args["pages"] for s in attach] == [0, SHARED // PAGE]
    # the store indexes both prompts' full pages (7 + 5 - 3 shared) and its
    # bytes are counted from the one part a page row has
    assert metrics.gauge("engine.prefix_pages").value == 9
    assert metrics.gauge("engine.prefix_store_bytes").value == \
        9 * PAGE * eng.kv_bytes_per_token


def test_engine_serves_a_hit_on_idled_pages_and_after_an_eviction(tiny,
                                                                  served):
    """Both owners have finished: their prompt pages idle in the store. A
    third request revives the shared three; then requests that need the
    whole pool evict every idle page, and the same prompt asked again is
    prefilled afresh: a miss, and the same tokens."""
    cfg, params = tiny
    eng = served
    assert eng.allocator.free_pages == 40 and len(eng._prefix_idle) == 9
    c0 = _counts()
    a = _prompt(29, 21)
    c = np.concatenate([a[:SHARED], _prompt(7, 23)])
    rc = eng.submit(c, max_new_tokens=6)
    eng.run_until_idle()
    _is_greedy(cfg, params, c, rc)
    assert rc.u_prefill_saved == SHARED
    assert _grew(c0)["engine.prefix_pages_reused"] == SHARED // PAGE
    # three unshared sequences of 13 pages each need 39 of the 40: the LRU
    # gives up idle pages, the shared three among them
    big = [_prompt(40, 30 + i) for i in range(3)]
    rs = [eng.submit(p, max_new_tokens=12) for p in big]
    eng.run_until_idle()
    for p, r in zip(big, rs):
        _is_greedy(cfg, params, p, r)
    grew = _grew(c0)
    assert grew["engine.prefix_evictions"] >= 9
    assert eng._prefix_lookup(eng._page_hashes(c)) == []
    rc2 = eng.submit(c, max_new_tokens=6)
    eng.run_until_idle()
    assert rc2.u_prefill_saved == 0
    assert np.asarray(rc2.result()).tolist() == \
        np.asarray(rc.result()).tolist()
    assert _grew(c0)["engine.compile_count"] == 0


def test_the_pool_is_made_once(tiny, monkeypatch):
    """`DeviceCache.allocate` makes each pool ONCE, in the family's own
    shape: a K-and-V pair of the whole pool made first and then replaced
    (4.19 GB twice beside 7 GB of weights at the benchmark's size) did not
    fit the chip."""
    from paddle_tpu.inference import cache as C
    from paddle_tpu.inference.family import family_of
    cfg, params = tiny
    made = []
    real = jnp.zeros
    monkeypatch.setattr(C.jnp, "zeros", lambda shape, *a, **k: (
        made.append(tuple(shape)), real(shape, *a, **k))[1])
    C.DeviceCache.allocate(
        family_of(km.KimiK2ForCausalLM(cfg, params)),
        EngineConfig(page_size=PAGE, max_slots=SLOTS), 41, jnp.float32)
    assert made == [(cfg.num_layers, 41, PAGE, cfg.latent_width),
                    (0, 1, PAGE, 0)]


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("knob,error", [
    (dict(kv_host_tier_bytes=1 << 20), PageLayoutUnsupported),
    (dict(kv_disk_tier_bytes=1 << 20), PageLayoutUnsupported),
    (dict(kv_dtype="int8"), ValueError),
    (dict(speculate_k=2), ValueError)],
    ids=["host_tier", "disk_tier", "int8_pool", "speculate_k"])
def test_configuration_refuses_what_a_latent_row_cannot_do(tiny, knob,
                                                           error):
    """Tier frames are twin K and V pools; the int8 scale pools are per K/V
    head; the family supplies no verify step."""
    cfg, params = tiny
    with pytest.raises(error, match="kimi_k2|page rows"):
        _engine(cfg, params, **knob)


@pytest.mark.parametrize("call", ["prefill_export", "submit_prefill_stream",
                                  "import_request", "submit_import",
                                  "drain_migrate"])
def test_calls_refuse_a_blob_of_twin_pools(served, call):
    """Hand-off and migration blobs state ``[.., kv heads, head dim]`` for K
    and again for V: a page row of one latent part is refused by a typed
    error that survives the wire, never packed as half a row."""
    eng = served
    with pytest.raises(PageLayoutUnsupported) as e:
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
    assert isinstance(from_wire(wire), PageLayoutUnsupported)
    assert "kimi_k2" in str(e.value) and "latent (128)" in str(e.value)


def test_a_config_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        km.tiny_config(experts_held=(30, 34))
    with pytest.raises(ValueError, match="first_dense"):
        km.tiny_config(first_dense=4)


# ------------------------------------------------------------------ the wire

def test_the_wire_serves_it_like_every_family(tiny, served):
    """`InferenceServer` over the same engine, `RemotePredictor.generate`:
    a context asked twice is a hit the second time, and both answers are
    the reference's greedy continuations. LAST in this file: the server's
    thread drives the engine from here on."""
    from paddle_tpu.inference.serve import InferenceServer, RemotePredictor
    cfg, params = tiny
    srv = InferenceServer(None, engine=served, auth_name="kimi")
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    c0 = _counts()
    ctx = _prompt(SHARED + 4, 51)
    asks = [np.concatenate([ctx, _prompt(n, 52 + n)]) for n in (5, 9)]
    cli = RemotePredictor(port=srv.port, secret="kimi", timeout=120.0)
    try:
        for ids in asks:
            out = np.asarray(cli.generate(ids, max_new_tokens=5))
            lg = _reference_logits(cfg, params, out)[len(ids) - 1:-1]
            assert out[:len(ids)].tolist() == ids.tolist()
            assert lg.argmax(-1).tolist() == out[len(ids):].tolist()
    finally:
        cli.shutdown_server()
        cli.close()
        t.join(timeout=30)
        if srv._engine_thread is not None:
            srv._engine_thread.join(timeout=30)
    grew = _grew(c0)
    assert (grew["engine.prefix_hit"], grew["engine.prefix_miss"]) == (1, 1)
    assert grew["engine.prefix_pages_reused"] == (SHARED + 4) // PAGE
