"""Phi-4-mini-flash through the engine's model seam, at the tiny preset (8
layers, so all five mixers occur; window 8, page 4, chunk 8), on the CPU in
float32, held to the benchmark's plain reference
(benchmarks/reference/phi4flash.py, which imports nothing of paddle_tpu).

- the step functions' logits, prefill chunks then decode through all three
  kinds of state, against the reference's full forward, on contexts that
  pass the window and span three chunks;
- the engine: greedy tokens against the reference, chunked against
  one-shot prefill, a reused slot against a fresh engine, window memory
  constant in length, the new counters and gauges;
- every refusal of a model with recurrent state, by its error type;
- controls: a program that keeps the SSM state in bfloat16, leaves out the
  ``D`` term or the pair norm's scale fails the tolerance a sound one holds.
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

from paddle_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from paddle_tpu.inference.errors import (RecurrentStateUnsupported,  # noqa: E402
                                         from_wire)
from paddle_tpu.models import phi4flash as phi  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from reference import phi4flash as ref  # noqa: E402

PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 3, 64
TOL = 2e-5      # float32 on both sides: 13x the largest sound reading


def ref_config(cfg):
    """The reference's view of a program configuration: the published
    keys, and ``assumed`` for the rest."""
    return dict(hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
                vocab_size=cfg.vocab_size,
                intermediate_size=cfg.intermediate_size,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                sliding_window=cfg.sliding_window,
                assumed=dict(mamba_expand=cfg.mamba_expand,
                             mamba_d_state=cfg.mamba_d_state,
                             mamba_d_conv=cfg.mamba_d_conv,
                             mamba_dt_rank=cfg.dt_rank))


@pytest.fixture(scope="module")
def tiny():
    cfg = phi.tiny_config()
    # std 0.1: every mixer moves the logits by far more than the tolerance
    return cfg, phi.init_params(cfg, seed=7, std=0.1)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 96, size=n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(page_size=PAGE, max_slots=SLOTS, max_seq_len=MAX_SEQ,
              prefill_chunk_tokens=CHUNK, prefix_cache=False, inflight=2)
    kw.update(over)
    return DecodeEngine(phi.Phi4FlashForCausalLM(cfg, params),
                        EngineConfig(**kw))


def _reference_logits(cfg, params, ids):
    return np.asarray(ref.logits(params, jnp.asarray(ids), ref_config(cfg)))


def step_logits(cfg, params, prompt, n_decode, slot=1, chunk=CHUNK):
    """Logits the step functions give for ``prompt`` prefilled in chunks
    and ``n_decode`` greedy tokens decoded, in slot ``slot`` of SLOTS:
    ``[n_decode + 1, V]`` (the last prompt position, then each decoded
    one) and the tokens."""
    maxp = MAX_SEQ // PAGE
    pool = jnp.zeros((1, 1 + SLOTS * maxp, PAGE, cfg.kv_width), jnp.float32)
    kc, vc = pool, pool
    state = tuple(jnp.zeros(s, d) for _, _, s, d in
                  phi.state_arrays(cfg, SLOTS, PAGE, jnp.float32))
    # a dirty slot: whatever the last sequence left must not show
    state = tuple(a + 3.0 for a in state)
    row = np.arange(1 + slot * maxp, 1 + (slot + 1) * maxp, dtype=np.int32)
    table = np.zeros((SLOTS, maxp), np.int32)
    table[slot] = row
    chunk_fn = jax.jit(lambda *a, state: phi.prefill_chunk_step(
        *a, cfg=cfg, state=state, slot=jnp.int32(slot)))
    for start in range(0, len(prompt), chunk):
        ids = np.zeros(chunk, np.int32)
        part = prompt[start:start + chunk]
        ids[:len(part)] = part
        lg, kc, vc, *state = chunk_fn(params, jnp.asarray(ids),
                                      jnp.int32(start), jnp.int32(len(part)),
                                      jnp.asarray(row), kc, vc,
                                      state=tuple(state))
    out, toks = [np.asarray(lg)], []
    active = np.zeros(SLOTS, bool)
    active[slot] = True
    decode = jax.jit(lambda p, ids, cache, act: phi.decode_step(
        p, ids, cache, act, cfg=cfg))
    length = len(prompt)
    for _ in range(n_decode):
        toks.append(int(out[-1].argmax()))
        ids = np.zeros(SLOTS, np.int32)
        ids[slot] = toks[-1]
        lengths = np.zeros(SLOTS, np.int32)
        lengths[slot] = length
        cache = dict(k_pages=kc, v_pages=vc, page_table=jnp.asarray(table),
                     lengths=jnp.asarray(lengths), state=tuple(state))
        lg, cache = decode(params, jnp.asarray(ids), cache,
                           jnp.asarray(active))
        kc, vc, state = cache["k_pages"], cache["v_pages"], cache["state"]
        out.append(np.asarray(lg[slot]))
        length += 1
    return np.stack(out), toks


def _gap(cfg, params, prog_params, prompt, n_decode, prog_cfg=None):
    """Largest |logit| difference between the program's prefill-then-decode
    logits and the reference's full forward over the same tokens, as a
    share of the reference's largest |logit|."""
    got, toks = step_logits(prog_cfg or cfg, prog_params, prompt, n_decode)
    ids = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = _reference_logits(cfg, params, ids)[len(prompt) - 1:]
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("n_prompt", [5, 8, 21, 24],
                         ids=["short", "one-chunk", "three-chunks-ragged",
                              "three-chunks-full"])
def test_step_logits_match_the_reference(tiny, n_prompt):
    """Prefill in chunks of 8 with carried state, then 14 decode steps:
    contexts of 19 to 38 tokens, past the window of 8 and its ring of 12,
    against the reference's one full forward with no cache."""
    cfg, params = tiny
    assert _gap(cfg, params, params, _prompt(n_prompt, n_prompt), 14) < TOL


def test_every_mixer_moves_the_logits(tiny):
    """The tolerance means something only if each kind of layer shows:
    zeroing one mixer's output projection moves the logits by far more."""
    cfg, params = tiny
    prompt = _prompt(21, 3)
    for leaf in ("front.m.out_proj", "front.a.out.w", "mid.m.out_proj",
                 "mid.a.out.w", "back.g.out", "back.c.out.w"):
        broken = dict(params, **{leaf: jnp.zeros_like(params[leaf])})
        assert _gap(cfg, params, broken, prompt, 6) > 100 * TOL, leaf


@pytest.mark.parametrize("control", ["ssm_state_bf16", "no_D", "no_pair_norm",
                                     "no_lambda", "window_off_by_one"])
def test_a_control_fails_the_tolerance(tiny, control):
    """What the comparison is for: a program one step off reads over the
    tolerance that the sound program holds (test above)."""
    cfg, params = tiny
    prog_cfg, prog = cfg, params
    if control == "ssm_state_bf16":
        prog_cfg = dataclasses.replace(cfg, ssm_state_dtype="bfloat16")
    elif control == "no_D":
        prog = {k: jnp.zeros_like(v) if k.endswith(".D") else v
                for k, v in params.items()}
    elif control == "no_pair_norm":
        prog = {k: jnp.ones_like(v) if k.endswith("subln.w") else v
                for k, v in params.items()}
    elif control == "no_lambda":
        prog = {k: jnp.zeros_like(v) if k.endswith(".lam") else v
                for k, v in params.items()}
    else:
        prog_cfg = dataclasses.replace(cfg, sliding_window=7)
    gap = _gap(cfg, params, prog, _prompt(21, 5), 14, prog_cfg=prog_cfg)
    assert gap > 10 * TOL, gap


# ------------------------------------------------------------ the engine

def test_engine_serves_greedy_tokens_of_the_reference(tiny):
    """Two requests of different lengths share the batch; each one's tokens
    are the reference's greedy continuation of its own prompt."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    prompts = [_prompt(21, 11), _prompt(5, 12), _prompt(9, 13)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        out = np.asarray(r.result())
        assert out[:len(p)].tolist() == p.tolist()
        lg = _reference_logits(cfg, params, out)[len(p) - 1:-1]
        assert lg.argmax(-1).tolist() == out[len(p):].tolist()


def test_chunked_prefill_matches_one_shot(tiny):
    cfg, params = tiny
    prompt = _prompt(27, 21)
    outs = []
    for chunk in (CHUNK, None):
        eng = _engine(cfg, params, prefill_chunk_tokens=chunk)
        r = eng.submit(prompt, max_new_tokens=10)
        eng.run_until_idle()
        outs.append(np.asarray(r.result()).tolist())
    assert outs[0] == outs[1]


def test_a_reused_slot_serves_like_a_fresh_engine(tiny):
    """One slot, two requests one after the other: the second finds the
    first's rings and recurrent state in its slot and must not see them."""
    cfg, params = tiny
    a, b = _prompt(26, 31), _prompt(11, 32)
    eng = _engine(cfg, params, max_slots=1)
    ra = eng.submit(a, max_new_tokens=16)
    rb = eng.submit(b, max_new_tokens=16)
    eng.run_until_idle()
    fresh = _engine(cfg, params, max_slots=1)
    rf = fresh.submit(b, max_new_tokens=16)
    fresh.run_until_idle()
    assert ra.done and np.asarray(rb.result()).tolist() == \
        np.asarray(rf.result()).tolist()


def test_window_memory_does_not_grow_with_length(tiny):
    """The window layers' K and V are a ring of window + page tokens a
    slot whatever the engine's sequence limit; the page pool grows with
    it. A sequence three times the ring long recycles its pages."""
    cfg, params = tiny
    sizes = {}
    for max_seq in (32, 64):
        _engine(cfg, params, max_seq_len=max_seq)
        sizes[max_seq] = {k: metrics.gauge(f"engine.cache_bytes.{k}").value
                          for k in ("paged", "window", "state")}
    ring = cfg.sliding_window + PAGE
    assert sizes[32]["window"] == sizes[64]["window"] == \
        2 * cfg.n_front * SLOTS * ring * cfg.kv_width * 4
    assert sizes[32]["state"] == sizes[64]["state"]
    assert sizes[64]["paged"] > 1.9 * sizes[32]["paged"] - 2 * PAGE \
        * cfg.kv_width * 4
    eng = _engine(cfg, params)
    before = metrics.counter("engine.window_pages_recycled").value
    shapes = [a.shape for a in eng._state]
    r = eng.submit(_prompt(19, 41), max_new_tokens=18)
    eng.run_until_idle()
    assert r.done and [a.shape for a in eng._state] == shapes
    # 36 positions are written (the last token's never is): pages 0..8 of
    # 4, a ring of 3 pages -> 6 recycled
    assert metrics.counter("engine.window_pages_recycled").value \
        - before == 6


def test_state_counters_and_spans(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    c0 = {k: metrics.counter(f"engine.{k}").value
          for k in ("state_resets", "state_carries")}
    reqs = [eng.submit(_prompt(n, 50 + n), max_new_tokens=3)
            for n in (21, 5)]           # three chunks; one one-shot prefill
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert metrics.counter("engine.state_resets").value \
        - c0["state_resets"] == 2
    assert metrics.counter("engine.state_carries").value \
        - c0["state_carries"] == 2
    assert metrics.gauge("engine.state_bytes_per_slot").value == \
        (cfg.n_front + 1) * cfg.d_inner * 4 * (cfg.mamba_d_conv - 1
                                              + cfg.mamba_d_state)
    launches = [s for s in metrics.spans("engine.prefill_launch")
                if s.args.get("request_id") == reqs[0].request_id]
    assert [s.args["state_carried"] for s in launches] == \
        [False, True, True]


def test_no_step_program_relays_a_state_array(tiny):
    """The trace-time twin of ``kernel.pool_relayout``: every engine
    program addresses the state stacks with ``layer=``, so building them
    counts nothing; one layer's state handed over without it counts."""
    from paddle_tpu.kernels import ssm
    cfg, params = tiny

    def relayouts():
        return sum(metrics.counter(f"kernel.state_relayout.{op}").value
                   for op in ("ssm_update", "ssm_scan"))
    before = relayouts()
    eng = _engine(cfg, params)
    eng.warmup(prompt_lens=[5, 21])
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill_chunk"]
    assert relayouts() == before
    one = jnp.zeros((SLOTS, cfg.mamba_d_state, cfg.d_inner))
    z = jnp.zeros((SLOTS, cfg.d_inner))
    n = jnp.zeros((SLOTS, cfg.mamba_d_state))
    ssm.ssm_update(one, z, z, n, n, jnp.zeros((cfg.mamba_d_state,
                                               cfg.d_inner)),
                   jnp.zeros(cfg.d_inner), jnp.ones(SLOTS, bool))
    assert relayouts() == before + 1


def test_warm_engine_compiles_nothing_more(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    eng.warmup(prompt_lens=[5, 9, 21])
    n = metrics.counter("engine.compile_count").value
    reqs = [eng.submit(_prompt(k, 60 + k), max_new_tokens=5)
            for k in (21, 5, 9, 17, 3)]
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert metrics.counter("engine.compile_count").value == n


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
def test_calls_refuse_what_pages_alone_cannot_restore(tiny, call):
    """Hand-off and migration, both directions: refused at the call, by a
    typed error that survives the wire, and the engine still serves."""
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
    r = eng.submit(_prompt(6, 2), max_new_tokens=3)
    eng.run_until_idle()
    assert len(r.result()) == 9


def test_gpt_refuses_nothing_and_a_model_without_a_family_is_named():
    """The seam is the model's: GPT-2 declares no state and keeps every
    feature; an object that declares no family is refused by name."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                                 num_heads=2, max_position_embeddings=64))
    m.eval()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                       speculate_k=2, prefix_cache=True))
    assert eng._fam.name == "gpt" and eng._state == ()
    assert metrics.gauge("engine.cache_bytes.window").value == 0
    assert metrics.gauge("engine.cache_bytes.state").value == 0

    class Bare:
        cfg = None
    with pytest.raises(TypeError, match="engine_family"):
        DecodeEngine(Bare())
