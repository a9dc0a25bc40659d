"""A GPT step program traces a layer's code once (PERF.md, PR 39).

`models/gpt.py::_block_stack` runs the transformer block as ONE traced
function called once a layer with a TRACED layer index, a layer's four
matrices as its arguments and the pools it rewrites as a carry; the prefill
kernel sits behind an inner function of its own, as the decode kernel did
(`kernels/pallas/prefill_attention.py::_stored_call`). Before, a Python loop
inlined the block 24 times into each of GPT-2 medium's six programs, 32 s of
a 57 s warm start. These tests hold the mechanism, on a 4-layer toy on the
CPU, for every step program and for float weights, int8 weights and an int8
pool:

- building a program moves `model.block_traces` by 1 and
  `model.block_calls` by the number of layers, and the `engine.compile:*`
  span says so; the lowered text holds one function for the block and a
  call of it a layer;
- what the programs compute is BIT-identical to the block inlined
  (`inlined_block.py`, the loop as it was): the step functions' results
  with the Pallas arms pinned, and the tokens an engine serves;
- `fast_generate` (the block inside a `lax.scan` body) equals `generate`;
- a second engine on the same model traces again and leaks no program.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
from paddle_tpu.models import gpt
from paddle_tpu.observability import metrics
from paddle_tpu.quantization.serving import quantize_gpt_params

from inlined_block import inlined_block_stack

NL = 4
PROGRAMS = ["decode", "prefill", "prefill_chunk", "verify"]
VARIANTS = {"float": {}, "int8-weights": {"weight_dtype": "int8"},
            "int8-pool": {"kv_dtype": "int8"}}


def _tiny_model(seed=11):
    paddle.seed(seed)
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=NL,
                        num_heads=2, intermediate_size=64,
                        max_position_embeddings=64, hidden_dropout=0.0,
                        attention_dropout=0.0)
    return gpt.GPTForCausalLM(cfg)


def _engine(model, **over):
    return DecodeEngine(model, EngineConfig(**{
        **dict(page_size=4, max_slots=3, min_bucket=8, max_seq_len=64),
        **over}))


def _counts():
    c = metrics.snapshot()["counters"]
    return np.array([c.get("model.block_traces", 0),
                     c.get("model.block_calls", 0),
                     c.get("engine.compile_count", 0)])


# ------------------------------------------- one trace, one function, nl calls

@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("program", PROGRAMS)
def test_a_step_program_traces_and_lowers_the_block_once(
        program, variant, monkeypatch):
    """The engine builds one program: the block's Python body runs once and
    is applied once a layer, the `engine.compile:<program>` span carries
    both counts, and the text the engine hands the compiler holds one
    `@block` function and a call of it a layer."""
    texts = []

    def build(self, prog, *small):
        lowered = jax.jit(prog).lower(self._params, self._cache, *small)
        texts.append(lowered.as_text())
        return lowered.compile()
    monkeypatch.setattr(DecodeEngine, "_build", build)
    eng = _engine(_tiny_model(), **VARIANTS[variant],
                  **({"speculate_k": 2} if program == "verify" else {}))
    was = _counts()
    if program in ("decode", "verify"):
        eng._step_exe()
    else:
        eng._prefill_exe(8, chunk=program == "prefill_chunk")
    assert list(_counts() - was) == [1, NL, 1]
    span = metrics.spans(name=f"engine.compile:{program}")[-1]
    assert (span.args["block_traces"], span.args["block_calls"]) == (1, NL)
    (text,) = texts
    assert len(re.findall(r"func\.func private @block\(", text)) == 1
    assert len(re.findall(r"call @block\(", text)) == NL


# ------------------------------------------------ the block inlined, bit for bit

def _run(program, params, cfg, quant):
    """One call of a step function at a tiny size over pools a one-shot
    prefill has written; ``quant``: an int8 pool and its scales."""
    nh, hd, npages, ps, maxp, b = 2, 32, 9, 4, 4, 2
    pool = jnp.zeros((NL, npages, ps, hd), jnp.int8 if quant else jnp.float32)
    scales = {"k_scale": jnp.ones((NL, npages, ps, nh), jnp.float32),
              "v_scale": jnp.ones((NL, npages, ps, nh), jnp.float32)} \
        if quant else {}
    table = jnp.arange(1, 1 + b * maxp, dtype=jnp.int32).reshape(b, maxp)
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, 64, 8).astype(np.int32))
    live = jnp.asarray([True, True])
    _, kc, vc, *sc = gpt.prefill_step(params, ids, jnp.int32(7), table[0],
                                      pool, pool, cfg=cfg, **scales)
    if quant:
        scales = dict(k_scale=sc[0], v_scale=sc[1])
    if program == "prefill":
        return gpt.prefill_step(params, ids, jnp.int32(5), table[1], kc, vc,
                                cfg=cfg, **scales)
    if program == "prefill_chunk":
        return gpt.prefill_chunk_step(params, ids, jnp.int32(4), jnp.int32(6),
                                      table[0], kc, vc, cfg=cfg, **scales)
    cache = dict(k_pages=kc, v_pages=vc, page_table=table,
                 lengths=jnp.asarray([7, 0], jnp.int32), **scales)
    if program == "decode":
        logits, new = gpt.decode_step(params, ids[:b], cache, live, cfg=cfg)
        return (logits, *(new[k] for k in sorted(new)))
    toks = jnp.asarray(rng.randint(0, 64, (b, 3)).astype(np.int32))
    em, n, new = gpt.verify_step(params, toks, jnp.asarray([2, 1], jnp.int32),
                                 cache, live, cfg=cfg)
    return (em, n, *(new[k] for k in sorted(new)))


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("program", PROGRAMS)
def test_a_step_function_equals_the_block_inlined_bit_for_bit(
        program, variant, monkeypatch):
    """Decode, a one-shot prefill, a chunk and the speculative verify, each
    compiled whole with the Pallas arms pinned (the kernels then take the
    traced layer too, as on the chip), against the same functions over the
    loop that inlines a layer at a time: logits, emitted tokens, pools and
    scales are equal bit for bit."""
    model = _tiny_model()
    params = gpt.serving_params(model.state_dict())
    if variant == "int8-weights":
        params = quantize_gpt_params(params)

    def run(p):
        return _run(program, p, model.cfg, variant == "int8-pool")
    set_flags({"tpu_paged_impl": "pallas", "tpu_prefill_impl": "pallas"})
    try:
        got = jax.jit(run)(params)
        monkeypatch.setattr(gpt, "_block_stack", inlined_block_stack())
        want = jax.jit(run)(params)
    finally:
        set_flags({"tpu_paged_impl": "auto", "tpu_prefill_impl": "auto"})
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert float(jnp.abs(got[-1].astype(jnp.float32)).max()) > 0


MODES = {"one-shot": {}, "chunked": {"prefill_chunk_tokens": 8},
         "speculative": {"speculate_k": 2}}


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("mode", MODES)
def test_an_engine_serves_the_tokens_of_the_block_inlined(mode, variant,
                                                          monkeypatch):
    """Three requests of different lengths through an engine (one-shot
    buckets, chunks of 8, or speculating) and through one whose programs
    inline the block a layer at a time: the same tokens."""
    model = _tiny_model()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, n).astype(np.int32) for n in (5, 19, 11)]

    def serve():
        eng = _engine(model, **VARIANTS[variant], **MODES[mode])
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.run_until_idle(max_steps=200)
        return [np.asarray(r.result(timeout=30)) for r in reqs]
    was = _counts()
    got = serve()
    traces, calls, compiles = _counts() - was
    assert (traces, calls) == (compiles, NL * compiles) and compiles >= 2
    monkeypatch.setattr(gpt, "_block_stack", inlined_block_stack())
    want = serve()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert len({tuple(g) for g in got}) == len(got)


# ------------------------------------------------- the block in a scan's body

@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.8, "top_k": 5,
                                           "seed": 3}],
                         ids=["greedy", "top-k"])
def test_fast_generate_equals_generate_and_the_block_inlined(sampling,
                                                             monkeypatch):
    """`fast_generate` calls the block inside a `lax.scan` body (and once
    before it, for the prompt): 2 traces, 2 x layers calls, the tokens of
    the eager `generate` and of the block inlined."""
    model = _tiny_model()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 64, (2, 6)).astype(np.int64))
    was = _counts()
    fast = model.fast_generate(ids, max_new_tokens=7, **sampling).numpy()
    assert list(_counts() - was)[:2] == [2, 2 * NL]
    np.testing.assert_array_equal(
        fast, model.generate(ids, max_new_tokens=7, **sampling).numpy())
    monkeypatch.setattr(gpt, "_block_stack", inlined_block_stack())
    model._fast_decode_cache.clear()
    np.testing.assert_array_equal(
        fast, model.fast_generate(ids, max_new_tokens=7, **sampling).numpy())


# ----------------------------------------------------------- a second engine

def test_a_second_engine_traces_again_and_leaks_no_program():
    """The block's function belongs to one call of `_block_stack`: a second
    engine on the same model builds its own programs (a trace and a compile
    each, as many as the first), serving through either compiles nothing
    more, and what they serve is the same."""
    model = _tiny_model()
    prompt = np.random.RandomState(3).randint(0, 64, 7).astype(np.int32)

    def serve(eng):
        req = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        return np.asarray(req.result(timeout=30))
    was = _counts()
    first = _engine(model, prefix_cache=False)
    first.warmup(prompt_lens=[8, 16])
    one = _counts() - was
    assert list(one) == [3, 3 * NL, 3] and len(first._programs) == 3
    second = _engine(model, prefix_cache=False)
    second.warmup(prompt_lens=[8, 16])
    assert list(_counts() - was) == list(2 * one)
    assert len(second._programs) == 3
    np.testing.assert_array_equal(serve(first), serve(second))
    np.testing.assert_array_equal(serve(first), serve(second))
    assert list(_counts() - was) == list(2 * one)
