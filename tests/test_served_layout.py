"""What a launch of a GPT step program hands the runtime (PERF.md, PR 37).

Every launch hands the runtime every leaf of the engine's parameters, at
about a microsecond of host time a leaf, so the GPT family serves from
`models/gpt.py::serving_params` and not from the state_dict: the 8 vectors
of a block (norms and biases) are stacked ``[nl, width]`` and the 4 matrices
stay a tuple of the model's own per-layer arrays (4 leaves a layer where
the state_dict has 12). The arithmetic is the per-layer program's: the
block reads ``leaf[layer]``, a layer's own matrices as its arguments
(`models/gpt.py::_block_stack`). These tests hold the layout to that:
the gauge that says how many leaves a launch takes, bit-identical logits
against the per-layer reading of the same weights (float and int8), a
weight swap without a compile, and int8 matrices widened a layer at a time.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import gpt
from paddle_tpu.observability import metrics
from paddle_tpu.quantization.serving import (GPT_MATMUL_SUFFIXES,
                                             QuantizedLeaf,
                                             quantize_gpt_params)

from inlined_block import inlined_block_stack, state_dict_get

NL = 3
PROGRAMS = ["decode_step", "prefill_step", "prefill_chunk_step",
            "verify_step"]


def _tiny_model(seed=11):
    paddle.seed(seed)
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=NL,
                        num_heads=2, intermediate_size=64,
                        max_position_embeddings=64, hidden_dropout=0.0,
                        attention_dropout=0.0)
    return gpt.GPTForCausalLM(cfg)


def _engine(model, **over):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    return DecodeEngine(model, EngineConfig(**{
        **dict(page_size=4, max_slots=3, min_bucket=8, max_seq_len=64),
        **over}))


def _leaves(tree):
    return len(jax.tree_util.tree_leaves(tree))


def _param_leaves():
    return metrics.snapshot()["gauges"]["engine.param_leaves"]


# ------------------------------------------------- what a launch hands over

@pytest.mark.parametrize("weights", ["native", "int8"])
def test_a_gpt_engine_hands_a_launch_a_third_of_the_state_dict(weights):
    """4 matrices a layer, 8 stacks of vectors and 4 top leaves (int8: a
    scale beside each matrix), where the state_dict has 12 a layer; the
    float matrices are the model's own arrays, not copies; the gauge reads
    what the compiled programs take."""
    model = _tiny_model()
    state = model.state_dict()
    eng = _engine(model, weight_dtype=weights)
    want = (8 if weights == "int8" else 4) * NL + 8 + 4
    assert _leaves(eng._params) == want < len(state) == 12 * NL + 4
    assert _param_leaves() == want
    for name in gpt.BLOCK_SUFFIXES:
        leaf = eng._params["blocks." + name]
        if name in GPT_MATMUL_SUFFIXES:
            assert isinstance(leaf, tuple) and len(leaf) == NL
            if weights == "native":
                assert all(leaf[i] is state[f"gpt.h.{i}.{name}"]._data
                           for i in range(NL))
        else:
            assert leaf.shape == (NL, *state[f"gpt.h.0.{name}"].shape)
    eng.warmup(prompt_lens=[8])
    for key, exe in eng._programs.items():
        assert _leaves(exe.args_info[0][0]) == want, key
    eng.refresh_params(model)
    assert _param_leaves() == want


@pytest.mark.parametrize("family", ["phi4flash", "granitemoehybrid",
                                    "brumby"])
def test_the_other_families_hand_over_what_they_did(family):
    """Their parameters were stacked already: the engine takes the model's
    own leaves, as many as the model has, and the gauge says so."""
    import importlib
    mod = importlib.import_module(f"paddle_tpu.models.{family}")
    cfg = mod.tiny_config()
    model = {"phi4flash": "Phi4FlashForCausalLM",
             "granitemoehybrid": "GraniteMoeHybridForCausalLM",
             "brumby": "BrumbyForCausalLM"}[family]
    model = getattr(mod, model)(cfg, mod.init_params(cfg, seed=7, std=0.1))
    eng = _engine(model, prefix_cache=False, prefill_chunk_tokens=8)
    assert _param_leaves() == _leaves(eng._params) == _leaves(model.params)
    assert all(eng._params[k] is v for k, v in model.params.items())


# ------------------------------ the per-layer reading of the same weights

def _run(program, params, cfg):
    """One call of a step function at a tiny size, the pools not empty
    (a first call fills them, a second reads them back)."""
    nl, nh, hd, npages, ps, maxp, b = NL, 2, 32, 9, 4, 4, 2
    pool = jnp.zeros((nl, npages, ps, hd), jnp.float32)
    table = jnp.arange(1, 1 + b * maxp, dtype=jnp.int32).reshape(b, maxp)
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, 64, 8).astype(np.int32))
    live = jnp.asarray([True, True])
    _, kc, vc = gpt.prefill_step(params, ids, jnp.int32(7), table[0], pool,
                                 pool, cfg=cfg)
    cache = dict(k_pages=kc, v_pages=vc, page_table=table,
                 lengths=jnp.asarray([7, 0], jnp.int32))
    if program == "decode_step":
        out = gpt.decode_step(params, ids[:b], cache, live, cfg=cfg)
        return out[0], out[1]["k_pages"], out[1]["v_pages"]
    if program == "verify_step":
        toks = jnp.asarray(rng.randint(0, 64, (b, 3)).astype(np.int32))
        em, n, new = gpt.verify_step(params, toks,
                                     jnp.asarray([2, 1], jnp.int32), cache,
                                     live, cfg=cfg)
        return em, n, new["k_pages"], new["v_pages"]
    if program == "prefill_chunk_step":
        return gpt.prefill_chunk_step(params, ids, jnp.int32(4),
                                      jnp.int32(6), table[0], kc, vc,
                                      cfg=cfg)
    return gpt.prefill_step(params, ids, jnp.int32(5), table[1], kc, vc,
                            cfg=cfg)


@pytest.mark.parametrize("weights", ["native", "int8"])
@pytest.mark.parametrize("program", PROGRAMS)
def test_the_served_layout_gives_the_per_layer_programs_output(
        program, weights, monkeypatch):
    """Decode, one-shot prefill, a chunk and the speculative verify over
    the served layout against the same functions reading the state_dict a
    layer at a time: the same products over the same weights in the same
    order, so logits, emitted tokens and the pools written are equal BIT
    FOR BIT, for float weights and for int8 ones."""
    from paddle_tpu.framework.flags import set_flags
    model = _tiny_model()
    state = {k: t._data for k, t in model.state_dict().items()}
    served = gpt.serving_params(model.state_dict())
    if weights == "int8":
        state, served = quantize_gpt_params(state), \
            quantize_gpt_params(served)
        assert all(isinstance(leaf, QuantizedLeaf)
                   for leaf in served["blocks.mlp.fc_in.weight"])
    set_flags({"tpu_paged_impl": "xla", "tpu_prefill_impl": "xla"})
    try:
        got = _run(program, served, model.cfg)
        monkeypatch.setattr(gpt, "_block_stack",
                            inlined_block_stack(state_dict_get))
        want = _run(program, state, model.cfg)
    finally:
        set_flags({"tpu_paged_impl": "auto", "tpu_prefill_impl": "auto"})
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert float(jnp.abs(got[-1]).max()) > 0       # the pools were written


# ------------------------------------------- a weight swap without a compile

@pytest.mark.parametrize("weights", ["native", "int8"])
def test_refresh_params_swaps_the_served_leaves_without_a_compile(weights):
    """`refresh_params` lays the new model's weights out as the programs
    were compiled to take them: no compile, the engine's leaves are the
    new weights, and what it then serves is what an engine built on the
    new model serves."""
    m1, m2 = _tiny_model(11), _tiny_model(12)
    prompt = np.random.RandomState(3).randint(0, 64, 7).astype(np.int32)

    def serve(eng):
        req = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        return req.result(timeout=30)

    eng = _engine(m1, weight_dtype=weights)
    first = serve(eng)
    compiles = metrics.snapshot()["counters"]["engine.compile_count"]
    programs = dict(eng._programs)
    eng.refresh_params(m2)
    for i, leaf in enumerate(eng._params["blocks.mlp.fc_in.weight"]):
        new = np.asarray(m2.state_dict()[f"gpt.h.{i}.mlp.fc_in.weight"]._data)
        if weights == "int8":
            assert isinstance(leaf, QuantizedLeaf)
            np.testing.assert_allclose(np.asarray(leaf.dequant()), new,
                                       atol=np.abs(new).max() / 127, rtol=0)
        else:
            np.testing.assert_array_equal(np.asarray(leaf), new)
    np.testing.assert_array_equal(
        np.asarray(eng._params["blocks.ln_2.bias"]),
        np.stack([np.asarray(m2.state_dict()[f"gpt.h.{i}.ln_2.bias"]._data)
                  for i in range(NL)]))
    swapped = serve(eng)
    assert metrics.snapshot()["counters"]["engine.compile_count"] == compiles
    assert eng._programs == programs
    np.testing.assert_array_equal(swapped,
                                  serve(_engine(m2, weight_dtype=weights)))
    assert not np.array_equal(swapped, first)


# ------------------------------------ int8: a matrix is widened where used

@pytest.mark.parametrize("program", PROGRAMS)
def test_an_int8_step_widens_one_layer_at_a_time(program):
    """The block hands `dequant` one layer's int8 matrix and its scales: each
    matmul leaf is widened once a layer where it is used, and no value of
    the traced program is a float array of several layers' matrices."""
    from paddle_tpu.framework.flags import set_flags
    model = _tiny_model()
    cfg = model.cfg
    params = quantize_gpt_params(gpt.serving_params(model.state_dict()))
    set_flags({"tpu_paged_impl": "xla", "tpu_prefill_impl": "xla"})
    try:
        jaxpr = jax.make_jaxpr(lambda p: _run(program, p, cfg))(params)
    finally:
        set_flags({"tpu_paged_impl": "auto", "tpu_prefill_impl": "auto"})
    h, f = cfg.hidden_size, cfg.intermediate_size
    layer = {(h, 3 * h), (h, h), (h, f), (f, h)}

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield (eqn.primitive.name, tuple(v.aval.shape),
                       v.aval.dtype)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    seen = list(walk(jaxpr.jaxpr))
    assert [e for e in seen if len(e[1]) == 3 and e[1][1:] in layer
            and jnp.issubdtype(e[2], jnp.floating)] == []
    widened = [e for e in seen if e[0] == "convert_element_type"
               and e[1] in layer and e[2] == jnp.float32]
    # every program here runs the block stack twice (a prefill fills the
    # pools first): 4 matmul leaves x layers x 2
    assert len(widened) == 4 * NL * 2
