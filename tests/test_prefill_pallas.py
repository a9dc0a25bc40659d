"""Authored Pallas ragged PREFILL kernel (r15,
`kernels/pallas/prefill_attention.py`): interpret-mode parity with the
XLA gather arm, the length-aware stop's per-cell trip counts, int8-KV
scale DMA, and token identity through every engine path the registry
routes it under (one-shot, chunked, prefix tail, the PTKS1 stream)."""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.pallas.prefill_attention import (
    block_visits, prefill_attention as pallas_prefill)
from paddle_tpu.observability import metrics


@pytest.fixture(autouse=True)
def _restore_flag():
    yield
    set_flags({"tpu_prefill_impl": "auto"})


NL = 3                  # layers of the test pools; every layer is noise


def _pool(rng, nh=2, dh=8, ps=4, maxp=6):
    """K and V in the stored layout [NL, num_pages, ps, nh*dh]."""
    shape = (NL, 1 + maxp, ps, nh * dh)
    kp = jnp.asarray(rng.randn(*shape).astype(np.float32))
    vp = jnp.asarray(rng.randn(*shape).astype(np.float32))
    row = jnp.asarray(np.arange(1, maxp + 1, dtype=np.int32))
    return kp, vp, row


def _quantized(pool, nh=2):
    """An int8 stored pool and its [NL, P, ps, nh] scales."""
    q, s = pa.quantize_kv(pool.reshape(*pool.shape[:3], nh, -1))
    return q.reshape(pool.shape), s


@pytest.mark.parametrize("layer", [0, NL - 1])
class TestKernelParity:
    """The stored pool read at its first and its last layer: pallas
    (interpret) against the XLA arm, and bit for bit against the
    per-layer form of that layer's pool."""

    @pytest.mark.parametrize("start,valid,c", [
        (0, 7, 8),       # fresh prompt, padded tail
        (8, 5, 8),       # chunk after 2 pages of context
        (4, 8, 8),       # mid-page start (prefix-cache tail shape)
        (0, 1, 4),       # single real token
        (12, 3, 4),      # deep context, short tail
    ])
    def test_matches_xla_arm(self, layer, start, valid, c):
        rng = np.random.RandomState(start * 17 + valid)
        kp, vp, row = _pool(rng)
        q = jnp.asarray(rng.randn(1, c, 2, 8).astype(np.float32))
        ref = pa._xla_prefill_attention(q, kp, vp, row, jnp.int32(start),
                                        jnp.int32(valid), layer)
        out = pallas_prefill(q[0], kp, vp, row, jnp.int32(start),
                             jnp.int32(valid), layer=layer, interpret=True)
        np.testing.assert_allclose(np.asarray(ref)[0, :valid],
                                   np.asarray(out)[:valid],
                                   rtol=1e-5, atol=1e-5)
        # without ``layer=``: that layer's pool alone, merged rank 3 or
        # [P, ps, nh, dh] as the benchmark's probe passes it
        for shape in [kp.shape[1:], kp.shape[1:3] + (2, 8)]:
            one = pallas_prefill(q[0], kp[layer].reshape(shape),
                                 vp[layer].reshape(shape), row,
                                 jnp.int32(start), jnp.int32(valid),
                                 interpret=True)
            np.testing.assert_array_equal(np.asarray(one), np.asarray(out))

    def test_multi_qblock_grid(self, layer):
        rng = np.random.RandomState(3)
        kp, vp, row = _pool(rng, maxp=16)
        q = jnp.asarray(rng.randn(1, 16, 2, 8).astype(np.float32))
        ref = pa._xla_prefill_attention(q, kp, vp, row, jnp.int32(8),
                                        jnp.int32(10), layer)
        out = pallas_prefill(q[0], kp, vp, row, jnp.int32(8),
                             jnp.int32(10), layer=layer, interpret=True,
                             block_q=4)
        np.testing.assert_allclose(np.asarray(ref)[0, :10],
                                   np.asarray(out)[:10],
                                   rtol=1e-5, atol=1e-5)

    def test_int8_scales_ride_the_same_operands(self, layer):
        rng = np.random.RandomState(7)
        kp, vp, row = _pool(rng)
        kq, ks = _quantized(kp)
        vq, vs = _quantized(vp)
        q = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
        ref = pa._xla_prefill_attention(q, kq, vq, row, jnp.int32(4),
                                        jnp.int32(6), layer, k_scale=ks,
                                        v_scale=vs)
        out = pallas_prefill(q[0], kq, vq, row, jnp.int32(4),
                             jnp.int32(6), layer=layer, interpret=True,
                             k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(ref)[0, :6],
                                   np.asarray(out)[:6],
                                   rtol=1e-5, atol=1e-5)
        one = pallas_prefill(q[0], kq[layer], vq[layer], row, jnp.int32(4),
                             jnp.int32(6), interpret=True,
                             k_scale=ks[layer], v_scale=vs[layer])
        np.testing.assert_array_equal(np.asarray(one), np.asarray(out))

    def test_jit_composes(self, layer):
        import jax
        rng = np.random.RandomState(9)
        kp, vp, row = _pool(rng)
        q = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))

        @jax.jit
        def f(q_, kp_, vp_, start, valid):
            return pallas_prefill(q_[0], kp_, vp_, row, start, valid,
                                  layer=layer, interpret=True)

        out = f(q, kp, vp, jnp.int32(4), jnp.int32(5))
        ref = pa._xla_prefill_attention(q, kp, vp, row, jnp.int32(4),
                                        jnp.int32(5), layer)
        np.testing.assert_allclose(np.asarray(ref)[0, :5],
                                   np.asarray(out)[:5],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_a_traced_layer_is_one_function_for_every_layer(kv):
    """The layer as a TRACED value, as `models/gpt.py::_block_stack` passes
    it: every layer's call gives the bits of the same call with the layer a
    constant, and a program that calls the kernel once a layer holds ONE
    function for it (`_stored_call`) and a call a layer, not a kernel body
    a layer (PERF.md, PR 39: 4.8-5.2 s of each prefill program's start)."""
    import re
    import jax
    rng = np.random.RandomState(13)
    kp, vp, row = _pool(rng)
    scales = {}
    if kv == "int8":
        (kp, ks), (vp, vs) = _quantized(kp), _quantized(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    q = jnp.asarray(rng.randn(8, 2, 8).astype(np.float32))
    start, valid = jnp.int32(4), jnp.int32(6)

    def at(layer, kp_, vp_, sc):
        return pallas_prefill(q, kp_, vp_, row, start, valid, layer=layer,
                              interpret=True, **sc)

    def every_layer(kp_, vp_, sc):
        return [at(jnp.int32(i) + 0 * start, kp_, vp_, sc)
                for i in range(NL)]
    lowered = jax.jit(every_layer).lower(kp, vp, scales)
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @_stored_call\(", text)) == 1
    assert len(re.findall(r"call @_stored_call\(", text)) == NL
    got = lowered.compile()(kp, vp, scales)
    for i in range(NL):
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(at(i, kp, vp, scales)))
    assert not np.array_equal(np.asarray(got[0]), np.asarray(got[NL - 1]))


class TestLengthScaling:
    """The ragged-stop proof: per-cell trip counts scale with the
    request's TRUE context (start + valid), never with pages_per_slot or
    the pow-2 bucket the chunk is padded to."""

    def test_visits_track_true_length_not_capacity(self):
        rng = np.random.RandomState(1)
        maxp = 64                       # a BIG slot: capacity is 64 pages
        kp, vp, row = _pool(rng, maxp=maxp)
        ps = 4
        for start, valid in [(0, 3), (8, 4), (20, 8)]:
            c = 8
            q = jnp.asarray(rng.randn(1, c, 2, 8).astype(np.float32))
            _, visits = pallas_prefill(
                q[0], kp, vp, row, jnp.int32(start), jnp.int32(valid),
                layer=1, interpret=True, return_visits=True)
            v = np.asarray(visits)
            want = -(-(start + valid) // ps)
            assert v.max() == want, (start, valid, v)
            assert v.max() < maxp       # never the capacity walk

    def test_walk_never_leaves_the_page_table_row(self):
        """start + valid past the slot's capacity (the shape one autotune
        measurement had) must not walk past the page-table row: on the chip
        that is a wild DMA and a halted core."""
        rng = np.random.RandomState(3)
        maxp = 4
        kp, vp, row = _pool(rng, maxp=maxp)          # 16-token slot
        q = jnp.asarray(rng.randn(1, 16, 2, 8).astype(np.float32))
        _, visits = pallas_prefill(q[0], kp, vp, row, jnp.int32(4),
                                   jnp.int32(16), layer=1, interpret=True,
                                   return_visits=True)
        assert np.asarray(visits).max() == maxp

    def test_padded_qblocks_visit_zero_pages(self):
        rng = np.random.RandomState(2)
        kp, vp, row = _pool(rng, maxp=16)
        q = jnp.asarray(rng.randn(1, 16, 2, 8).astype(np.float32))
        _, visits = pallas_prefill(q[0], kp, vp, row, jnp.int32(0),
                                   jnp.int32(5), layer=1, interpret=True,
                                   return_visits=True, block_q=4)
        v = np.asarray(visits)[:, 0]    # per q block, head 0
        assert v[0] > 0 and v[1] > 0    # rows 0..7 hold the 5 real tokens
        assert v[2] == 0 and v[3] == 0  # rows 8..15 are bucket padding
        assert int(block_visits(jnp.int32(0), jnp.int32(5), 8, 4, 4)) == 0


def _tiny_model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(21)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


class TestEngineTokenIdentity:
    """The acceptance bar: forcing the pallas arm through every prefill
    path the registry routes produces TOKEN-IDENTICAL output to the XLA
    arm (interpret mode off-TPU)."""

    def _run(self, model, prompt, impl, n=6, **ecfg):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        set_flags({"tpu_prefill_impl": impl})
        eng = DecodeEngine(model, EngineConfig(page_size=4, max_slots=2,
                                               min_bucket=8, **ecfg))
        r = eng.submit(prompt, max_new_tokens=n)
        eng.run_until_idle(max_steps=80)
        return r.result(timeout=30)

    def test_one_shot_and_chunked_and_int8(self):
        m = _tiny_model()
        prompt = np.random.RandomState(1).randint(0, 97, 21) \
            .astype(np.int32)
        for kw in ({}, {"prefill_chunk_tokens": 8}, {"kv_dtype": "int8"}):
            a = self._run(m, prompt, "xla", **kw)
            b = self._run(m, prompt, "pallas", **kw)
            assert np.array_equal(a, b), (kw, a, b)

    def test_prefix_cache_tail(self):
        m = _tiny_model()
        prompt = np.random.RandomState(2).randint(0, 97, 17) \
            .astype(np.int32)
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        ref = self._run(m, prompt, "xla")
        set_flags({"tpu_prefill_impl": "pallas"})
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        r1 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=80)
        hit_before = metrics.snapshot()["counters"].get(
            "engine.prefix_hit", 0)
        r2 = eng.submit(prompt, max_new_tokens=6)   # tail path, cache hit
        eng.run_until_idle(max_steps=80)
        assert metrics.snapshot()["counters"].get(
            "engine.prefix_hit", 0) == hit_before + 1
        assert np.array_equal(r1.result(5), ref)
        assert np.array_equal(r2.result(5), ref)

    def test_ptks1_stream_path(self):
        """The PR 13 prefill-worker stream runs NOTHING but this kernel:
        stream a prompt's pages off a pallas-armed prefill engine,
        assemble, import into a decode engine — token-identical to the
        xla-armed stream AND to fast_generate."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        from paddle_tpu.serving.disagg import KVStreamAssembler
        m = _tiny_model()
        prompt = np.random.RandomState(3).randint(0, 97, 13) \
            .astype(np.int32)
        want = np.asarray(m.fast_generate(
            paddle.Tensor(prompt[None], _internal=True),
            max_new_tokens=4).numpy())[0]

        def stream(impl):
            set_flags({"tpu_prefill_impl": impl})
            pf = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                              min_bucket=8,
                                              prefill_chunk_tokens=4))
            sink = pf.submit_prefill_stream(prompt)
            pf.run_until_idle(max_steps=40)
            asm = KVStreamAssembler()
            handoff = None
            while True:
                kind, payload = sink.get(timeout=10)
                if kind == "rec":
                    handoff = asm.feed(payload) or handoff
                elif kind == "done":
                    break
                elif kind == "err":
                    raise AssertionError(payload)
            assert handoff is not None
            dc = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                              min_bucket=8))
            r = dc.submit_import(handoff, max_new_tokens=4)
            dc.run_until_idle(max_steps=40)
            return r.result(timeout=30)

        out_p = stream("pallas")
        out_x = stream("xla")
        assert np.array_equal(out_p, want) and np.array_equal(out_x, want)

    def test_dispatch_switch_and_counters(self):
        rng = np.random.RandomState(4)
        kp, vp, row = _pool(rng)
        q = jnp.asarray(rng.randn(1, 4, 2, 8).astype(np.float32))
        set_flags({"tpu_prefill_impl": "xla"})
        before = metrics.counter(
            "kernel.dispatch.prefill_attention.xla").value
        a = pa.prefill_attention(q, kp, vp, row, jnp.int32(0), jnp.int32(4),
                                 layer=1)
        assert metrics.counter(
            "kernel.dispatch.prefill_attention.xla").value == before + 1
        set_flags({"tpu_prefill_impl": "pallas"})
        pbefore = metrics.counter(
            "kernel.dispatch.prefill_attention.pallas").value
        b = pa.prefill_attention(q, kp, vp, row, jnp.int32(0), jnp.int32(4),
                                 layer=1)
        assert metrics.counter(
            "kernel.dispatch.prefill_attention.pallas").value == pbefore + 1
        np.testing.assert_allclose(np.asarray(a)[0], np.asarray(b)[0],
                                   rtol=1e-5, atol=1e-5)
