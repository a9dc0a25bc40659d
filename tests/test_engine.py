"""Batched decode engine: paged KV cache correctness, continuous batching,
page accounting, and the serve GENERATE wire op.

The load-bearing contract: paged-cache decode is TOKEN-IDENTICAL to dense
`fast_generate` (same math, different cache layout), for B=1 and B>1,
including sequences that cross page boundaries.
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics


def _tiny_model(seed=7, vocab=97, max_pos=64):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=max_pos, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _fast_ref(model, prompt, n):
    ids = paddle.Tensor(np.asarray(prompt)[None].astype(np.int32),
                        _internal=True)
    return np.asarray(model.fast_generate(ids, max_new_tokens=n).numpy())[0]


class TestPagedAttentionKernel:
    """kernels/paged_attention.py against a dense reference."""

    @pytest.mark.parametrize("layer", [0, 2])
    def test_gather_matches_dense_layout(self, layer):
        import jax.numpy as jnp
        from paddle_tpu.kernels import paged_attention as pa
        rng = np.random.RandomState(0)
        ps, nh, dh = 4, 2, 8
        # a 13-token sequence scattered over pages [3, 1, 4, 2] of one
        # layer of the stored [nl, P, ps, nh*dh] pool; the other layers
        # hold noise the gather must not touch
        toks = rng.randn(13, nh, dh).astype(np.float32)
        pages = rng.randn(3, 6, ps, nh * dh).astype(np.float32)
        table = np.array([3, 1, 4, 2], np.int32)
        for t in range(13):
            pages[layer, table[t // ps], t % ps] = toks[t].reshape(-1)
        got = pa.gather_kv(jnp.asarray(pages), jnp.asarray(table[None]),
                           layer, nh)
        assert got.shape == (1, 4 * ps, nh, dh)
        np.testing.assert_array_equal(np.asarray(got)[0, :13], toks)

    @pytest.mark.parametrize("form", ["per-layer", "per-layer-merged",
                                      "stacked-0", "stacked-last"])
    def test_paged_attention_matches_dense_softmax(self, form):
        """Every accepted form of the pools: the stored stack read with
        ``layer=`` (first and last layer), and ONE layer's pool without it
        (rank 4 as the benchmark's probe passes it, or merged rank 3)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels import paged_attention as pa
        rng = np.random.RandomState(1)
        ps, nh, dh, L = 4, 2, 8, 11
        q = rng.randn(1, nh, dh).astype(np.float32)
        ks = rng.randn(L, nh, dh).astype(np.float32)
        vs = rng.randn(L, nh, dh).astype(np.float32)
        kp = np.zeros((5, ps, nh, dh), np.float32)
        vp = np.zeros_like(kp)
        table = np.array([2, 4, 1], np.int32)
        for t in range(L):
            kp[table[t // ps], t % ps] = ks[t]
            vp[table[t // ps], t % ps] = vs[t]
        kw = {}
        if form == "per-layer-merged":
            kp, vp = kp.reshape(5, ps, -1), vp.reshape(5, ps, -1)
        elif form.startswith("stacked"):
            layer = 0 if form == "stacked-0" else 2
            stack = rng.randn(2, 3, 5, ps, nh * dh).astype(np.float32)
            stack[0, layer], stack[1, layer] = (kp.reshape(5, ps, -1),
                                                vp.reshape(5, ps, -1))
            kp, vp = stack
            kw = dict(layer=layer)
        got = pa.paged_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), jnp.asarray(table[None]),
                                 jnp.asarray([L - 1], np.int32), **kw)
        # dense reference: plain f32 softmax attention over the L tokens
        sc = np.einsum("hd,lhd->hl", q[0] / np.sqrt(dh), ks)
        pr = np.asarray(jax.nn.softmax(jnp.asarray(sc), axis=-1))
        want = np.einsum("hl,lhd->hd", pr, vs)
        np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-5,
                                   atol=1e-6)

    def test_trash_page_routing(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels import paged_attention as pa
        kp = jnp.zeros((2, 3, 2, 4))             # [nl, P, ps, nh*dh]
        vp = jnp.zeros_like(kp)
        k = jnp.ones((1, 1, 4))
        table = jnp.asarray([[1, 2]], jnp.int32)
        # inactive slot: the write must land on TRASH_PAGE, not page 1,
        # and in the layer asked for alone
        kp2, _ = pa.write_token_kv(kp, vp, k, k, table,
                                   jnp.asarray([0], jnp.int32),
                                   jnp.asarray([False]), 1)
        assert np.asarray(kp2)[1, pa.TRASH_PAGE].sum() == 4
        assert np.asarray(kp2)[1, 1:].sum() == 0
        assert np.asarray(kp2)[0].sum() == 0


class TestEngineParity:
    """Paged decode == dense fast_generate, token for token."""

    def test_b1_crosses_page_boundary(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        # page_size 4, prompt 5, 12 new tokens: the sequence spans pages
        # 0..4 and the prompt itself straddles a page edge
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8))
        prompt = np.random.RandomState(0).randint(0, 97, 5).astype(np.int32)
        req = eng.submit(prompt, max_new_tokens=12)
        eng.run_until_idle(max_steps=50)
        np.testing.assert_array_equal(req.result(timeout=30),
                                      _fast_ref(m, prompt, 12))

    def test_batch_gt1_mixed_lengths(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=4,
                                           min_bucket=8))
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, 97, s).astype(np.int32)
                   for s in (3, 7, 9, 16)]
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_idle(max_steps=100)
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, p, 8))

    def test_single_token_request(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        prompt = np.random.RandomState(2).randint(0, 97, 6).astype(np.int32)
        req = eng.submit(prompt, max_new_tokens=1)
        eng.run_until_idle(max_steps=10)
        np.testing.assert_array_equal(req.result(timeout=30),
                                      _fast_ref(m, prompt, 1))


class TestContinuousBatching:
    def test_more_requests_than_slots(self):
        """7 requests over 2 slots: later requests are admitted as earlier
        ones retire, mid-flight, and every output still matches the dense
        reference."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 97, 3 + i).astype(np.int32)
                   for i in range(7)]
        # staggered max_new so retirements interleave with admissions
        ns = [5, 9, 3, 7, 4, 8, 6]
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, ns)]
        eng.run_until_idle(max_steps=300)
        for p, n, r in zip(prompts, ns, reqs):
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, p, n))

    def test_late_submit_joins_running_batch(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        rng = np.random.RandomState(4)
        p1 = rng.randint(0, 97, 4).astype(np.int32)
        p2 = rng.randint(0, 97, 6).astype(np.int32)
        r1 = eng.submit(p1, max_new_tokens=10)
        for _ in range(3):
            eng.step()                       # r1 alone for a few tokens
        r2 = eng.submit(p2, max_new_tokens=5)   # joins mid-decode
        eng.run_until_idle(max_steps=100)
        np.testing.assert_array_equal(r1.result(timeout=30),
                                      _fast_ref(m, p1, 10))
        np.testing.assert_array_equal(r2.result(timeout=30),
                                      _fast_ref(m, p2, 5))

    def test_pages_reclaimed_and_occupancy_gauge(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        total = eng.allocator.free_pages
        rng = np.random.RandomState(5)
        reqs = [eng.submit(rng.randint(0, 97, 5).astype(np.int32), 4)
                for _ in range(3)]
        eng.run_until_idle(max_steps=100)
        for r in reqs:
            assert r.done
        assert eng.allocator.free_pages == total     # all pages returned
        assert metrics.gauge("engine.pages_in_use").value == 0
        assert metrics.histogram("engine.queue_wait_seconds").count >= 3

    def test_pool_too_small_request_errors(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        # 4 usable pages of 4 tokens = 16-token capacity
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8, num_pages=5,
                                           max_seq_len=40))
        req = eng.submit(np.arange(20, dtype=np.int32), max_new_tokens=10)
        eng.run_until_idle(max_steps=10)
        with pytest.raises(RuntimeError, match="pages"):
            req.result(timeout=5)

    def test_submit_validates_capacity(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1))
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.arange(60, dtype=np.int32), max_new_tokens=30)

    def test_eos_retires_early(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        prompt = np.random.RandomState(6).randint(0, 97, 4).astype(np.int32)
        ref = _fast_ref(m, prompt, 12)
        eos = int(ref[len(prompt) + 2])      # the 3rd generated token
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8, eos_id=eos))
        req = eng.submit(prompt, max_new_tokens=12)
        eng.run_until_idle(max_steps=50)
        out = req.result(timeout=30)
        assert out[-1] == eos
        np.testing.assert_array_equal(out, ref[:len(out)])


class TestPallasEngineParity:
    """The whole serving stack on the authored Pallas kernel (interpret mode
    on CPU): still token-identical to dense fast_generate."""

    @pytest.fixture(autouse=True)
    def _restore_flag(self):
        from paddle_tpu.framework.flags import set_flags
        yield
        set_flags({"tpu_paged_impl": "auto"})

    def test_engine_on_pallas_matches_fast_generate(self):
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        set_flags({"tpu_paged_impl": "pallas"})
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, 97, s).astype(np.int32) for s in (5, 9)]
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_idle(max_steps=60)
        set_flags({"tpu_paged_impl": "auto"})  # ref decodes on the default
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, p, 8))
        assert metrics.counter("paged_attention.impl.pallas").value > 0

    def test_flag_flip_compiles_new_decode_program(self):
        """The impl is baked into the traced program, so the flag is part of
        the engine's program-cache key: flipping it mid-life compiles a new
        decode program instead of being silently ignored."""
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        set_flags({"tpu_paged_impl": "xla"})
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8))
        rng = np.random.RandomState(14)
        eng.submit(rng.randint(0, 97, 4).astype(np.int32), 3)
        eng.run_until_idle(max_steps=20)
        compiles = metrics.counter("engine.compile_count").value
        pallas_before = metrics.counter("paged_attention.impl.pallas").value

        set_flags({"tpu_paged_impl": "pallas"})
        req = eng.submit(rng.randint(0, 97, 4).astype(np.int32), 3)
        eng.run_until_idle(max_steps=20)
        np.testing.assert_array_equal(req.result(timeout=30)[-3:],
                                      _fast_ref(m, req.prompt, 3)[-3:])
        # exactly ONE new program (the pallas decode step), and it fired
        assert metrics.counter("engine.compile_count").value == compiles + 1
        assert metrics.counter(
            "paged_attention.impl.pallas").value > pallas_before


def _relayouts():
    return {k: v for k, v in metrics.snapshot()["counters"].items()
            if k.startswith("kernel.pool_relayout.")}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("ecfg", [
    dict(), dict(prefill_chunk_tokens=8), dict(kv_dtype="int8"),
    dict(speculate_k=2)], ids=["plain", "chunked", "int8", "spec"])
def test_no_engine_program_relays_the_pool(impl, ecfg):
    """`kernel.pool_relayout.*` counts, at trace time, every attention call
    whose pools arrive per layer instead of as the stored stack. Building
    every program of an engine leaves it where it was; one call in the
    per-layer form (the benchmark probe's) moves it by one."""
    import jax.numpy as jnp
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.kernels import paged_attention as pa
    set_flags({"tpu_paged_impl": impl, "tpu_prefill_impl": impl})
    try:
        before = _relayouts()
        eng = DecodeEngine(_tiny_model(), EngineConfig(
            page_size=4, max_slots=2, min_bucket=8, **ecfg))
        eng.warmup(prompt_lens=[5, 20], tail_lens=[3])
        assert eng._kc.shape == (eng._nl, eng.allocator.num_pages, 4,
                                 eng._nh * eng._dh)
        assert _relayouts() == before
        pool = jnp.zeros((5, 4, 2, 8), jnp.float32)
        table = jnp.zeros((2, 3), jnp.int32)
        pa.paged_attention(jnp.zeros((2, 2, 8), jnp.float32), pool, pool,
                           table, jnp.zeros((2,), jnp.int32))
        pa.prefill_attention(jnp.zeros((1, 8, 2, 8), jnp.float32), pool, pool,
                             table[0], jnp.int32(0), jnp.int32(8))
        after = _relayouts()
        for op in ("paged_attention", "prefill_attention"):
            name = f"kernel.pool_relayout.{op}"
            assert after[name] == before.get(name, 0) + 1
    finally:
        set_flags({"tpu_paged_impl": "auto", "tpu_prefill_impl": "auto"})


class TestDesyncStepLoop:
    """The de-synchronized hot path: ONE fused host->device upload per step,
    no blocking readback besides sampled token ids (deferred by the
    in-flight window, a prefill's first token among them), host/device
    timers populated."""

    def test_one_upload_one_token_readback_per_step(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, inflight=2))
        h2d = metrics.counter("engine.h2d_transfers")
        d2h = metrics.counter("engine.d2h_transfers")
        steps = metrics.counter("engine.steps")
        base = (h2d.value, d2h.value, steps.value)
        rng = np.random.RandomState(10)
        reqs = [eng.submit(rng.randint(0, 97, 5).astype(np.int32), 6)
                for _ in range(2)]
        eng.run_until_idle(max_steps=60)
        for r in reqs:
            assert r.done
        n_steps = steps.value - base[2]
        n_prefills = 2
        # exactly one packed slot-state upload per decode step (+ one fused
        # upload per prefill), and exactly one token-chain readback per
        # fifo entry: a dispatched step's, or a prefill's first token —
        # nothing else crosses the transfer boundary in the loop
        assert h2d.value - base[0] == n_steps + n_prefills
        assert d2h.value - base[1] == n_steps + n_prefills

    def test_readback_is_deferred_behind_inflight_window(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8, inflight=3))
        prompt = np.random.RandomState(11).randint(0, 97, 4).astype(np.int32)
        req = eng.submit(prompt, max_new_tokens=10)
        eng.step()                    # prefill + dispatch #1
        # admission waited for nothing: the first token is the fifo's
        # oldest entry, still on the chip with the step that read it there
        assert [[s for s, _ in snap] for _, snap, _ in eng._inflight] \
            == [[0], [0]]
        assert req.generated == [] and req.trace.t_first_token is None
        eng.step()                    # dispatch #2: window full, the
        assert len(eng._inflight) == 2          # oldest entry harvested:
        assert len(req.generated) == 1          # the prefill's token
        assert req.trace.t_first_token is not None
        eng.step()                    # dispatch #3: step #1 harvested
        assert len(eng._inflight) == 2
        assert len(req.generated) == 2
        eng.run_until_idle(max_steps=30)
        np.testing.assert_array_equal(req.result(timeout=30),
                                      _fast_ref(m, prompt, 10))

    def test_harvest_spans_carry_every_blocking_readback(self):
        """`engine.harvest` is the engine thread waiting for the device:
        one span a blocking readback, prefill's and decode's told apart,
        each directly under the `engine.step` that made it (none under
        `engine.admit`, which waits for nothing)."""
        import time
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8))
        d2h = metrics.counter("engine.d2h_transfers")
        base, t0 = d2h.value, time.perf_counter()
        req = eng.submit(np.random.RandomState(12).randint(0, 97, 4)
                         .astype(np.int32), 4)
        eng.run_until_idle(max_steps=30)
        assert req.done
        harvests = metrics.spans(name="engine.harvest", since=t0)
        assert len(harvests) == d2h.value - base
        assert [h.args["of"] for h in harvests] == ["prefill"] + ["decode"] * 3
        assert all(h.args["tokens"] == 1 and h.dur > 0 for h in harvests)
        steps = {s.id: s for s in metrics.spans(name="engine.step", since=t0)}
        for h in harvests:
            step = steps[h.parent]
            assert step.t0 <= h.t0 and h.t0 + h.dur <= step.t0 + step.dur

    def test_capacity_guard_retires_instead_of_corrupting(self):
        """Regression (overflow satellite): a sequence about to write past
        pages_per_slot * page_size is retired with an error BEFORE the step
        is scheduled — the trash-page spill on device is the backstop, not
        the path."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8))
        req = eng.submit(np.random.RandomState(13).randint(0, 97, 4)
                         .astype(np.int32), 4)
        eng.step()                              # placed + first decode step
        eng._lengths[0] = eng.slot_capacity     # simulate runaway length
        eng.run_until_idle(max_steps=20)
        with pytest.raises(RuntimeError, match="slot capacity"):
            req.result(timeout=5)
        # pages reclaimed, slot reusable
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1


class TestAbort:
    def test_abort_fails_queued_and_inflight_then_refuses_submits(self):
        """serve_loop's exit path: every outstanding request errors out
        immediately (no client hangs to its timeout), pages are reclaimed,
        and later submits fail fast instead of queueing onto a dead
        engine."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                           min_bucket=8))
        rng = np.random.RandomState(8)
        inflight = eng.submit(rng.randint(0, 97, 4).astype(np.int32), 10)
        queued = eng.submit(rng.randint(0, 97, 4).astype(np.int32), 10)
        eng.step()                              # inflight occupies the slot
        eng.abort("device fell over")
        for req in (inflight, queued):
            with pytest.raises(RuntimeError, match="device fell over"):
                req.result(timeout=5)
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1
        with pytest.raises(RuntimeError, match="engine stopped"):
            eng.submit(rng.randint(0, 97, 4).astype(np.int32), 2)


class TestServeGenerate:
    """GENERATE wire op: scheduler-queue admission over TCP, batched with
    other connections' requests."""

    def _server(self, model, **ekw):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        from paddle_tpu.inference.serve import InferenceServer
        eng = DecodeEngine(model, EngineConfig(
            page_size=4, max_slots=2, min_bucket=8, **ekw))
        srv = InferenceServer(None, engine=eng, auth_name="engine")
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        return srv

    def test_concurrent_clients_match_fast_generate(self):
        from paddle_tpu.inference.serve import RemotePredictor
        m = _tiny_model()
        srv = self._server(m)
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 97, 4 + i).astype(np.int32)
                   for i in range(3)]
        outs = [None] * 3

        def client(i):
            cli = RemotePredictor(port=srv.port, model_prefix="engine")
            outs[i] = cli.generate(prompts[i], max_new_tokens=6)
            cli.close()

        ths = [threading.Thread(target=client, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        for p, o in zip(prompts, outs):
            assert o is not None, "client thread died"
            np.testing.assert_array_equal(o, _fast_ref(m, p, 6))
        cli = RemotePredictor(port=srv.port, model_prefix="engine")
        stats = cli.stats()
        assert stats["counters"]["serve.generate_requests"] >= 3
        cli.shutdown_server()
        cli.close()

    def test_engine_only_server_generates_random_secret(self, monkeypatch):
        """No auth_name and no PADDLE_SERVE_TOKEN: the server must mint a
        RANDOM per-startup secret (r5 advisor — any derivable default digest
        lets whoever can reach the port SHUTDOWN the server). Clients with
        the generated secret connect; a guessed well-known one is dropped."""
        import socket
        import struct
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        from paddle_tpu.inference.serve import (
            MAGIC, InferenceServer, RemotePredictor, auth_token)
        monkeypatch.delenv("PADDLE_SERVE_TOKEN", raising=False)
        eng = DecodeEngine(_tiny_model(), EngineConfig(page_size=4,
                                                       max_slots=1))
        srv = InferenceServer(None, engine=eng)
        assert srv.generated_secret and len(srv.generated_secret) >= 32
        srv2 = InferenceServer(None, engine=eng)
        assert srv2.generated_secret != srv.generated_secret
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        # guessed constants fail: connection dropped before any op
        raw = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
        raw.sendall(struct.pack("<I", MAGIC) + auth_token("None"))
        raw.settimeout(3)
        try:
            assert raw.recv(12) == b""
        except ConnectionResetError:
            pass
        raw.close()
        # the printed secret works
        cli = RemotePredictor(port=srv.port, secret=srv.generated_secret)
        assert cli.ping()
        cli.shutdown_server()
        cli.close()
        # srv2 was never served, but its engine thread runs: stop it, or it
        # outlives this file in its xdist worker and takes the next armed
        # process-wide fault (tests/test_chaos.py arms `engine.crash` once)
        srv2._stop.set()
        srv2._engine_thread.join(timeout=10)
        assert not srv2._engine_thread.is_alive()
        srv2._sock.close()

    def test_legacy_model_prefix_client_with_env_token(self, monkeypatch):
        """Back-compat: the old auth let PADDLE_SERVE_TOKEN beat
        model_prefix on BOTH sides, so a legacy deployment (env set
        everywhere, clients still passing model_prefix=) must keep
        connecting — the legacy alias keeps its legacy precedence."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        from paddle_tpu.inference.serve import InferenceServer, \
            RemotePredictor
        monkeypatch.setenv("PADDLE_SERVE_TOKEN", "legacy-shared-secret")
        eng = DecodeEngine(_tiny_model(), EngineConfig(page_size=4,
                                                       max_slots=1))
        srv = InferenceServer(None, engine=eng)
        assert srv.generated_secret is None      # env var IS the secret
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        cli = RemotePredictor(port=srv.port, model_prefix="/some/model/path")
        assert cli.ping()
        cli.shutdown_server()
        cli.close()

    def test_run_op_rejected_on_engine_only_server(self):
        from paddle_tpu.inference.serve import RemotePredictor
        m = _tiny_model()
        srv = self._server(m)
        cli = RemotePredictor(port=srv.port, model_prefix="engine")
        with pytest.raises(RuntimeError, match="engine-only"):
            cli.run([np.zeros((1, 4), np.float32)])
        cli.close()
        cli2 = RemotePredictor(port=srv.port, model_prefix="engine")
        cli2.shutdown_server()
        cli2.close()


class TestChunkedPrefill:
    """Decode-priority chunked prefill (EngineConfig.prefill_chunk_tokens):
    token-identical to the one-shot bucketed path, and a long prompt no
    longer stalls in-flight decodes for its full prefill wall."""

    def test_token_parity_across_chunk_and_page_boundaries(self):
        """Chunked == unchunked == fast_generate for prompts below the
        chunk size (one-shot path), exactly 2 chunks, ragged tails, and
        chunk edges that straddle page edges (page 4, chunk 8, prompt 33:
        pages and chunks interleave off-phase)."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        rng = np.random.RandomState(5)
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8,
                                           prefill_chunk_tokens=8))
        for s in (5, 16, 20, 33):
            prompt = rng.randint(0, 97, s).astype(np.int32)
            req = eng.submit(prompt, max_new_tokens=10)
            eng.run_until_idle(max_steps=200)
            np.testing.assert_array_equal(req.result(timeout=30),
                                          _fast_ref(m, prompt, 10))

    def test_decodes_keep_running_during_long_prefill(self):
        """The tentpole scheduling property, pinned by ORDERING (no wall
        clocks): two short requests mid-decode finish BEFORE a long
        prompt's first token when its prefill is chunked (one chunk per
        step interleaves with their decode steps) — and AFTER it when the
        prefill is one-shot (the whole wall lands inside one step)."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        rng = np.random.RandomState(6)
        long_prompt = rng.randint(0, 97, 40).astype(np.int32)

        def run(chunk):
            m = _tiny_model()
            eng = DecodeEngine(m, EngineConfig(
                page_size=4, max_slots=4, min_bucket=8,
                prefill_chunk_tokens=chunk))
            eng.warmup(prompt_lens=[3, 40])
            shorts = [eng.submit(rng.randint(0, 97, 3).astype(np.int32),
                                 max_new_tokens=8) for _ in range(2)]
            for _ in range(2):
                eng.step()              # shorts are decoding
            long_req = eng.submit(long_prompt, max_new_tokens=4)
            eng.run_until_idle(max_steps=300)
            for r in shorts + [long_req]:
                assert r.done and r._error is None
            return shorts, long_req

        shorts, long_req = run(chunk=4)    # 10 chunks vs 6 decode steps
        assert all(r.trace.t_done < long_req.trace.t_first_token
                   for r in shorts), (
            "chunked: shorts must finish while the long prompt prefills")
        assert metrics.snapshot()["counters"]["engine.prefill_chunks"] >= 10

        shorts, long_req = run(chunk=None)  # one-shot baseline
        assert all(long_req.trace.t_first_token < r.trace.t_done
                   for r in shorts), (
            "unchunked: the one-shot prefill should finish before the "
            "shorts' remaining decode steps (this is the stall chunking "
            "removes)")

    def test_chunked_abort_reclaims_prefilling_slot(self):
        """abort() mid-chunking: the prefilling request fails with the
        reason, its pages return to the pool, and the engine refuses new
        submits."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8,
                                           prefill_chunk_tokens=8))
        rng = np.random.RandomState(7)
        free0 = eng.allocator.free_pages
        req = eng.submit(rng.randint(0, 97, 30).astype(np.int32), 8)
        eng.step()                        # first chunk only
        assert not req.done
        eng.abort("test kill")
        with pytest.raises(RuntimeError, match="test kill"):
            req.result(timeout=5)
        assert eng.allocator.free_pages == free0
        with pytest.raises(RuntimeError, match="engine stopped"):
            eng.submit(rng.randint(0, 97, 3).astype(np.int32), 2)


class TestKVHandoff:
    """Page-granular KV export/import (KVHandoff): prefill on one engine,
    decode on another, token-identical to never having moved."""

    def test_round_trip_matches_same_engine_decode(self):
        from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                                 KVHandoff)
        m = _tiny_model()
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, 97, 21).astype(np.int32)
        ref = _fast_ref(m, prompt, 12)

        # exporter uses CHUNKED prefill, importer is a plain engine: the
        # handoff format is scheduler-agnostic
        eng_a = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                             min_bucket=8,
                                             prefill_chunk_tokens=8))
        eng_b = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                             min_bucket=8))
        h = eng_a.prefill_export(prompt)
        assert eng_a.allocator.free_pages == eng_a.allocator.num_pages - 1
        blob = h.pack()
        h2 = KVHandoff.unpack(blob)
        np.testing.assert_array_equal(h2.k_pages, h.k_pages)
        req = eng_b.import_request(h2, max_new_tokens=12)
        eng_b.run_until_idle(max_steps=100)
        np.testing.assert_array_equal(req.result(timeout=30), ref)

    def test_import_shares_decode_batch_with_local_requests(self):
        """An imported request decodes alongside locally-prefilled ones in
        the same fixed-shape step."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        rng = np.random.RandomState(9)
        p_remote = rng.randint(0, 97, 9).astype(np.int32)
        p_local = rng.randint(0, 97, 6).astype(np.int32)
        eng_a = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                             min_bucket=8))
        eng_b = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                             min_bucket=8))
        h = eng_a.prefill_export(p_remote)
        r_local = eng_b.submit(p_local, max_new_tokens=8)
        r_remote = eng_b.import_request(h, max_new_tokens=8)
        eng_b.run_until_idle(max_steps=100)
        np.testing.assert_array_equal(r_remote.result(timeout=30),
                                      _fast_ref(m, p_remote, 8))
        np.testing.assert_array_equal(r_local.result(timeout=30),
                                      _fast_ref(m, p_local, 8))

    def test_geometry_mismatch_refused(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        rng = np.random.RandomState(10)
        prompt = rng.randint(0, 97, 9).astype(np.int32)
        eng_a = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                             min_bucket=8))
        h = eng_a.prefill_export(prompt)
        eng_psize = DecodeEngine(m, EngineConfig(page_size=8, max_slots=1,
                                                 min_bucket=8))
        with pytest.raises(ValueError, match="page_size mismatch"):
            eng_psize.import_request(h, max_new_tokens=4)
        m2 = _tiny_model(seed=8)
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        import paddle_tpu as paddle
        paddle.seed(8)
        cfg4 = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                         num_heads=4, intermediate_size=64,
                         max_position_embeddings=64, hidden_dropout=0.0,
                         attention_dropout=0.0)
        eng_heads = DecodeEngine(GPTForCausalLM(cfg4),
                                 EngineConfig(page_size=4, max_slots=1,
                                              min_bucket=8))
        with pytest.raises(ValueError, match="geometry mismatch"):
            eng_heads.import_request(h, max_new_tokens=4)
        del m2
