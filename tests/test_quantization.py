"""Quantization end-to-end (docs/QUANTIZATION.md): int8 KV pages, weight-only
int8 serving, quantized allreduce.

The contracts under test:

- **int8 KV numerics** — prefill/decode logits stay within the documented
  bound of f32 (QUANT_LOGIT_BOUND), and wherever f32's top-1 margin clears
  2x the bound the int8 top-1 token is identical (margin-gated parity).
- **int8 KV path identity** — quantization error is a property of the
  CACHE, not the path through it: one-shot prefill, chunked prefill,
  prefix-cache hits, speculative decode, and a KV-handoff round trip all
  emit EXACTLY the same tokens on an int8 engine (each path conditions on
  the same quantized pages by construction).
- **weight-only int8** — matmul leaves convert to int8 + per-channel scales
  with a per-element error bound of scale/2, dequantized at use inside the
  same programs.
- **quantized allreduce** — blockwise abs-max int8: per-block error bound
  (`comms.roundtrip_bound`), >= 3x payload-bytes reduction provable from
  the `collective.bytes` counters, in-graph parity under shard_map.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference.engine import (DecodeEngine, EngineConfig,
                                         KVHandoff)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.observability import metrics
from paddle_tpu.quantization import comms
from paddle_tpu.quantization.serving import (QUANT_LOGIT_BOUND,
                                             QuantizedLeaf,
                                             margin_gated_parity,
                                             quantize_gpt_params)


def _tiny_model(seed=11, max_pos=64):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    intermediate_size=64, max_position_embeddings=max_pos,
                    hidden_dropout=0.0, attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _run_engine(model, prompt, n, **ecfg):
    eng = DecodeEngine(model, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, **ecfg))
    r = eng.submit(prompt, max_new_tokens=n)
    eng.run_until_idle(max_steps=200)
    return r.result(timeout=30), eng


def _margin_gated_match(lg_f, lg_q):
    """The documented parity check (`margin_gated_parity` — the one
    implementation, shared with bench.py's kv_quant_ok), assert-flavored."""
    diff, ok = margin_gated_parity(lg_f, lg_q)
    assert ok, (f"int8 parity violated: logit diff {diff} vs bound "
                f"{QUANT_LOGIT_BOUND} (or top-1 diverged on a "
                "wide-margin position)")
    return diff


# ---------------------------------------------------------------- int8 KV


def _int8_pool(rng, nl, npages, ps, nh, dh):
    """A random int8 pool in the stored layout [nl, P, ps, nh*dh] and its
    [nl, P, ps, nh] scales."""
    from paddle_tpu.kernels import paged_attention as pa
    q, s = pa.quantize_kv(jnp.asarray(
        rng.randn(nl, npages, ps, nh, dh).astype(np.float32)))
    return q.reshape(nl, npages, ps, nh * dh), s


class TestInt8KV:
    def _pools(self, cfg, npg, ps, quant):
        # the stored layout: [nl, P, ps, nh*dh] values, [nl, P, ps, nh] scales
        shape = (cfg.num_layers, npg, ps, cfg.hidden_size)
        if quant:
            z = jnp.zeros(shape, jnp.int8)
            s = jnp.zeros(shape[:3] + (cfg.num_heads,), jnp.float32)
            return z, jnp.zeros_like(z), s, jnp.zeros_like(s)
        z = jnp.zeros(shape, jnp.float32)
        return z, jnp.zeros_like(z), None, None

    def test_prefill_and_decode_logits_within_bound(self):
        """f32 vs int8 caches, gpt-function level, across a page boundary:
        prefill logits AND three decode steps' logits stay within the
        documented bound with margin-gated top-1 agreement."""
        m = _tiny_model()
        cfg = m.cfg
        params = gpt_mod.serving_params(m.state_dict())
        ps, s0 = 4, 10                      # prompt spans 2.5 pages
        npg = 8
        row = jnp.pad(jnp.arange(1, 5, dtype=jnp.int32), (0, 12))[:16]
        ids = jnp.asarray(np.random.RandomState(0)
                          .randint(0, 64, s0).astype(np.int32))
        kf, vf, _, _ = self._pools(cfg, npg, ps, quant=False)
        lg_f, kf, vf = gpt_mod.prefill_step(
            params, ids, jnp.int32(s0), row[:4], kf, vf, cfg=cfg)
        kq, vq, ks, vs = self._pools(cfg, npg, ps, quant=True)
        lg_q, kq, vq, ks, vs = gpt_mod.prefill_step(
            params, ids, jnp.int32(s0), row[:4], kq, vq, cfg=cfg,
            k_scale=ks, v_scale=vs)
        _margin_gated_match(lg_f, lg_q)

        # decode: both caches advance with their OWN sampled tokens —
        # greedy chains can diverge at narrow margins, so each path is
        # compared as its own trajectory, logits-bounded stepwise from a
        # shared state only for the FIRST step
        tok = jnp.argmax(lg_f)[None].astype(jnp.int32)
        table = row[:4][None]
        cache_f = dict(k_pages=kf, v_pages=vf, page_table=table,
                       lengths=jnp.asarray([s0], jnp.int32))
        cache_q = dict(k_pages=kq, v_pages=vq, page_table=table,
                       lengths=jnp.asarray([s0], jnp.int32),
                       k_scale=ks, v_scale=vs)
        mask = jnp.asarray([True])
        dl_f, cache_f = gpt_mod.decode_step(params, tok, cache_f, mask,
                                            cfg=cfg)
        dl_q, cache_q = gpt_mod.decode_step(params, tok, cache_q, mask,
                                            cfg=cfg)
        _margin_gated_match(dl_f, dl_q)
        assert cache_q["k_pages"].dtype == jnp.int8
        assert cache_q["k_pages"].shape == (cfg.num_layers, npg, ps,
                                            cfg.hidden_size)
        assert cache_q["k_scale"].shape == (cfg.num_layers, npg, ps,
                                            cfg.num_heads)

    def test_cross_path_token_identity(self):
        """The engine acceptance contract: every int8 path — one-shot,
        chunked prefill, prefix-cache hit, speculative decode, handoff
        round trip — emits the SAME tokens (page boundaries crossed: the
        13-token prompt spans 3.25 pages of 4)."""
        m = _tiny_model()
        rng = np.random.RandomState(3)
        prompt = np.tile(rng.randint(0, 64, 4), 4)[:13].astype(np.int32)
        base, _ = _run_engine(m, prompt, 8, kv_dtype="int8")

        chunked, _ = _run_engine(m, prompt, 8, kv_dtype="int8",
                                 prefill_chunk_tokens=4)
        assert np.array_equal(base, chunked), "chunked diverged"

        # prefix hit: same engine, resubmit — cached pages attach
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, kv_dtype="int8"))
        r1 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=200)
        miss = r1.result(timeout=30)
        h0 = metrics.counter("engine.prefix_hit").value
        r2 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=200)
        hit = r2.result(timeout=30)
        assert metrics.counter("engine.prefix_hit").value == h0 + 1
        assert np.array_equal(miss, hit), \
            "a prefix-cache hit changed int8 tokens — scales must ride " \
            "the shared pages"
        assert np.array_equal(base, miss)

        spec, _ = _run_engine(m, prompt, 8, kv_dtype="int8", speculate_k=3,
                              prefix_cache=False)
        assert np.array_equal(base, spec), "speculative int8 diverged"

        src = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, kv_dtype="int8"))
        blob = src.prefill_export(prompt).pack()
        dst = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, kv_dtype="int8"))
        r = dst.import_request(KVHandoff.unpack(blob), max_new_tokens=8)
        dst.run_until_idle(max_steps=200)
        assert np.array_equal(base, r.result(timeout=30)), \
            "handoff round trip diverged"

    def test_handoff_blob_carries_scales_and_refuses_mismatch(self):
        m = _tiny_model()
        prompt = np.random.RandomState(5).randint(0, 64, 9).astype(np.int32)
        src = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, kv_dtype="int8"))
        h = src.prefill_export(prompt)
        assert h.cache_dtype == "int8" and h.k_scales is not None
        assert h.k_scales.shape == h.k_pages.shape[:-1]
        h2 = KVHandoff.unpack(h.pack())
        np.testing.assert_array_equal(h.k_pages, h2.k_pages)
        np.testing.assert_array_equal(h.k_scales, h2.k_scales)
        np.testing.assert_array_equal(h.v_scales, h2.v_scales)

        # dtype refusal both directions — never a silent cast
        f32_eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                               min_bucket=8))
        with pytest.raises(ValueError, match="dtype mismatch"):
            f32_eng.import_request(h2, max_new_tokens=4)
        fh = f32_eng.prefill_export(prompt)
        int8_eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                                min_bucket=8,
                                                kv_dtype="int8"))
        with pytest.raises(ValueError, match="dtype mismatch"):
            int8_eng.import_request(fh, max_new_tokens=4)

        # a tampered blob — int8 dtype but scales stripped — refuses loudly
        import json as _json
        import struct as _struct
        raw = h.pack()
        mlen = len(KVHandoff.MAGIC)
        (hlen,) = _struct.unpack("<I", raw[mlen:mlen + 4])
        head = _json.loads(raw[mlen + 4:mlen + 4 + hlen].decode())
        del head["scales_shape"]
        hb = _json.dumps(head).encode()
        tampered = (KVHandoff.MAGIC + _struct.pack("<I", len(hb)) + hb
                    + raw[mlen + 4 + hlen:])
        with pytest.raises(ValueError, match="scales"):
            KVHandoff.unpack(tampered)

    def test_kv_bytes_per_token_and_capacity_ratio(self):
        """The capacity arithmetic the bench rung's >= 1.9x assertion rides:
        int8 per-token bytes (values + scales) vs f32."""
        m = _tiny_model()
        _, f32_eng = _run_engine(m, np.arange(1, 6, dtype=np.int32), 2)
        _, q_eng = _run_engine(m, np.arange(1, 6, dtype=np.int32), 2,
                               kv_dtype="int8")
        nh = m.cfg.num_heads
        dh = m.cfg.hidden_size // nh
        nl = m.cfg.num_layers
        assert f32_eng.kv_bytes_per_token == nl * 2 * nh * dh * 4
        assert q_eng.kv_bytes_per_token == nl * 2 * (nh * dh + nh * 4)
        assert f32_eng.kv_bytes_per_token / q_eng.kv_bytes_per_token >= 1.9
        assert metrics.gauge("engine.kv_bytes_per_token").value > 0

    def test_bf16_pool_and_bad_dtype(self):
        m = _tiny_model()
        prompt = np.arange(1, 8, dtype=np.int32)
        out, eng = _run_engine(m, prompt, 3, kv_dtype="bf16")
        assert out.shape == (10,)
        assert eng._kc.dtype == jnp.bfloat16
        with pytest.raises(ValueError, match="kv_dtype"):
            DecodeEngine(m, EngineConfig(kv_dtype="fp4"))

    def test_autotune_int8_measures_with_real_dtype(self, monkeypatch):
        """`auto` dispatch on an int8 pool must MEASURE when the backend
        has >1 candidate: the selection builds its synthetic arrays from the
        real q dtype and the int8-ness rides the key's suffix — a
        composite dtype string would crash `.astype` on the TPU path the
        feature targets (single-candidate CPU short-circuits never reach
        it, hence this forced two-candidate pin)."""
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.kernels import paged_attention as pa, registry
        monkeypatch.setattr(registry.ops()["paged_attention"], "candidates",
                            lambda ctx: ["xla", "pallas"])
        rng = np.random.RandomState(2)
        b, nh, dh, ps, maxp = 2, 1, 8, 4, 3   # unique geometry: fresh key
        npages = 1 + b * maxp
        q = jnp.asarray(rng.randn(b, nh, dh).astype(np.float32))
        kq, ks = _int8_pool(rng, 2, npages, ps, nh, dh)
        vq, vs = _int8_pool(rng, 2, npages, ps, nh, dh)
        pt = jnp.asarray(np.arange(1, npages).reshape(b, maxp)
                         .astype(np.int32))
        pos = jnp.asarray(np.array([2, 9], np.int32))
        set_flags({"tpu_paged_impl": "auto"})
        try:
            out = pa.paged_attention(q, kq, vq, pt, pos,
                                     k_scale=ks, v_scale=vs, layer=1)
        finally:
            set_flags({"tpu_paged_impl": "auto"})
        ref = pa._xla_paged_attention(q, kq, vq, pt, pos, 1,
                                      k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        # the measured winner landed under the variant-suffixed key
        assert any(k[0] == "paged" and str(k[-1]).endswith("/kv-int8")
                   for k in registry.table()), registry.table().keys()

    @pytest.mark.parametrize("layer", [0, 2])
    def test_pallas_int8_parity(self, layer):
        """The Pallas kernel's in-register dequant matches the XLA gather
        path bit-for-f32-bit on the same int8 pages (interpret mode) of
        the stored pool's first and last layer, the per-layer form of that
        layer gives the same bits, and the ragged length-aware stop still
        holds."""
        from paddle_tpu.kernels import paged_attention as pa
        from paddle_tpu.kernels.pallas.paged_attention import (
            paged_attention as pallas_paged)
        rng = np.random.RandomState(0)
        B, nh, dh, ps, maxp = 3, 2, 8, 4, 4
        npages = 1 + B * maxp
        q = jnp.asarray(rng.randn(B, nh, dh).astype(np.float32))
        kq, ks = _int8_pool(rng, 3, npages, ps, nh, dh)
        vq, vs = _int8_pool(rng, 3, npages, ps, nh, dh)
        pt = jnp.asarray(rng.permutation(np.arange(1, npages))
                         .reshape(B, maxp).astype(np.int32))
        pos = jnp.asarray(np.array([2, 7, 13], np.int32))
        ref = pa._xla_paged_attention(q, kq, vq, pt, pos, layer,
                                      k_scale=ks, v_scale=vs)
        out, visits = pallas_paged(q, kq, vq, pt, pos, layer=layer,
                                   k_scale=ks, v_scale=vs, interpret=True,
                                   return_visits=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)
        one = pallas_paged(
            q, kq[layer].reshape(npages, ps, nh, dh),
            vq[layer].reshape(npages, ps, nh, dh), pt, pos,
            k_scale=ks[layer], v_scale=vs[layer], interpret=True)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(out))
        np.testing.assert_array_equal(
            np.asarray(visits)[:, 0], (np.asarray(pos) + ps) // ps)


# ---------------------------------------------------------- weight int8


class TestWeightInt8:
    def test_quantize_state_dict_leaves(self):
        m = _tiny_model()
        params = {k: t._data for k, t in m.state_dict().items()}
        qp = quantize_gpt_params(params)
        for k, v in qp.items():
            if any(k.endswith(s) for s in
                   ("attn.qkv_proj.weight", "attn.out_proj.weight",
                    "mlp.fc_in.weight", "mlp.fc_out.weight")):
                assert isinstance(v, QuantizedLeaf), k
                orig = np.asarray(params[k], np.float32)
                deq = np.asarray(v.dequant(), np.float32)
                # per-element bound: half a step of the channel's scale
                bound = np.broadcast_to(np.asarray(v.scale) / 2.0,
                                        orig.shape)
                assert (np.abs(orig - deq) <= bound + 1e-7).all(), k
                assert v.q.dtype == jnp.int8
            else:
                assert v is params[k], f"non-matmul leaf {k} was touched"
        with pytest.raises(ValueError, match="weight_dtype"):
            quantize_gpt_params(params, dtype="fp8")

    def test_quantize_stacked_layout_usable_in_scan(self):
        """Stacked quantization is checked at USE, not just structure: the
        scanned forward dequantizes the sliced leaves in the scan body, so
        `scan_logits` over quantized stacked params runs and stays
        margin-gated-close to the float forward."""
        from paddle_tpu.models.gpt import scan_logits, stack_gpt_params
        m = _tiny_model()
        params = {k: t._data for k, t in m.state_dict().items()}
        stacked = stack_gpt_params(params)
        qs = quantize_gpt_params(stacked)
        leaf = qs["blocks"]["mlp.fc_in.weight"]
        assert isinstance(leaf, QuantizedLeaf)
        # per-layer per-channel: the scale keeps the [nl] axis
        assert leaf.scale.shape == (m.cfg.num_layers, 1,
                                    m.cfg.intermediate_size)
        assert isinstance(qs["blocks"]["ln_1.weight"], jnp.ndarray)
        ids = jnp.asarray(np.random.RandomState(4)
                          .randint(0, 64, (2, 8)).astype(np.int32))
        lg_f = scan_logits(stacked, ids, m.cfg, training=False)
        lg_q = scan_logits(qs, ids, m.cfg, training=False)
        _margin_gated_match(lg_f, lg_q)

    def test_engine_weight_int8_decodes_within_bound(self):
        """weight_dtype='int8' decodes through the same warm programs; the
        first sampled token's logits stay margin-gated-close to float."""
        m = _tiny_model()
        prompt = np.random.RandomState(7).randint(0, 64, 9).astype(np.int32)
        base, _ = _run_engine(m, prompt, 4)
        out, eng = _run_engine(m, prompt, 4, weight_dtype="int8")
        assert out.shape == base.shape
        assert isinstance(eng._params["blocks.mlp.fc_in.weight"][0],
                          QuantizedLeaf)
        # refresh keeps the quantized pytree STRUCTURE (hot swap, not a
        # structure mismatch at the next warm call)
        eng.refresh_params(m)
        assert isinstance(eng._params["blocks.mlp.fc_in.weight"][0],
                          QuantizedLeaf)
        r = eng.submit(prompt, max_new_tokens=2)
        eng.run_until_idle(max_steps=60)
        assert r.result(timeout=30).shape == (11,)

    def test_weight_int8_logits_bound(self):
        m = _tiny_model()
        cfg = m.cfg
        params = gpt_mod.serving_params(m.state_dict())
        qp = quantize_gpt_params(params)
        ids = jnp.asarray(np.random.RandomState(1)
                          .randint(0, 64, 6).astype(np.int32))
        row = jnp.pad(jnp.arange(1, 3, dtype=jnp.int32), (0, 14))
        z = jnp.zeros((cfg.num_layers, 3, 4, cfg.hidden_size), jnp.float32)
        lg_f, _, _ = gpt_mod.prefill_step(params, ids, jnp.int32(6),
                                          row[:2], z, jnp.zeros_like(z),
                                          cfg=cfg)
        lg_q, _, _ = gpt_mod.prefill_step(qp, ids, jnp.int32(6), row[:2],
                                          jnp.zeros_like(z),
                                          jnp.zeros_like(z), cfg=cfg)
        _margin_gated_match(lg_f, lg_q)

    def test_partial_rank_spec_scale_sharding(self):
        """A PartitionSpec shorter than the leaf's rank (trailing axes
        replicated) must still drop the CONTRACTION shard from the scale:
        ('mp',) on a 2D [in, out] leaf shards the contraction axis — the
        scale's matching axis is size 1 and must come back unsharded."""
        from jax.sharding import Mesh, NamedSharding
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        w = jax.device_put(
            jnp.asarray(np.random.RandomState(2)
                        .randn(16, 8).astype(np.float32)),
            NamedSharding(mesh, P("mp")))
        qp = quantize_gpt_params({"gpt.h.0.mlp.fc_in.weight": w})
        leaf = qp["gpt.h.0.mlp.fc_in.weight"]
        assert leaf.q.sharding.spec == P("mp")        # values keep placement
        assert all(x is None for x in leaf.scale.sharding.spec)
        np.testing.assert_allclose(np.asarray(leaf.dequant()),
                                   np.asarray(w), atol=float(
                                       np.abs(np.asarray(w)).max() / 127))


# ----------------------------------------------------- quantized allreduce


class TestQuantizedAllreduce:
    def test_codec_roundtrip_bound(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(777).astype(np.float32) * 5)
        q, s, meta = comms.quantize_blockwise(x, 64)
        assert q.dtype == jnp.int8 and q.shape == (13, 64)
        back = comms.dequantize_blockwise(q, s, meta)
        assert back.shape == x.shape
        err = np.abs(np.asarray(back) - np.asarray(x))
        bound = np.asarray(comms.roundtrip_bound(x, 64))
        assert (err <= bound + 1e-7).all()
        # worst block's bound is still tiny relative to its abs-max
        assert bound.max() <= np.abs(np.asarray(x)).max() / 127.0 + 1e-7

    def test_local_allreduce_bound_and_payload(self):
        rng = np.random.RandomState(1)
        x = rng.randn(4096).astype(np.float32)

        def bytes_now():
            snap = metrics.snapshot()["counters"]
            return sum(v for k, v in snap.items()
                       if k.startswith("collective.bytes"))

        t = paddle.to_tensor(x.copy())
        b0 = bytes_now()
        dist.all_reduce(t)
        plain = bytes_now() - b0
        qc0 = metrics.snapshot()["counters"].get(
            "collective.quantized_calls", 0)
        tq = paddle.to_tensor(x.copy())
        b1 = bytes_now()
        dist.all_reduce(tq, quantized=True)
        quant = bytes_now() - b1
        assert plain / quant >= 3.0, (plain, quant)
        assert metrics.snapshot()["counters"][
            "collective.quantized_calls"] == qc0 + 1
        err = np.abs(np.asarray(tq._data) - x)
        bound = np.asarray(comms.roundtrip_bound(jnp.asarray(x)))
        assert (err <= bound + 1e-7).all()

    def test_avg_and_unsupported_ops(self):
        x = np.random.RandomState(2).randn(100).astype(np.float32)
        t = paddle.to_tensor(x.copy())
        dist.all_reduce(t, op=dist.ReduceOp.AVG, quantized=True)
        err = np.abs(np.asarray(t._data) - x)   # 1 participant: avg == x
        bound = np.asarray(comms.roundtrip_bound(jnp.asarray(x)))
        assert (err <= bound + 1e-7).all()
        for op in (dist.ReduceOp.MAX, dist.ReduceOp.MIN,
                   dist.ReduceOp.PROD):
            with pytest.raises(ValueError, match="SUM/AVG"):
                dist.all_reduce(paddle.to_tensor(x.copy()), op=op,
                                quantized=True)

    def test_in_graph_quantized_sum(self):
        """In-graph path under shard_map over 8 virtual devices: the
        quantized SUM lands within the ACCUMULATED per-rank bound of the
        exact sum (each participant contributes its own round-trip error)."""
        n_dev = 8
        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("x",))
        g = dist.new_group(axis_name="x")
        rng = np.random.RandomState(3)
        x = rng.randn(n_dev, 512).astype(np.float32)

        def body(a):
            t = Tensor(a, _internal=True)
            dist.all_reduce(t, group=g, quantized=True)
            return t._data

        f = shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                      check_rep=False)
        out = np.asarray(jax.jit(f)(x))
        expect = np.tile(x.sum(axis=0), (n_dev, 1)).reshape(out.shape)
        bound = sum(np.asarray(comms.roundtrip_bound(jnp.asarray(x[i])))
                    for i in range(n_dev))
        assert (np.abs(out - expect.reshape(out.shape))
                <= np.tile(bound, (n_dev, 1)).reshape(out.shape)
                + 1e-6).all()
