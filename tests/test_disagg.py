"""Disaggregated serving (paddle_tpu/serving/disagg.py + the engine/
serve/router wiring, docs/SERVING.md "Disaggregated serving"): the
arbitrary-role lease scheme, the PTKS1 page-stream wire format and its
corruption refusals, prefill->decode token parity (f32, int8-KV and
speculative decode pinned), the decode-tier zero-prefill-programs pin,
fleet-wide once-per-prefix accounting through the router's affinity
directory, and the mid-stream prefill-worker-death fallback (chaos).

Replicas are real in-process InferenceServers with real engines on CPU;
every routed answer is checked token-identical against dense
`fast_generate`, so the two-phase flow can never pass by answering the
wrong tokens.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics

FLEET_SECRET = "test-fleet"


def _tiny_model(seed=7):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _fast_ref(model, prompt, n):
    ids = paddle.Tensor(np.asarray(prompt)[None].astype(np.int32),
                        _internal=True)
    return np.asarray(model.fast_generate(ids, max_new_tokens=n).numpy())[0]


def _engine(model, **ekw):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    kw = dict(page_size=4, max_slots=2, min_bucket=8)
    kw.update(ekw)
    return DecodeEngine(model, EngineConfig(**kw))


def _replica(model, role="both", **ekw):
    from paddle_tpu.inference.serve import InferenceServer
    srv = InferenceServer(None, engine=_engine(model, **ekw),
                          auth_name=FLEET_SECRET, role=role)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _router(**kw):
    from paddle_tpu.serving import Router
    kw.setdefault("replica_secret", FLEET_SECRET)
    kw.setdefault("auth_name", "router-front")
    kw.setdefault("page_size", 4)
    router = Router(**kw)
    threading.Thread(target=router.serve_forever, daemon=True).start()
    return router


def _client(router):
    from paddle_tpu.inference.serve import RemotePredictor
    return RemotePredictor(port=router.port, secret="router-front")


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def _run_stream(eng, prompt, cache=True):
    """Drive one engine-level prefill-stream job and return its records."""
    sink = eng.submit_prefill_stream(prompt, cache=cache)
    eng.step()
    items = []
    while True:
        kind, val = sink.get(timeout=30)
        items.append((kind, val))
        if kind in ("done", "err"):
            break
    assert items[0][0] == "count", items[0]
    assert items[-1][0] == "done", items[-1]
    recs = [v for k, v in items if k == "rec"]
    assert len(recs) == items[0][1], (len(recs), items[0][1])
    return recs


def _assemble(records):
    from paddle_tpu.serving.disagg import KVStreamAssembler
    asm = KVStreamAssembler()
    out = None
    for r in records:
        out = asm.feed(r)
    assert out is not None, "stream ended without a final record"
    return out


# ---------------------------------------------------------------- roles


class TestRoleScheme:
    """elastic.py's arbitrary-role lease scheme: one parser for
    router:/prefill:/decode: (and future roles), with the legacy
    unprefixed-replica back-compat PINNED."""

    def test_role_round_trip(self):
        from paddle_tpu.distributed.fleet.elastic import (node_role,
                                                          role_node_id,
                                                          router_node_id)
        assert role_node_id("prefill", "p0") == "prefill:p0"
        assert node_role(role_node_id("prefill", "p0")) == "prefill"
        assert node_role(role_node_id("decode", "d1")) == "decode"
        # router_node_id is now a role_node_id alias — same lease format
        assert router_node_id("x") == role_node_id("router", "x")
        assert node_role(router_node_id("x")) == "router"

    def test_legacy_unprefixed_ids_stay_replicas(self):
        """Test-pinned back-compat: every pre-role lease id — and any id
        whose colon prefix is not a role token — is a replica."""
        from paddle_tpu.distributed.fleet.elastic import node_role
        for legacy in ("replica-123", "legacy-id", "r0", "node_7",
                       "NotARole:x", "1bad:x", ":empty", "with space:x"):
            assert node_role(legacy) == "replica", legacy

    def test_invalid_role_token_refused(self):
        from paddle_tpu.distributed.fleet.elastic import role_node_id
        for bad in ("Bad", "has space", "", "1digit", "way" + "x" * 40):
            with pytest.raises(ValueError):
                role_node_id(bad, "id")

    def test_unknown_role_prefix_stays_a_migration_peer(self):
        """Back-compat for ids whose colon prefix merely PARSES as a
        role (e.g. a legacy ``east-1:replica-3``): the peer-discovery
        and rotation filters are NEGATIVE (exclude only the known
        non-decoding roles), so such a lease keeps its PR-12 behavior
        as a decode-capable replica."""
        from paddle_tpu.inference.serve import InferenceServer

        class _FakeReg:
            node_id = "self"
            endpoint = "h:1"

            def alive_nodes(self):
                return {"east-1:replica-3": "h:2", "router:r": "h:3",
                        "prefill:p": "h:4", "legacy": "h:5",
                        "decode:d": "h:6"}

        srv = InferenceServer.__new__(InferenceServer)
        srv._registry = _FakeReg()
        assert srv._discover_peers() == ["h:6", "h:2", "h:5"] \
            or set(srv._discover_peers()) == {"h:2", "h:5", "h:6"}
        # and the router keeps it in rotation as a 'both'-tier replica
        from paddle_tpu.serving.router import ReplicaState
        assert ReplicaState("east-1:replica-3", "h:2").role == "both"


# ------------------------------------------------------------ wire format


class TestStreamFormat:
    """The PTKS1 page stream: legacy back-compat, round trips, and the
    corruption refusals (ISSUE satellite: typed HandoffCorrupt BEFORE
    any page is adopted)."""

    def test_legacy_one_shot_blob_imports_unchanged(self):
        """A pre-stream PTKV1 blob through the assembler is a complete
        stream of one — old senders keep working."""
        model = _tiny_model()
        src, dst = _engine(model), _engine(model)
        prompt = (np.arange(10) % 50).astype(np.int32)
        ref = _fast_ref(model, prompt, 6)
        blob = src.prefill_export(prompt).pack()
        h = _assemble([blob])
        req = dst.submit_import(h, max_new_tokens=6)
        dst.run_until_idle(max_steps=64)
        assert np.array_equal(req.result(timeout=30), ref)

    def test_stream_records_round_trip_bit_exact(self):
        model = _tiny_model()
        src = _engine(model)
        from paddle_tpu.serving.disagg import stream_records
        h = src.prefill_export((np.arange(10) % 50).astype(np.int32))
        for ppb in (1, 2, 7):
            got = _assemble(stream_records(h, pages_per_batch=ppb))
            assert np.array_equal(np.asarray(got.k_pages),
                                  np.asarray(h.k_pages))
            assert np.array_equal(np.asarray(got.v_pages),
                                  np.asarray(h.v_pages))
            assert got.first_token == h.first_token
            assert np.array_equal(got.prompt, h.prompt)

    def test_bitflipped_mid_stream_chunk_refused_typed(self):
        from paddle_tpu.inference.errors import HandoffCorrupt
        from paddle_tpu.serving.disagg import KVStreamAssembler
        model = _tiny_model()
        recs = _run_stream(_engine(model),
                           (np.arange(10) % 50).astype(np.int32))
        assert len(recs) >= 3
        asm = KVStreamAssembler()
        asm.feed(recs[0])
        bad = bytearray(recs[1])
        bad[-3] ^= 0x40                      # deep in the page payload
        with pytest.raises(HandoffCorrupt):
            asm.feed(bytes(bad))

    def test_truncated_record_refused_typed(self):
        from paddle_tpu.inference.errors import HandoffCorrupt
        from paddle_tpu.serving.disagg import KVStreamAssembler
        model = _tiny_model()
        recs = _run_stream(_engine(model),
                           (np.arange(10) % 50).astype(np.int32))
        asm = KVStreamAssembler()
        asm.feed(recs[0])
        with pytest.raises(HandoffCorrupt):
            asm.feed(recs[1][:len(recs[1]) // 2])

    def test_out_of_order_and_short_stream_refused(self):
        from paddle_tpu.inference.errors import HandoffCorrupt
        from paddle_tpu.serving.disagg import KVStreamAssembler
        model = _tiny_model()
        recs = _run_stream(_engine(model),
                           (np.arange(10) % 50).astype(np.int32))
        # out of order: a later record where the header should be
        with pytest.raises(HandoffCorrupt):
            KVStreamAssembler().feed(recs[1])
        # skipping a page batch: the final record must refuse (pages
        # missing), never hand back a handoff with silent zero pages
        asm = KVStreamAssembler()
        asm2_recs = [recs[0]] + recs[2:]
        with pytest.raises(HandoffCorrupt):
            for r in asm2_recs:
                asm.feed(r)

    def test_partial_wire_stream_leaves_decode_pool_at_baseline(self):
        """KV_STREAM whose sender dies mid-relay: the decode server's
        connection loop sees EOF mid-receive — no page was adopted, the
        pool stays at baseline, and the replica keeps serving."""
        from paddle_tpu.inference.serve import (MAGIC, OP_KV_STREAM,
                                                auth_token, send_arrays)
        model = _tiny_model()
        srv = _replica(model, role="decode")
        eng = srv._engine
        baseline = eng.allocator.free_pages
        recs = _run_stream(_engine(model),
                           (np.arange(10) % 50).astype(np.int32))
        sock = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
        sock.sendall(struct.pack("<I", MAGIC) + auth_token(FLEET_SECRET))
        # promise options + tag + all records, deliver only the first two
        sock.sendall(struct.pack("<III", MAGIC, OP_KV_STREAM,
                                 2 + len(recs)))
        send_arrays(sock, [np.asarray([6, 1, 1, 0], np.int32),
                           np.zeros(0, np.uint8),
                           np.frombuffer(recs[0], np.uint8)])
        sock.close()
        time.sleep(0.2)
        assert eng.allocator.free_pages == baseline
        # the replica still serves: a clean stream admits and decodes
        h = _assemble(recs)
        req = eng.submit_import(h, max_new_tokens=4)
        eng.run_until_idle(max_steps=64)
        assert req.result(timeout=30) is not None
        srv._stop.set()


# ------------------------------------------------------------ token parity


class TestTokenParity:
    """Disaggregated flow token-identical to symmetric serving — pinned
    for the f32, int8-KV and speculative-decode sources (ISSUE
    acceptance)."""

    def _roundtrip(self, model, prompt, n, src_kw=None, dst_kw=None):
        src = _engine(model, **(src_kw or {}))
        dst = _engine(model, **(dst_kw or {}))
        h = _assemble(_run_stream(src, prompt))
        req = dst.submit_import(h, max_new_tokens=n)
        dst.run_until_idle(max_steps=200)
        out = req.result(timeout=30)
        # the decode engine never compiled a prefill program: the
        # disaggregation no-retrace pin (also in tests/test_no_retrace)
        assert not any(k[0] in ("prefill", "prefill_chunk")
                       for k in dst._programs), list(dst._programs)
        return out

    def test_f32_parity_one_shot_and_chunked_sources(self):
        model = _tiny_model()
        prompt = (np.arange(13) % 60).astype(np.int32)
        ref = _fast_ref(model, prompt, 6)
        out = self._roundtrip(model, prompt, 6)
        assert np.array_equal(out, ref), (out, ref)
        # a chunked prefill worker streams multiple page batches and
        # lands on the same tokens
        out_c = self._roundtrip(model, prompt, 6,
                                src_kw=dict(prefill_chunk_tokens=4))
        assert np.array_equal(out_c, ref), (out_c, ref)

    def test_int8_kv_parity(self):
        """int8 pages + scales travel the stream; decode on the import
        side is token-identical to symmetric int8 serving (the
        documented int8 contract: all int8 paths match each other)."""
        model = _tiny_model()
        prompt = (np.arange(12) % 60).astype(np.int32)
        sym = _engine(model, kv_dtype="int8")
        r = sym.submit(prompt, max_new_tokens=6)
        sym.run_until_idle(max_steps=200)
        ref = r.result(timeout=30)
        out = self._roundtrip(model, prompt, 6,
                              src_kw=dict(kv_dtype="int8"),
                              dst_kw=dict(kv_dtype="int8"))
        assert np.array_equal(out, ref), (out, ref)

    def test_speculative_decode_parity(self):
        """A speculating decode replica resumes from the stream and
        stays bit-identical to plain greedy decode."""
        model = _tiny_model()
        prompt = np.tile((np.arange(6) % 40).astype(np.int32), 2)
        ref = _fast_ref(model, prompt, 8)
        out = self._roundtrip(model, prompt, 8,
                              dst_kw=dict(speculate_k=2, max_slots=2))
        assert np.array_equal(out, ref), (out, ref)
        spec = metrics.snapshot()["counters"].get("engine.spec_steps", 0)
        assert spec >= 1, "speculative path did not run"

    def test_router_and_engine_hash_implementations_agree(self):
        """The fleet directory keys on the SAME rolling hashes the
        engine stores use — a drift would silently zero every affinity
        hit."""
        from paddle_tpu.serving.disagg import prompt_page_hashes
        model = _tiny_model()
        eng = _engine(model)
        ids = (np.arange(17) % 70).astype(np.int32)
        assert eng._page_hashes(ids) == prompt_page_hashes(ids, 4)


# -------------------------------------------------------- fleet directory


class TestPrefixDirectory:
    def test_longest_match_and_register(self):
        from paddle_tpu.serving.disagg import PrefixDirectory
        d = PrefixDirectory()
        h = [bytes([i]) * 16 for i in range(4)]
        d.register(h[:2], "p0")
        assert d.lookup(h) == ("p0", 2)
        d.register(h, "p1")              # longer chain on another worker
        assert d.lookup(h) == ("p1", 4)
        assert d.lookup([b"z" * 16]) == (None, 0)

    def test_invalidate_and_replace(self):
        from paddle_tpu.serving.disagg import PrefixDirectory
        d = PrefixDirectory()
        h = [bytes([i]) * 16 for i in range(4)]
        d.register(h, "p0")
        d.replace("p0", h[:1])           # store evicted pages 1..3
        assert d.lookup(h) == ("p0", 1)
        d.invalidate("p0")               # membership churn
        assert d.lookup(h) == (None, 0)
        assert len(d) == 0

    def test_bounded_lru(self):
        from paddle_tpu.serving.disagg import PrefixDirectory
        d = PrefixDirectory(capacity=3)
        hs = [bytes([i]) * 16 for i in range(5)]
        d.register(hs, "p0")
        assert len(d) == 3
        assert d.lookup(hs[:1]) == (None, 0)      # oldest evicted
        assert d.lookup(hs) == ("p0", 5)


# ------------------------------------------------------------- fleet wire


class TestDisaggFleet:
    """The full two-phase flow over real wire: router + 1 prefill worker
    + decode replicas."""

    def _fleet(self, model, n_decode=1, **router_kw):
        pf = _replica(model, role="prefill", prefill_chunk_tokens=4)
        dcs = [_replica(model, role="decode") for _ in range(n_decode)]
        replicas = {"prefill:p0": f"127.0.0.1:{pf.port}"}
        replicas.update({f"decode:d{i}": f"127.0.0.1:{s.port}"
                         for i, s in enumerate(dcs)})
        router = _router(replicas=replicas, **router_kw)
        return pf, dcs, router

    def test_two_phase_token_identical_with_no_retrace_pin(self):
        model = _tiny_model()
        pf, dcs, router = self._fleet(model)
        cli = _client(router)
        try:
            d0 = _counter("router.disagg_requests")
            prompt = (np.arange(11) % 60).astype(np.int32)
            ref = _fast_ref(model, prompt, 6)
            out = cli.generate(prompt, max_new_tokens=6)
            assert np.array_equal(out, ref), (out, ref)
            assert _counter("router.disagg_requests") == d0 + 1
            # the decode replica compiled ZERO prefill programs
            assert not any(k[0] in ("prefill", "prefill_chunk")
                           for k in dcs[0]._engine._programs)
            # deadline + idempotency key ride the stream options
            out2 = cli.generate(prompt, max_new_tokens=6, deadline_s=30.0,
                                request_key=bytes(range(16)))
            assert np.array_equal(out2, ref)
        finally:
            cli.close()
            router.stop()
            pf._stop.set()
            for s in dcs:
                s._stop.set()

    def test_shared_prefix_prefilled_once_fleet_wide(self):
        """ISSUE acceptance: a shared 2-page system prompt across 8
        requests is prefilled exactly ONCE fleet-wide — the first
        request pays the whole prompt, every later one only its
        uncached tail (engine.prefill_tokens accounting, fleet-global
        because in-process replicas share one registry)."""
        model = _tiny_model()
        pf, dcs, router = self._fleet(model, n_decode=2)
        cli = _client(router)
        try:
            sys_prompt = (np.arange(8) % 60).astype(np.int32)   # 2 pages
            tails = [(np.arange(4) + 10 * i).astype(np.int32) % 90
                     for i in range(8)]
            t0 = _counter("engine.prefill_tokens")
            hits0 = _counter("router.affinity_hits")
            miss0 = _counter("router.affinity_misses")
            for tail in tails:
                prompt = np.concatenate([sys_prompt, tail])
                ref = _fast_ref(model, prompt, 4)
                out = cli.generate(prompt, max_new_tokens=4)
                assert np.array_equal(out, ref), (out, ref)
            spent = _counter("engine.prefill_tokens") - t0
            # first request: whole 12-token prompt; the other seven:
            # 4-token tails only — the 8-token system prompt prefills
            # exactly once across the whole fleet
            assert spent == 12 + 7 * 4, spent
            assert _counter("router.affinity_hits") - hits0 == 7
            assert _counter("router.affinity_misses") - miss0 == 1
        finally:
            cli.close()
            router.stop()
            pf._stop.set()
            for s in dcs:
                s._stop.set()

    @pytest.mark.chaos
    def test_midstream_worker_death_falls_back_zero_errors(self):
        """ISSUE acceptance (chaos-pinned): a prefill worker dying
        MID-STREAM costs zero client-visible errors — the partial pages
        are discarded cleanly and every request completes
        token-identical via the symmetric fallback."""
        from paddle_tpu.testing import faults
        model = _tiny_model()
        pf, dcs, router = self._fleet(model)
        cli = _client(router)
        try:
            prompt = (np.arange(11) % 60).astype(np.int32)
            ref = _fast_ref(model, prompt, 6)
            f0 = _counter("router.disagg_fallbacks")
            baseline = dcs[0]._engine.allocator.free_pages
            with faults.scoped("serve.stream_drop", times=1):
                outs = [cli.generate(prompt, max_new_tokens=6)
                        for _ in range(4)]
            for out in outs:
                assert np.array_equal(out, ref), (out, ref)
            assert _counter("router.disagg_fallbacks") >= f0 + 1
            assert faults.fired("serve.stream_drop") >= 1
            # the decode pool is back at baseline (the partial stream
            # adopted nothing; completed requests released their pages)
            assert dcs[0]._engine.allocator.free_pages == baseline
        finally:
            cli.close()
            router.stop()
            pf._stop.set()
            for s in dcs:
                s._stop.set()

    @pytest.mark.chaos
    def test_stale_directory_drill_still_completes(self):
        """router.stale_directory forces an affinity route on a stale
        entry: the worker just prefills the whole prompt — the
        directory is an optimization, never a correctness dependency."""
        from paddle_tpu.testing import faults
        model = _tiny_model()
        pf, dcs, router = self._fleet(model)
        cli = _client(router)
        try:
            prompt = (np.arange(9) % 60).astype(np.int32)
            ref = _fast_ref(model, prompt, 5)
            with faults.scoped("router.stale_directory", times=1):
                out = cli.generate(prompt, max_new_tokens=5)
            assert np.array_equal(out, ref), (out, ref)
            assert _counter("router.stale_affinity") >= 1
        finally:
            cli.close()
            router.stop()
            pf._stop.set()
            for s in dcs:
                s._stop.set()

    def test_prefill_role_refuses_decode_work(self):
        """Tier discipline: GENERATE against a prefill-role replica is a
        typed wire refusal (the router never routes one there; a direct
        client must not break the no-decode contract either)."""
        from paddle_tpu.inference.serve import RemotePredictor
        model = _tiny_model()
        pf = _replica(model, role="prefill")
        cli = RemotePredictor(port=pf.port, secret=FLEET_SECRET)
        try:
            with pytest.raises(RuntimeError, match="prefill-role"):
                cli.generate(np.arange(6, dtype=np.int32),
                             max_new_tokens=2)
        finally:
            cli.close()
            pf._stop.set()


# ------------------------------------------------------------- observability


class TestDisaggObservability:
    def test_prefix_store_bytes_gauge_and_stats_export(self):
        """ISSUE satellite: engine.prefix_store_bytes tracks the store,
        and the serve STATS payload exports the hashes + page size the
        router directory feeds on."""
        import json as _json

        from paddle_tpu.inference.serve import stats_payload
        model = _tiny_model()
        srv = _replica(model, role="prefill")
        eng = srv._engine
        try:
            recs = _run_stream(eng, (np.arange(8) % 50).astype(np.int32))
            assert recs
            g = metrics.snapshot()["gauges"]
            assert g.get("engine.prefix_pages", 0) >= 1
            expect = g["engine.prefix_pages"] * 4 * eng.kv_bytes_per_token
            assert g.get("engine.prefix_store_bytes") == expect
            snap = _json.loads(stats_payload(srv._stats_extra())
                               .tobytes().decode())
            assert snap["role"] == "prefill"
            assert snap["prefix"]["page_size"] == 4
            assert len(snap["prefix"]["hashes"]) \
                == len(eng.prefix_hashes()) >= 1
            assert metrics.snapshot()["gauges"].get(
                "engine.prefix_exported_hashes", 0) >= 1
        finally:
            srv._stop.set()


# PTKS1 page records of conftest's seeded pages [5, 2, 7] (seed 2600),
# packed by the commit before the pool was stored merged: blake2b-128
PTKS1_DIGESTS = {"f32": "cf510ec4d2f9c62620b8120155697540",
                 "int8": "5df4f7a2e383dd67794ebbd4c74bf7f4"}


@pytest.mark.parametrize("kv", sorted(PTKS1_DIGESTS))
def test_ptks1_record_is_byte_identical_to_the_unmerged_pools(
        kv, seeded_kv_pages):
    """A stream record cut from the merged pool is the record the
    ``[..., nh, dh]`` pool gave, byte for byte, and
    ``import_pages(export_pages(...))`` into another engine's pool at
    other page ids lands every page bit-identical."""
    import hashlib
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.serving.disagg import pack_stream_pages
    ecfg = EngineConfig(page_size=4, max_slots=2, min_bucket=8, kv_dtype=kv)
    src = DecodeEngine(_tiny_model(), ecfg)
    pages = [5, 2, 7]
    seeded_kv_pages(src, pages, 2600)
    blobs = src._cache.export_pages(pages)
    assert hashlib.blake2b(pack_stream_pages(1, 0, *blobs),
                           digest_size=16).hexdigest() == PTKS1_DIGESTS[kv]
    dst = DecodeEngine(_tiny_model(), ecfg)
    there = [1, 8, 3]
    dst._cache = dst._cache.import_pages(there, *blobs)
    for a, b in zip(blobs, dst._cache.export_pages(there)):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(a, b)
    # scales a float pool has no place for, or an int8 pool is not given
    wrong = (blobs[0][..., 0], blobs[0][..., 0]) if kv == "f32" else ()
    with pytest.raises(ValueError, match="page import"):
        dst._cache.import_pages(there, blobs[0], blobs[1], *wrong)

