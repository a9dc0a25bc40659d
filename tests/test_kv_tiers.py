"""Tiered prefix-KV economy: host-RAM/disk spill + re-upload (round 17).

The load-bearing contracts:
- eviction DEMOTES instead of discarding: refcount-0 prefix pages spill
  (values + int8 scales) into a bounded host tier, then a bounded disk
  tier, keyed by the same rolling page-chain hashes as the HBM store;
- a tier hit re-uploads the pages and prefills ONLY the tail — decode is
  TOKEN-IDENTICAL to the cold run on every tier (f32 AND int8), pinned
  via engine.prefill_tokens deltas;
- every tier failure degrades to a clean cold prefill: corrupt/stale
  blobs refuse TYPED (engine.kvtier.refusals) and read as misses, spill
  and re-upload faults never fail a request or leak a page;
- refresh_params flushes the tiers (stale-weights KV must never
  re-upload) and the fleet directory routes spilled prefixes to the one
  replica that can re-upload them.
"""
import hashlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics
from paddle_tpu.testing import faults


def _tiny_model(seed=7, vocab=97, max_pos=64):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=max_pos, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _fast_ref(model, prompt, n, **kw):
    ids = paddle.Tensor(np.asarray(prompt)[None].astype(np.int32),
                        _internal=True)
    return np.asarray(model.fast_generate(ids, max_new_tokens=n,
                                          **kw).numpy())[0]


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def _gauge(name):
    return metrics.snapshot()["gauges"].get(name)


def _engine(m, **kw):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 4)
    kw.setdefault("min_bucket", 8)
    return DecodeEngine(m, EngineConfig(**kw))


def _assert_pool_clean(eng):
    assert eng.allocator.free_pages == eng.allocator.num_pages - 1


# ------------------------------------------------------- store unit tests


SHAPE = (2, 4, 2, 8)                     # (nl, ps, nh, dh)


def _mk_store(host=0, disk=0, disk_dir=None, shape=SHAPE):
    from paddle_tpu.inference.kv_tiers import KVTierStore
    return KVTierStore(host_bytes=host, disk_bytes=disk, disk_dir=disk_dir,
                       page_shape=shape, dtype="float32", scales=False)


def _page(i, shape=SHAPE):
    rng = np.random.RandomState(100 + i)
    h = hashlib.blake2b(b"page-%d" % i, digest_size=16).digest()
    return h, rng.standard_normal(shape).astype(np.float32), \
        rng.standard_normal(shape).astype(np.float32)


def _blob_size():
    """One framed page blob's exact size (salt/epoch fields are
    fixed-width, so every blob of one geometry is the same length)."""
    s = _mk_store(host=1 << 20)
    h, k, v = _page(0)
    return len(s._pack(h, k, v, None, None))


class TestTierStoreUnit:
    """KVTierStore alone: framing, LRU bounds, demotion, typed refusal."""

    def test_host_roundtrip_bit_identical_and_read_through(self):
        s = _mk_store(host=1 << 20)
        h, k, v = _page(1)
        s.put(h, k, v)
        for _ in range(2):               # read-through: a hit keeps the entry
            e = s.get(h)
            assert e is not None and e.tier == "host"
            np.testing.assert_array_equal(e.k, k)
            np.testing.assert_array_equal(e.v, v)
        assert s.hashes() == [h.hex()]
        assert s.get(b"\x00" * 16) is None          # plain miss, no refusal

    def test_host_overflow_demotes_lru_to_disk(self, tmp_path):
        sz = _blob_size()
        s = _mk_store(host=2 * sz, disk=1 << 20, disk_dir=str(tmp_path))
        pages = [_page(i) for i in range(1, 4)]
        for h, k, v in pages:
            s.put(h, k, v)
        # host holds the 2 newest; the oldest DEMOTED to disk, not lost
        assert s.host_pages == 2 and s.disk_pages == 1
        e = s.get(pages[0][0])
        assert e is not None and e.tier == "disk"
        np.testing.assert_array_equal(e.k, pages[0][1])
        # recency: touching page-2 makes page-3 the next demotion victim
        assert s.get(pages[1][0]).tier == "host"
        h4, k4, v4 = _page(4)
        s.put(h4, k4, v4)
        assert s.get(pages[1][0]).tier == "host"
        assert s.get(pages[2][0]).tier == "disk"

    def test_disk_overflow_discards_lru_and_unlinks(self, tmp_path):
        sz = _blob_size()
        s = _mk_store(disk=2 * sz, disk_dir=str(tmp_path))
        pages = [_page(i) for i in range(1, 4)]
        for h, k, v in pages:
            s.put(h, k, v)
        # no host tier: blobs go straight to disk, capacity over history
        assert s.host_pages == 0 and s.disk_pages == 2
        assert len(list(tmp_path.glob("*.ptkt"))) == 2
        ref0 = _counter("engine.kvtier.refusals")
        assert s.get(pages[0][0]) is None           # discarded == plain miss
        assert _counter("engine.kvtier.refusals") == ref0
        assert s.get(pages[2][0]).tier == "disk"

    def test_disk_bitflip_refuses_typed_and_drops_entry(self, tmp_path):
        s = _mk_store(disk=1 << 20, disk_dir=str(tmp_path))
        h, k, v = _page(1)
        s.put(h, k, v)
        (path,) = tmp_path.glob("*.ptkt")
        raw = bytearray(path.read_bytes())
        raw[-1] ^= 0xFF                             # rot one body byte
        path.write_bytes(bytes(raw))
        ref0 = _counter("engine.kvtier.refusals")
        assert s.get(h) is None
        assert _counter("engine.kvtier.refusals") == ref0 + 1
        assert s.disk_pages == 0 and not path.exists()

    def test_flush_empties_tiers_and_stales_prior_blobs(self, tmp_path):
        from paddle_tpu.inference.errors import HandoffCorrupt
        sz = _blob_size()
        s = _mk_store(host=sz, disk=1 << 20, disk_dir=str(tmp_path))
        (h1, k1, v1), (h2, k2, v2) = _page(1), _page(2)
        s.put(h1, k1, v1)
        s.put(h2, k2, v2)                # overflows host -> h1 on disk
        assert s.host_pages == 1 and s.disk_pages == 1
        pre = s._pack(h1, k1, v1, None, None)   # a blob from THIS epoch
        s.flush()
        assert s.host_pages == 0 and s.disk_pages == 0
        assert not list(tmp_path.glob("*.ptkt"))
        # an undeletable/copied-back pre-flush blob refuses as STALE
        with pytest.raises(HandoffCorrupt, match="STALE"):
            s._unpack(h1, pre)

    def test_foreign_magic_key_and_store_all_refuse_typed(self):
        from paddle_tpu.inference.errors import HandoffCorrupt
        s1, s2 = _mk_store(host=1 << 20), _mk_store(host=1 << 20)
        h, k, v = _page(1)
        blob = s1._pack(h, k, v, None, None)
        with pytest.raises(HandoffCorrupt, match="magic"):
            s1._unpack(h, b"NOTKV1" + blob[6:])
        with pytest.raises(HandoffCorrupt, match="key|geometry"):
            s1._unpack(_page(2)[0], blob)           # mis-keyed
        with pytest.raises(HandoffCorrupt, match="STALE"):
            s2._unpack(h, blob)                     # another store's salt


# --------------------------------------------------- engine-level tiering


class TestTierEngine:
    """Spill -> re-upload through the real engine: token identity per
    tier, tail-only prefill (counter-pinned), clean pool bookkeeping."""

    def test_host_tier_hit_token_identical_tail_only(self):
        m = _tiny_model()
        eng = _engine(m, kv_host_tier_bytes=1 << 20)
        prompt = np.random.RandomState(0).randint(0, 97, 17).astype(np.int32)
        ref = _fast_ref(m, prompt, 8)
        r1 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        ev0, dem0 = _counter("engine.prefix_evictions"), \
            _counter("engine.prefix_evictions_demoted")
        eng._shrink_prefix()             # force pressure eviction -> spill
        # 17 tokens at page 4: pages 0..3 full -> 4 cached pages demoted
        assert _counter("engine.prefix_evictions") == ev0 + 4
        assert _counter("engine.prefix_evictions_demoted") == dem0 + 4
        assert _gauge("engine.kvtier.host_pages") == 4
        assert not eng._prefix_pages     # HBM store really is empty
        _assert_pool_clean(eng)
        tok0, hit0, up0 = _counter("engine.prefill_tokens"), \
            _counter("engine.kvtier.hits_host"), \
            _counter("engine.kvtier.reuploads_host")
        r2 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        # the headline: re-uploaded pages cost ZERO prefill-program work —
        # only the 1-token tail ran, and the output is token-identical
        assert _counter("engine.prefill_tokens") - tok0 == 1
        assert _counter("engine.kvtier.hits_host") == hit0 + 4
        assert _counter("engine.kvtier.reuploads_host") == up0 + 4
        _assert_pool_clean(eng)

    def test_disk_tier_hit_token_identical(self, tmp_path):
        m = _tiny_model()
        # host bound too small for one blob: spills land straight on disk
        eng = _engine(m, kv_host_tier_bytes=64,
                      kv_disk_tier_bytes=1 << 20,
                      kv_disk_tier_dir=str(tmp_path))
        prompt = np.random.RandomState(4).randint(0, 97, 17).astype(np.int32)
        ref = _fast_ref(m, prompt, 8)
        r1 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        eng._shrink_prefix()
        assert _gauge("engine.kvtier.host_pages") == 0
        assert _gauge("engine.kvtier.disk_pages") == 4
        assert len(list(tmp_path.glob("*.ptkt"))) == 4
        tok0, hit0, up0 = _counter("engine.prefill_tokens"), \
            _counter("engine.kvtier.hits_disk"), \
            _counter("engine.kvtier.reuploads_disk")
        r2 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        assert _counter("engine.prefill_tokens") - tok0 == 1
        assert _counter("engine.kvtier.hits_disk") == hit0 + 4
        assert _counter("engine.kvtier.reuploads_disk") == up0 + 4
        _assert_pool_clean(eng)

    def test_int8_mixed_tier_chain_bit_identical(self, tmp_path):
        """int8 pools spill values AND scale planes. A host bound of ONE
        blob splits the 4-page chain across tiers (newest in host, rest
        demoted to disk) — the mixed re-upload is still bit-identical to
        the engine's own cold run, tail-only."""
        m = _tiny_model()
        eng = _engine(m, kv_dtype="int8", kv_host_tier_bytes=1000,
                      kv_disk_tier_bytes=1 << 20,
                      kv_disk_tier_dir=str(tmp_path))
        prompt = np.random.RandomState(5).randint(0, 97, 17).astype(np.int32)
        r1 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        cold = r1.result(timeout=30)
        eng._shrink_prefix()
        assert _gauge("engine.kvtier.host_pages") == 1
        assert _gauge("engine.kvtier.disk_pages") == 3
        tok0, uph0, upd0 = _counter("engine.prefill_tokens"), \
            _counter("engine.kvtier.reuploads_host"), \
            _counter("engine.kvtier.reuploads_disk")
        r2 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), cold)
        assert _counter("engine.prefill_tokens") - tok0 == 1
        assert _counter("engine.kvtier.reuploads_host") == uph0 + 1
        assert _counter("engine.kvtier.reuploads_disk") == upd0 + 3
        _assert_pool_clean(eng)

    def test_refresh_params_flushes_every_tier(self, tmp_path):
        """The satellite stale-KV pin, per tier: spilled blobs hold KV
        computed under the OLD weights, so a weight hot-swap must flush
        host AND disk — the resubmission cold-prefills and matches the
        NEW model's reference, with zero tier hits or re-uploads."""
        m = _tiny_model()
        # host bound fits ONE ~2.3 KB f32 page blob: the 4-page spill
        # populates BOTH tiers (newest in host, three demoted to disk)
        eng = _engine(m, kv_host_tier_bytes=2600,
                      kv_disk_tier_bytes=1 << 20,
                      kv_disk_tier_dir=str(tmp_path))
        prompt = np.random.RandomState(13).randint(0, 97, 17)\
            .astype(np.int32)
        r = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r.result(timeout=30),
                                      _fast_ref(m, prompt, 6))
        eng._shrink_prefix()
        assert _gauge("engine.kvtier.host_pages") > 0
        assert _gauge("engine.kvtier.disk_pages") > 0
        m2 = _tiny_model(seed=12)
        eng.refresh_params(m2)
        assert _gauge("engine.kvtier.host_pages") == 0
        assert _gauge("engine.kvtier.disk_pages") == 0
        assert not list(tmp_path.glob("*.ptkt"))
        assert eng.tier_hashes() == []
        hit0 = _counter("engine.kvtier.hits_host") \
            + _counter("engine.kvtier.hits_disk")
        up0 = _counter("engine.kvtier.reuploads_host") \
            + _counter("engine.kvtier.reuploads_disk")
        r2 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30),
                                      _fast_ref(m2, prompt, 6))
        assert _counter("engine.kvtier.hits_host") \
            + _counter("engine.kvtier.hits_disk") == hit0
        assert _counter("engine.kvtier.reuploads_host") \
            + _counter("engine.kvtier.reuploads_disk") == up0
        _assert_pool_clean(eng)

    def test_degradation_level2_demotes_to_host_tier(self):
        """Pressure ladder level 2 sheds cache warmth for capacity — but
        with a host tier configured the warmth is DEMOTED, not lost:
        after the queue drains, the same prefix re-uploads from host RAM
        instead of re-prefilling."""
        m = _tiny_model()
        eng = _engine(m, max_slots=1, max_queue_depth=8,
                      kv_host_tier_bytes=1 << 20)
        rep = np.tile(np.arange(4, dtype=np.int32), 4)   # 16 tokens
        ref = _fast_ref(m, rep, 6)
        a = eng.submit(rep, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(a.result(timeout=30), ref)
        ev0, dem0, disc0 = _counter("engine.prefix_evictions"), \
            _counter("engine.prefix_evictions_demoted"), \
            _counter("engine.prefix_evictions_discarded")
        # a long-running slot + 6 queued = pressure 6/8 -> level 2
        run = eng.submit(rep, max_new_tokens=24)
        eng.step()
        queued = [eng.submit(rep, max_new_tokens=2) for _ in range(6)]
        eng.step()
        assert _gauge("engine.degradation_level") == 2
        ev = _counter("engine.prefix_evictions") - ev0
        assert ev > 0, "level 2 must shed idle prefix pages"
        assert _counter("engine.prefix_evictions_demoted") - dem0 == ev, \
            "with a host tier every level-2 eviction must DEMOTE"
        assert _counter("engine.prefix_evictions_discarded") == disc0
        assert _gauge("engine.kvtier.host_pages") > 0
        up0 = _counter("engine.kvtier.reuploads_host")
        eng.run_until_idle(max_steps=400)
        run.result(timeout=30)
        for q in queued:
            q.result(timeout=30)
        assert _gauge("engine.degradation_level") == 0
        # warmth recovered: backlogged requests on the SAME prefix
        # re-uploaded the demoted pages instead of re-prefilling them
        assert _counter("engine.kvtier.reuploads_host") > up0
        r2 = eng.submit(rep, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        _assert_pool_clean(eng)

    def test_prefill_export_reuploads_from_tier(self):
        """The disaggregated prefill worker rides the same economy: an
        exported handoff after a spill re-uploads the pages, runs only
        the tail, and its page contents + first token are bit-identical
        to the cold export."""
        m = _tiny_model()
        eng = _engine(m, kv_host_tier_bytes=1 << 20, max_slots=2)
        prompt = np.random.RandomState(6).randint(0, 97, 17).astype(np.int32)
        h1 = eng.prefill_export(prompt)
        eng._shrink_prefix()
        assert _gauge("engine.kvtier.host_pages") == 4
        tok0, up0 = _counter("engine.prefill_tokens"), \
            _counter("engine.kvtier.reuploads_host")
        h2 = eng.prefill_export(prompt)
        assert _counter("engine.prefill_tokens") - tok0 == 1
        assert _counter("engine.kvtier.reuploads_host") == up0 + 4
        assert h2.first_token == h1.first_token
        # the four re-uploaded pages are bit-equal; the tail token's K/V is
        # recomputed by the 1-token chunk program where the cold export ran
        # the one-shot program, and two XLA programs may differ in the
        # last ulp
        for new, old in ((h2.k_pages, h1.k_pages), (h2.v_pages, h1.v_pages)):
            np.testing.assert_array_equal(new[:, :4], old[:, :4])
            np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-7)
        _assert_pool_clean(eng)

    @pytest.mark.slow
    def test_stream_prefill_reuploads_token_identical(self):
        """Slow drill: the chunk-streaming prefill path (OP_PREFILL's
        record stream) after a spill ships the re-uploaded pages as its
        resident-prefix record, streams only the tail, and the assembled
        handoff decodes token-identically on a separate decode engine."""
        from tests.test_disagg import _assemble, _run_stream
        m = _tiny_model()
        pf = _engine(m, kv_host_tier_bytes=1 << 20, max_slots=2)
        de = _engine(m)
        prompt = np.random.RandomState(8).randint(0, 97, 17).astype(np.int32)
        ref = _fast_ref(m, prompt, 8)
        cold = _assemble(_run_stream(pf, prompt))
        pf._shrink_prefix()
        tok0 = _counter("engine.prefill_tokens")
        warm = _assemble(_run_stream(pf, prompt))
        assert _counter("engine.prefill_tokens") - tok0 == 1
        assert warm.first_token == cold.first_token
        np.testing.assert_array_equal(warm.k_pages, cold.k_pages)
        np.testing.assert_array_equal(warm.v_pages, cold.v_pages)
        r = de.import_request(warm, max_new_tokens=8)
        de.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r.result(timeout=30), ref)
        _assert_pool_clean(pf)
        _assert_pool_clean(de)


# ----------------------------------------------------------- chaos drills


class TestTierChaos:
    """Every tier fault degrades to a clean cold prefill — counted,
    typed, never fatal, never a leaked page."""

    def test_spill_fail_degrades_to_plain_discard(self):
        m = _tiny_model()
        eng = _engine(m, kv_host_tier_bytes=1 << 20)
        prompt = np.random.RandomState(9).randint(0, 97, 17).astype(np.int32)
        ref = _fast_ref(m, prompt, 6)
        r1 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        fail0, dem0, disc0 = _counter("engine.kvtier.spill_fail"), \
            _counter("engine.prefix_evictions_demoted"), \
            _counter("engine.prefix_evictions_discarded")
        fired0 = faults.fired("kvtier.spill_fail")
        with faults.scoped("kvtier.spill_fail"):
            eng._shrink_prefix()         # the eviction itself NEVER fails
        assert faults.fired("kvtier.spill_fail") == fired0 + 1
        assert _counter("engine.kvtier.spill_fail") == fail0 + 1
        assert _counter("engine.prefix_evictions_demoted") == dem0
        assert _counter("engine.prefix_evictions_discarded") == disc0 + 4
        assert _gauge("engine.kvtier.host_pages") == 0
        _assert_pool_clean(eng)          # pages reclaimed despite the fault
        tok0 = _counter("engine.prefill_tokens")
        r2 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        assert _counter("engine.prefill_tokens") - tok0 == 17  # clean cold

    def test_reupload_fail_degrades_to_cold_prefill(self):
        m = _tiny_model()
        eng = _engine(m, kv_host_tier_bytes=1 << 20)
        prompt = np.random.RandomState(10).randint(0, 97, 17)\
            .astype(np.int32)
        ref = _fast_ref(m, prompt, 6)
        r1 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        eng._shrink_prefix()
        fail0, tok0 = _counter("engine.kvtier.reupload_fail"), \
            _counter("engine.prefill_tokens")
        with faults.scoped("kvtier.reupload_fail"):
            r2 = eng.submit(prompt, max_new_tokens=6)
            eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        assert _counter("engine.kvtier.reupload_fail") == fail0 + 1
        assert _counter("engine.prefill_tokens") - tok0 == 17  # full cold
        _assert_pool_clean(eng)
        # the tier entries survive the failed upload (read-through get):
        # r2 retired and re-registered the pages, so spill them again and
        # the NEXT hit recovers the fast path
        eng._shrink_prefix()
        tok1 = _counter("engine.prefill_tokens")
        r3 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r3.result(timeout=30), ref)
        assert _counter("engine.prefill_tokens") - tok1 == 1

    def test_disk_corruption_refuses_typed_and_cold_prefills(self, tmp_path):
        """Both corruption modes — the armed kvtier.disk_corrupt fault
        and REAL on-disk bit rot — surface as typed refusals counted in
        engine.kvtier.refusals, drop the rotten entry, and degrade the
        request to a correct cold/partial prefill. Never an error."""
        m = _tiny_model()
        eng = _engine(m, kv_host_tier_bytes=64,
                      kv_disk_tier_bytes=1 << 20,
                      kv_disk_tier_dir=str(tmp_path))
        prompt = np.random.RandomState(11).randint(0, 97, 17)\
            .astype(np.int32)
        ref = _fast_ref(m, prompt, 6)
        r1 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        eng._shrink_prefix()
        assert _gauge("engine.kvtier.disk_pages") == 4
        # injected: the chain's FIRST lookup rots -> whole chain misses
        ref0, tok0 = _counter("engine.kvtier.refusals"), \
            _counter("engine.prefill_tokens")
        with faults.scoped("kvtier.disk_corrupt", times=1):
            r2 = eng.submit(prompt, max_new_tokens=6)
            eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        assert _counter("engine.kvtier.refusals") == ref0 + 1
        assert _counter("engine.prefill_tokens") - tok0 == 17
        assert _gauge("engine.kvtier.disk_pages") == 3   # entry dropped
        # real bit rot: r2 re-registered the pages; spill them again and
        # flip one byte in one blob file on disk
        eng._shrink_prefix()
        assert _gauge("engine.kvtier.disk_pages") == 4
        path = sorted(tmp_path.glob("*.ptkt"))[0]
        raw = bytearray(path.read_bytes())
        raw[-1] ^= 0xFF
        path.write_bytes(bytes(raw))
        ref1 = _counter("engine.kvtier.refusals")
        r3 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r3.result(timeout=30), ref)
        assert _counter("engine.kvtier.refusals") == ref1 + 1
        _assert_pool_clean(eng)


# -------------------------------------------------- fleet directory wiring


class TestTierDirectory:
    """Spilled-tier advertisement: the engine exports its spilled chain
    hashes, the fleet directory unions them into the replica's prefix
    depth and flags them, so the router lands a spilled prefix on the
    ONE replica that can re-upload it."""

    def test_directory_tracks_spilled_depth_and_membership(self):
        from paddle_tpu.serving.disagg import (PrefixDirectory,
                                               prompt_page_hashes)
        hs = prompt_page_hashes(np.arange(17, dtype=np.int32), 4)
        d = PrefixDirectory()
        d.replace("prefill:b", hs[:3])
        # a claims the full chain, tail spilled: single-owner map — the
        # overlap (and its spilled flags) moves from b to a
        d.replace("prefill:a", hs, spilled=hs[2:])
        rid, depth = d.lookup(hs)
        # a's spilled tail still counts as resident depth: the re-upload
        # costs one device_put, not a prefill — deepest replica wins
        assert (rid, depth) == ("prefill:a", len(hs))
        assert not d.is_spilled(hs[0], "prefill:a")
        assert d.is_spilled(hs[-1], "prefill:a")
        assert not d.is_spilled(hs[-1], "prefill:b")
        assert d.spilled_depth("prefill:a") == len(hs) - 2
        assert d.spilled_depth("prefill:b") == 0
        # a refresh that empties the replica clears its spilled set too
        d.replace("prefill:a", [])
        assert d.spilled_depth("prefill:a") == 0
        assert d.lookup(hs) == (None, 0)
        # membership churn drops the spilled bookkeeping with the entries
        d.replace("prefill:b", hs[:3], spilled=hs[:1])
        assert d.lookup(hs) == ("prefill:b", 3)
        assert d.spilled_depth("prefill:b") == 1
        d.invalidate("prefill:b")
        assert d.lookup(hs) == (None, 0)
        assert d.spilled_depth("prefill:b") == 0

    def test_engine_advertises_spilled_hashes_to_directory(self):
        from paddle_tpu.serving.disagg import PrefixDirectory
        m = _tiny_model()
        eng = _engine(m, kv_host_tier_bytes=1 << 20)
        prompt = np.random.RandomState(12).randint(0, 97, 17)\
            .astype(np.int32)
        assert eng.tier_hashes() == []
        r = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle(max_steps=60)
        r.result(timeout=30)
        assert eng.tier_hashes() == []   # resident, nothing spilled yet
        eng._shrink_prefix()
        th = eng.tier_hashes()
        assert sorted(th) == sorted(h.hex() for h in r.page_hashes[:4])
        # the STATS consumer's exact move: union spilled into the
        # replica's advertised chain and route the full depth to it
        d = PrefixDirectory()
        spilled = [bytes.fromhex(x) for x in th]
        d.replace("prefill:x", spilled, spilled=spilled)
        rid, depth = d.lookup(list(r.page_hashes))
        assert (rid, depth) == ("prefill:x", 4)
        assert d.is_spilled(bytes(r.page_hashes[0]), "prefill:x")


# PTKT1 frames of conftest's seeded pages [5, 2, 7] (seed 2600) under a
# zeroed store salt, spilled by the commit before the pool was stored
# merged: blake2b-128 of each page's frame
PTKT1_DIGESTS = {
    "f32": ["5468f58cba65da999a09fdd00c30f831",
            "f921813da69094ef7e391303109c14c9",
            "86a7e948ed9c7d288040441027f95cfe"],
    "int8": ["d0057238106a659891d4f53b106a1331",
             "7b66fa671e49dee858e5deea87669181",
             "be80fcb307e2b620f90033d4f96dcae8"]}


@pytest.mark.parametrize("kv", sorted(PTKT1_DIGESTS))
def test_ptkt1_frames_are_byte_identical_to_the_unmerged_pools(
        kv, seeded_kv_pages):
    """A spill out of the merged pool frames each page as the
    ``[..., nh, dh]`` pool did, byte for byte, and the tier's entries are
    the page contents that went in."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    eng = DecodeEngine(_tiny_model(), EngineConfig(
        page_size=4, max_slots=2, min_bucket=8, kv_dtype=kv,
        kv_host_tier_bytes=1 << 20))
    pages = [5, 2, 7]
    k, v, ks, vs = seeded_kv_pages(eng, pages, 2600)
    eng._tiers._salt = "0" * 16
    hashes = [hashlib.blake2b(b"page-%d" % p, digest_size=16).digest()
              for p in pages]
    assert eng._spill_pages(pages, hashes) == 3
    assert [hashlib.blake2b(eng._tiers._host[h], digest_size=16).hexdigest()
            for h in hashes] == PTKT1_DIGESTS[kv]
    for i, h in enumerate(hashes):
        e = eng._tiers.get(h)
        np.testing.assert_array_equal(e.k, k[:, i])
        np.testing.assert_array_equal(e.v, v[:, i])
        if ks is not None:
            np.testing.assert_array_equal(e.ks, ks[:, i])
            np.testing.assert_array_equal(e.vs, vs[:, i])

