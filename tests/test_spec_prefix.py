"""Prefix caching (copy-on-write KV pages) + speculative decoding (n-gram
draft, k-token verify) in the decode engine, plus the refcounted allocator's
loud failure modes and the autotune disk cache.

The load-bearing contracts:
- prefix-cached decode is TOKEN-IDENTICAL to uncached decode, cached pages
  are attached by reference (zero prefill work for them, counter-pinned),
  and eviction under pool pressure never touches a live slot's pages;
- speculative decode is BIT-IDENTICAL to non-speculative decode — greedy
  through the engine, temperature/top-k through `verify_step`'s sampled
  path with the same PRNG threading as `fast_generate` — regardless of
  what the drafter proposed.
"""
import json

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


def _fast_ref(model, prompt, n, **kw):
    ids = paddle.Tensor(np.asarray(prompt)[None].astype(np.int32),
                        _internal=True)
    return np.asarray(model.fast_generate(ids, max_new_tokens=n,
                                          **kw).numpy())[0]


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


class TestPageAllocatorRefcounts:
    """Loud failure modes + share/retain semantics (the satellite)."""

    def _alloc(self, n=8):
        from paddle_tpu.inference.engine import PageAllocator
        return PageAllocator(n)

    def test_double_free_raises(self):
        a = self._alloc()
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(ValueError, match="double free"):
            a.free([pages[0]])

    def test_duplicate_ids_in_one_call_raise_without_mutating(self):
        a = self._alloc()
        (p,) = a.alloc(1)
        with pytest.raises(ValueError, match="duplicate"):
            a.free([p, p])
        # the loud path must not have half-freed: one clean free still works
        a.free([p])

    def test_trash_page_and_bogus_ids_refused(self):
        a = self._alloc()
        with pytest.raises(ValueError, match="trash page"):
            a.free([0])
        with pytest.raises(ValueError, match="bogus"):
            a.free([99])
        with pytest.raises(ValueError, match="bogus"):
            a.free([-1])

    def test_share_grows_refcount_and_free_releases_per_owner(self):
        a = self._alloc()
        pages = a.alloc(2)
        a.share(pages)                       # second owner
        assert a.refcount(pages[0]) == 2
        a.free(pages)                        # first owner leaves
        assert a.refcount(pages[0]) == 1
        assert a.free_pages == 5             # still held by the second
        a.free(pages)                        # second owner leaves
        assert a.free_pages == 7
        with pytest.raises(ValueError, match="double free"):
            a.free(pages)

    def test_share_unallocated_page_refused(self):
        a = self._alloc()
        with pytest.raises(ValueError, match="unallocated"):
            a.share([3])

    def test_retain_hook_keeps_page_and_evict_reclaims(self):
        a = self._alloc(4)
        kept = []
        a.retain_hook = lambda p: kept.append(p) or True
        a.evict_hook = lambda n: [kept.pop(0) for _ in range(min(n, len(kept)))]
        pages = a.alloc(3)
        a.free(pages)
        assert a.free_pages == 3             # retained counts as reclaimable
        got = a.alloc(2)                     # forces eviction of 2
        assert got is not None and len(got) == 2
        assert len(kept) == 1


class TestSubmitValidation:
    def test_nonpositive_max_new_tokens_rejected(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        eng = DecodeEngine(_tiny_model(), EngineConfig(page_size=4,
                                                       max_slots=1))
        for bad in (0, -3):
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=bad)
        # nothing was admitted: the engine is still fully idle
        assert not eng._has_work()


class TestPrefixCache:
    def _engine(self, m, **kw):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        kw.setdefault("page_size", 4)
        kw.setdefault("max_slots", 4)
        kw.setdefault("min_bucket", 8)
        return DecodeEngine(m, EngineConfig(**kw))

    def test_resubmission_hits_and_matches_reference(self):
        """The headline: a resubmitted prompt attaches its cached pages by
        reference, prefills ONLY the tail (counter-pinned: prefill_tokens
        delta == tail length), and the output is token-identical."""
        m = _tiny_model()
        eng = self._engine(m)
        prompt = np.random.RandomState(0).randint(0, 97, 17).astype(np.int32)
        ref = _fast_ref(m, prompt, 8)
        r1 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        tok0 = _counter("engine.prefill_tokens")
        hits0, reused0 = _counter("engine.prefix_hit"), \
            _counter("engine.prefix_pages_reused")
        r2 = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        # 17 tokens at page 4: pages 0..3 are full, (17-1)//4 = 4 shared,
        # tail = 1 token — ZERO prefill-program work for the cached pages
        assert _counter("engine.prefix_hit") == hits0 + 1
        assert _counter("engine.prefix_pages_reused") == reused0 + 4
        assert _counter("engine.prefill_tokens") - tok0 == 1
        # all pages reclaimable after retirement (cached ones retained)
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1

    def test_concurrent_shared_prefix_requests(self):
        """N live requests share one system prompt's pages copy-on-write:
        refcounts grow past 1, every output matches the dense reference,
        and the shared pages return to idle-cached only after ALL owners
        retire."""
        m = _tiny_model()
        eng = self._engine(m)
        rng = np.random.RandomState(1)
        system = rng.randint(0, 97, 16).astype(np.int32)
        seed_req = eng.submit(system, max_new_tokens=2)   # registers pages
        eng.run_until_idle(max_steps=40)
        assert seed_req.done
        prompts = [np.concatenate([system,
                                   rng.randint(0, 97, 3).astype(np.int32)])
                   for _ in range(3)]
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.step()                    # all admitted, decoding concurrently
        shared_page = eng._prefix_lookup(reqs[0].page_hashes)[0]
        assert eng.allocator.refcount(shared_page) == 3   # 3 live owners
        eng.run_until_idle(max_steps=100)
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, p, 6))
        assert _counter("engine.prefix_hit") >= 3
        assert eng.allocator.refcount(shared_page) == 0   # idle-cached again
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1

    def test_eviction_under_pressure_and_live_pages_safe(self):
        """A pool sized so new traffic must evict: LRU refcount-0 cached
        pages are reclaimed (engine.prefix_evictions), a LIVE request's
        pages are never touched, and an evicted prefix simply misses and
        re-prefills correctly."""
        m = _tiny_model()
        # pool: 11 usable pages of 4 tokens
        eng = self._engine(m, max_slots=2, num_pages=12, max_seq_len=40)
        rng = np.random.RandomState(2)
        pa = rng.randint(0, 97, 16).astype(np.int32)     # 4 full pages
        ra = eng.submit(pa, max_new_tokens=4)            # 5 pages total
        eng.run_until_idle(max_steps=40)
        np.testing.assert_array_equal(ra.result(timeout=30),
                                      _fast_ref(m, pa, 4))
        # A's 4 full pages sit idle-cached; a live request + one more big
        # request exceed the free list and force eviction
        live = eng.submit(rng.randint(0, 97, 16).astype(np.int32),
                          max_new_tokens=12)             # 7 pages live
        eng.step()
        ev0, disc0, dem0 = _counter("engine.prefix_evictions"), \
            _counter("engine.prefix_evictions_discarded"), \
            _counter("engine.prefix_evictions_demoted")
        big = eng.submit(rng.randint(0, 97, 13).astype(np.int32),
                         max_new_tokens=7)               # needs 5 pages
        eng.run_until_idle(max_steps=100)
        ev = _counter("engine.prefix_evictions") - ev0
        assert ev > 0
        # the discarded/demoted split always sums to the total — and with
        # no spill tiers configured every eviction is a DISCARD
        # (tests/test_kv_tiers.py pins the demoted arm)
        assert _counter("engine.prefix_evictions_discarded") - disc0 == ev
        assert _counter("engine.prefix_evictions_demoted") == dem0
        np.testing.assert_array_equal(live.result(timeout=30),
                                      _fast_ref(m, live.prompt, 12))
        np.testing.assert_array_equal(big.result(timeout=30),
                                      _fast_ref(m, big.prompt, 7))
        # the evicted prefix re-prefills from scratch, still correct
        r2 = eng.submit(pa, max_new_tokens=4)
        eng.run_until_idle(max_steps=40)
        np.testing.assert_array_equal(r2.result(timeout=30),
                                      _fast_ref(m, pa, 4))

    def test_refresh_params_flushes_stale_kv(self):
        """Weight hot-swap invalidates the store: cached pages hold KV
        computed under the OLD weights, so a hit after `refresh_params`
        would silently condition new-weights decode on stale KV. The flush
        returns idle pages to the free list and the resubmission misses,
        re-prefills, and matches the NEW model's reference."""
        m = _tiny_model()
        eng = self._engine(m)
        prompt = np.random.RandomState(13).randint(0, 97, 16)\
            .astype(np.int32)
        r = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle(max_steps=40)
        np.testing.assert_array_equal(r.result(timeout=30),
                                      _fast_ref(m, prompt, 4))
        assert eng._prefix_pages
        m2 = _tiny_model(seed=12)
        eng.refresh_params(m2)
        assert not eng._prefix_pages and not eng._prefix_idle
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1
        hits0 = _counter("engine.prefix_hit")
        r2 = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle(max_steps=40)
        np.testing.assert_array_equal(r2.result(timeout=30),
                                      _fast_ref(m2, prompt, 4))
        assert _counter("engine.prefix_hit") == hits0   # miss, not a hit

    def test_cache_opt_out_never_registers_or_reuses(self):
        m = _tiny_model()
        eng = self._engine(m)
        hits0 = _counter("engine.prefix_hit")
        prompt = np.random.RandomState(3).randint(0, 97, 16).astype(np.int32)
        for _ in range(2):
            r = eng.submit(prompt, max_new_tokens=4, cache=False)
            eng.run_until_idle(max_steps=40)
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, prompt, 4))
        assert _counter("engine.prefix_hit") == hits0
        assert not eng._prefix_pages
        # and the engine-level kill switch
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        eng2 = DecodeEngine(m, EngineConfig(page_size=4, max_slots=1,
                                            min_bucket=8,
                                            prefix_cache=False))
        for _ in range(2):
            r = eng2.submit(prompt, max_new_tokens=4)
            eng2.run_until_idle(max_steps=40)
            assert r.done
        assert not eng2._prefix_pages

    def test_chunked_prefill_pages_are_cache_eligible(self):
        """A prompt that arrived via decode-priority chunked prefill
        registers its pages too; the resubmission hits."""
        m = _tiny_model()
        eng = self._engine(m, prefill_chunk_tokens=8)
        prompt = np.random.RandomState(4).randint(0, 97, 21).astype(np.int32)
        ref = _fast_ref(m, prompt, 6)
        r1 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=100)
        np.testing.assert_array_equal(r1.result(timeout=30), ref)
        hits0 = _counter("engine.prefix_hit")
        r2 = eng.submit(prompt, max_new_tokens=6)
        eng.run_until_idle(max_steps=100)
        np.testing.assert_array_equal(r2.result(timeout=30), ref)
        assert _counter("engine.prefix_hit") == hits0 + 1

    def test_imported_handoff_pages_are_cache_eligible(self):
        """KV handoff composes with the prefix cache: pages imported from
        another engine register locally, so a shared-prefix submit after
        the import reuses them."""
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        m = _tiny_model()
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, 97, 17).astype(np.int32)
        eng_a = self._engine(m, max_slots=1)
        eng_b = self._engine(m, max_slots=2)
        h = eng_a.prefill_export(prompt)
        r = eng_b.import_request(h, max_new_tokens=6)
        eng_b.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r.result(timeout=30),
                                      _fast_ref(m, prompt, 6))
        hits0 = _counter("engine.prefix_hit")
        r2 = eng_b.submit(prompt, max_new_tokens=6)
        eng_b.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r2.result(timeout=30),
                                      _fast_ref(m, prompt, 6))
        assert _counter("engine.prefix_hit") == hits0 + 1
        # and the EXPORTING engine retained its own prefilled pages
        hits_a0 = _counter("engine.prefix_hit")
        r3 = eng_a.submit(prompt, max_new_tokens=6)
        eng_a.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r3.result(timeout=30),
                                      _fast_ref(m, prompt, 6))
        assert _counter("engine.prefix_hit") == hits_a0 + 1

    def test_repeated_export_hits_the_cache(self):
        """The export path itself reuses cached prefixes: a second export
        of the same prompt prefills only the tail, and the handoff blob
        still resumes decode bit-identically."""
        from paddle_tpu.inference.engine import KVHandoff
        m = _tiny_model()
        rng = np.random.RandomState(15)
        prompt = rng.randint(0, 97, 17).astype(np.int32)
        eng_a = self._engine(m, max_slots=1)
        eng_b = self._engine(m, max_slots=1)
        h1 = eng_a.prefill_export(prompt)
        tok0 = _counter("engine.prefill_tokens")
        hits0 = _counter("engine.prefix_hit")
        h2 = eng_a.prefill_export(prompt)
        assert _counter("engine.prefix_hit") == hits0 + 1
        # 17 tokens, 4 pages cached, tail = 1: only the tail prefilled
        assert _counter("engine.prefill_tokens") - tok0 == 1
        # the four cached pages travel by reference and are bit-equal; the
        # tail token's K is recomputed by the 1-token CHUNK program where
        # the first export ran the 32-wide one-shot program, and two XLA
        # programs may round the same matmul differently in the last ulp
        np.testing.assert_array_equal(h2.k_pages[:, :4], h1.k_pages[:, :4])
        np.testing.assert_allclose(h2.k_pages, h1.k_pages, rtol=1e-6,
                                   atol=1e-7)
        assert h2.first_token == h1.first_token
        r = eng_b.import_request(KVHandoff.unpack(h2.pack()),
                                 max_new_tokens=8)
        eng_b.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r.result(timeout=30),
                                      _fast_ref(m, prompt, 8))


class TestSpeculativeDecode:
    def _engine(self, m, k=3, **kw):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        kw.setdefault("page_size", 4)
        kw.setdefault("max_slots", 2)
        kw.setdefault("min_bucket", 8)
        return DecodeEngine(m, EngineConfig(speculate_k=k, **kw))

    def test_greedy_parity_across_prompts_and_page_boundaries(self):
        """Speculative engine output == fast_generate, token for token:
        random prompts (drafts mostly rejected), repetitive prompts (drafts
        mostly accepted), lengths that straddle page edges, and enough new
        tokens that accepted runs cross page boundaries mid-step."""
        m = _tiny_model()
        eng = self._engine(m, k=3)
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 97, s).astype(np.int32)
                   for s in (3, 5, 9, 16)]
        prompts.append(np.tile(rng.randint(0, 97, 4).astype(np.int32), 5))
        for p in prompts:
            r = eng.submit(p, max_new_tokens=14)
            eng.run_until_idle(max_steps=120)
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, p, 14))
        assert _counter("engine.spec_steps") > 0

    def test_concurrent_mixed_slots_parity(self):
        """Slots with drafts and slots without verify in the SAME
        fixed-shape step; staggered admission/retirement included."""
        m = _tiny_model()
        eng = self._engine(m, k=2, max_slots=3)
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 97, 3 + i).astype(np.int32)
                   for i in range(5)]
        ns = [6, 11, 4, 9, 7]
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, ns)]
        eng.run_until_idle(max_steps=300)
        for p, n, r in zip(prompts, ns, reqs):
            np.testing.assert_array_equal(r.result(timeout=30),
                                          _fast_ref(m, p, n))

    def test_accept_rate_positive_on_repetitive_text(self):
        """The tentpole's measurable claim at test scale: on repetitive
        text the n-gram drafter's proposals verify, spec_accept_rate > 0,
        and steps emit > 1 token on average."""
        m = _tiny_model()
        eng = self._engine(m, k=3, max_slots=1)
        phrase = np.random.RandomState(8).randint(0, 97, 4).astype(np.int32)
        prompt = np.tile(phrase, 4)                      # 16 tokens
        steps0 = _counter("engine.steps")
        r = eng.submit(prompt, max_new_tokens=20)
        eng.run_until_idle(max_steps=120)
        np.testing.assert_array_equal(r.result(timeout=30),
                                      _fast_ref(m, prompt, 20))
        steps = _counter("engine.steps") - steps0
        assert _counter("engine.spec_accepted") > 0
        assert metrics.snapshot()["gauges"]["engine.spec_accept_rate"] > 0
        # 19 post-first tokens in fewer steps than plain decode would take
        assert steps < 19, f"no multi-token steps ({steps} steps)"

    def test_per_request_opt_out(self):
        m = _tiny_model()
        eng = self._engine(m, k=3, max_slots=1)
        phrase = np.random.RandomState(9).randint(0, 97, 4).astype(np.int32)
        prompt = np.tile(phrase, 4)
        drafted0 = _counter("engine.spec_drafted")
        r = eng.submit(prompt, max_new_tokens=10, speculate=False)
        eng.run_until_idle(max_steps=60)
        np.testing.assert_array_equal(r.result(timeout=30),
                                      _fast_ref(m, prompt, 10))
        assert _counter("engine.spec_drafted") == drafted0

    def test_eos_mid_acceptance_truncates_exactly(self):
        """EOS inside an accepted run: the emitted tokens are cut at the
        first EOS inclusive and the slot retires — byte-identical to the
        plain engine's EOS behavior."""
        m = _tiny_model()
        phrase = np.random.RandomState(10).randint(0, 97, 4).astype(np.int32)
        prompt = np.tile(phrase, 4)
        ref = _fast_ref(m, prompt, 16)
        eos = int(ref[len(prompt) + 5])
        eng = self._engine(m, k=3, max_slots=1, eos_id=eos)
        r = eng.submit(prompt, max_new_tokens=16)
        eng.run_until_idle(max_steps=80)
        out = r.result(timeout=30)
        assert out[-1] == eos
        np.testing.assert_array_equal(out, ref[:len(out)])
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1

    def test_spec_composes_with_prefix_cache(self):
        """Both tentpole halves on at once: cached-prefix resubmission of a
        repetitive prompt, decoded speculatively — still token-identical."""
        m = _tiny_model()
        eng = self._engine(m, k=3, max_slots=2)
        phrase = np.random.RandomState(11).randint(0, 97, 4).astype(np.int32)
        prompt = np.tile(phrase, 5)                      # 20 tokens, 5 pages
        ref = _fast_ref(m, prompt, 12)
        for i in range(2):
            r = eng.submit(prompt, max_new_tokens=12)
            eng.run_until_idle(max_steps=100)
            np.testing.assert_array_equal(r.result(timeout=30), ref)
        assert _counter("engine.prefix_hit") >= 1
        assert _counter("engine.spec_steps") > 0

    def test_bad_speculate_k_rejected(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        with pytest.raises(ValueError, match="speculate_k"):
            DecodeEngine(_tiny_model(), EngineConfig(speculate_k=0))


class TestVerifyStepSampled:
    """`verify_step`'s sampled path: bit-identical to `fast_generate` at
    temperature/top-k with the SAME PRNG threading (one key split per
    emitted token), for ANY drafts — the exactness guarantee is in the
    acceptance rule, not the drafter."""

    @pytest.mark.parametrize("temperature,top_k,seed", [
        (1.0, 0, 0),          # greedy through the sampled code path
        (0.8, 5, 3),
        (1.3, 8, 11),
        (0.7, 0, 5),          # temperature-only sampling
    ])
    def test_sampled_spec_loop_matches_fast_generate(self, temperature,
                                                     top_k, seed):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import TRASH_PAGE
        from paddle_tpu.models.gpt import (_make_sampler, prefill_step,
                                           serving_params, verify_step)
        m = _tiny_model()
        cfg = m.cfg
        params = serving_params(m.state_dict())
        rng = np.random.RandomState(seed + 1)
        prompt = rng.randint(0, 97, 7).astype(np.int32)
        N, K, ps, maxp = 12, 3, 4, 8
        ref = _fast_ref(m, prompt, N, temperature=temperature, top_k=top_k,
                        seed=seed)

        kc = jnp.zeros((cfg.num_layers, 1 + maxp, ps, 2 * 16), jnp.float32)
        vc = jnp.zeros_like(kc)
        row = np.full(maxp, TRASH_PAGE, np.int32)
        row[:maxp - 1] = np.arange(1, maxp)
        sampler = _make_sampler(float(temperature), int(top_k))
        packed = np.zeros(8, np.int32)
        packed[:prompt.size] = prompt
        logits0, kc, vc = prefill_step(params, jnp.asarray(packed),
                                       jnp.asarray(prompt.size),
                                       jnp.asarray(row), kc, vc, cfg=cfg)
        key = jax.random.PRNGKey(seed)
        first, key = sampler(logits0[None], key)
        out, length = [int(first[0])], prompt.size
        drng = np.random.RandomState(99)
        while len(out) < N:
            # ADVERSARIAL drafts: random tokens, random draft_len — parity
            # must hold whatever the proposer says
            k_draft = min(K, N - len(out) - 1, drng.randint(0, K + 1))
            tok_seq = np.zeros((1, K + 1), np.int32)
            tok_seq[0, 0] = out[-1]
            tok_seq[0, 1:] = drng.randint(0, 97, K)
            cache = dict(k_pages=kc, v_pages=vc,
                         page_table=jnp.asarray(row[None]),
                         lengths=jnp.asarray([length], jnp.int32))
            em, ne, cache, nk = verify_step(
                params, jnp.asarray(tok_seq),
                jnp.asarray([k_draft], jnp.int32), cache,
                jnp.asarray([True]), cfg=cfg, sampler=sampler,
                keys=key[None])
            kc, vc = cache["k_pages"], cache["v_pages"]
            n = int(ne[0])
            out.extend(int(t) for t in np.asarray(em)[0, :n])
            length += n
            key = nk[0]
        np.testing.assert_array_equal(
            np.concatenate([prompt, np.asarray(out[:N], np.int32)]), ref)


    def test_inactive_slot_key_chain_does_not_advance(self):
        """An inactive slot emits 0 tokens, so its PRNG chain must come
        back UNSPLIT — a chain one split ahead would silently diverge every
        later sampled token."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import TRASH_PAGE
        from paddle_tpu.models.gpt import (_make_sampler, serving_params,
                                           verify_step)
        m = _tiny_model()
        cfg = m.cfg
        params = serving_params(m.state_dict())
        ps, maxp, K = 4, 4, 2
        kc = jnp.zeros((cfg.num_layers, 1 + 2 * maxp, ps, 2 * 16),
                       jnp.float32)
        vc = jnp.zeros_like(kc)
        table = np.arange(1, 1 + 2 * maxp, dtype=np.int32).reshape(2, maxp)
        keys = jnp.stack([jax.random.PRNGKey(1), jax.random.PRNGKey(2)])
        cache = dict(k_pages=kc, v_pages=vc, page_table=jnp.asarray(table),
                     lengths=jnp.asarray([2, 2], jnp.int32))
        tok_seq = jnp.asarray(np.zeros((2, K + 1), np.int32))
        _, ne, _, nk = verify_step(
            params, tok_seq, jnp.asarray([0, 0], jnp.int32), cache,
            jnp.asarray([True, False]), cfg=cfg,
            sampler=_make_sampler(0.8, 3), keys=keys)
        assert int(ne[1]) == 0
        np.testing.assert_array_equal(np.asarray(nk[1]),
                                      np.asarray(keys[1]))
        # the ACTIVE slot's chain did advance by its one split
        assert not np.array_equal(np.asarray(nk[0]), np.asarray(keys[0]))


@pytest.mark.parametrize("kv", ["f32", "int8"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_step",
                                     "verify_step"])
def test_xla_arms_gather_from_the_stack_without_slicing_a_layer(program, kv):
    """The XLA arms index ``pool[layer, page_table]`` in ONE gather: no
    equation of the traced step yields a layer's pool (``[P, ps, nh*dh]``
    with or without a leading 1), values or scales — on the chip such a
    slice is a copy of 1/nl of the pool, per layer, per program."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models import gpt
    m = _tiny_model()
    cfg = m.cfg
    params = gpt.serving_params(m.state_dict())
    nl, nh, hd, npages, ps, maxp, b = 2, 2, 32, 9, 4, 4, 2
    quant = kv == "int8"
    pool = jnp.zeros((nl, npages, ps, hd), jnp.int8 if quant
                     else jnp.float32)
    sc = dict(k_scale=jnp.zeros((nl, npages, ps, nh), jnp.float32),
              v_scale=jnp.zeros((nl, npages, ps, nh), jnp.float32)) \
        if quant else {}
    table = jnp.arange(1, 1 + b * maxp, dtype=jnp.int32).reshape(b, maxp)
    cache = dict(k_pages=pool, v_pages=pool, page_table=table,
                 lengths=jnp.asarray([3, 5], jnp.int32), **sc)
    live = jnp.asarray([True, True])
    if program == "decode_step":
        def fn():
            return gpt.decode_step(params, jnp.zeros(b, jnp.int32), cache,
                                   live, cfg=cfg)
    elif program == "verify_step":
        def fn():
            return gpt.verify_step(params, jnp.zeros((b, 3), jnp.int32),
                                   jnp.asarray([2, 1], jnp.int32), cache,
                                   live, cfg=cfg)
    else:
        def fn():
            return gpt.prefill_chunk_step(
                params, jnp.zeros(8, jnp.int32), jnp.int32(4), jnp.int32(6),
                table[0], pool, pool, cfg=cfg, **sc)
    set_flags({"tpu_paged_impl": "xla", "tpu_prefill_impl": "xla"})
    try:
        jaxpr = jax.make_jaxpr(fn)()
    finally:
        set_flags({"tpu_paged_impl": "auto", "tpu_prefill_impl": "auto"})
    layer_shapes = {(npages, ps, w) for w in (hd, nh)}

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                shape = tuple(getattr(v.aval, "shape", ()))
                yield eqn.primitive.name, shape[1:] if shape[:1] == (1,) \
                    else shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    seen = list(walk(jaxpr.jaxpr))
    assert [e for e in seen if e[1] in layer_shapes] == []
    # and the windows do come out of gathers over the whole stack
    assert sum(name == "gather" for name, _ in seen) >= 2 * nl


class TestDraftIndex:
    """The O(1)-per-token n-gram index behind the self-drafting proposer."""

    def test_matches_brute_force_suffix_search(self):
        from paddle_tpu.inference.engine import _DraftIndex
        rng = np.random.RandomState(14)
        hist = rng.randint(0, 5, 60).tolist()       # small vocab: collisions
        idx = _DraftIndex(hist[:10])

        def brute(h, k):
            for n in (3, 2, 1):
                limit = len(h) - n
                if limit <= 0:
                    continue
                tail = h[-n:]
                for j in range(limit - 1, -1, -1):
                    if h[j:j + n] == tail:
                        return h[j + n:j + n + k]
            return []

        for t in hist[10:]:
            assert idx.draft(3) == brute(idx.hist, 3)
            idx.append(t)
        assert idx.draft(3) == brute(idx.hist, 3)

    def test_always_has_a_follower(self):
        from paddle_tpu.inference.engine import _DraftIndex
        idx = _DraftIndex([7, 7])
        d = idx.draft(4)
        assert d, "a registered gram must have >= 1 follower"


class TestAutotuneDiskCache:
    """PADDLE_AUTOTUNE_CACHE: measured winners persist to a JSON table and
    are consulted before re-measuring; corrupt/stale files are ignored,
    never fatal."""

    def _run_winner(self, monkeypatch, tmp_path, measure_values,
                    cache_file=None):
        from paddle_tpu.kernels import paged_attention as pa, registry
        registry.clear()
        path = str(cache_file if cache_file is not None
                   else tmp_path / "autotune.json")
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", path)
        monkeypatch.setattr(registry.ops()["paged_attention"], "candidates",
                            lambda ctx: ["xla", "alt"])
        calls = []

        def fake_measure(fn, args, **kw):
            calls.append(1)
            return measure_values[len(calls) - 1]

        monkeypatch.setattr(registry, "measure", fake_measure)
        key, measure = pa._paged_selection(1, 2, 2, 1, 2, "float32")
        win = registry.dispatch("paged_attention", key=key, measure=measure)
        return win, len(calls), path

    def test_winner_persists_and_skips_remeasure(self, monkeypatch,
                                                 tmp_path):
        from paddle_tpu.kernels import registry
        win, n_measured, path = self._run_winner(
            monkeypatch, tmp_path, measure_values=[0.002, 0.001])
        assert win == "alt" and n_measured == 2
        table = json.load(open(path))
        assert table["version"] == 1 and len(table["winners"]) == 1
        # a fresh process (cleared in-memory cache) trusts the disk table
        win2, n2, _ = self._run_winner(monkeypatch, tmp_path,
                                       measure_values=[0.001, 0.002])
        assert win2 == "alt"           # disk answer, NOT the new timings
        assert n2 == 0, "disk hit must skip measurement"
        registry.clear()

    def test_corrupt_cache_ignored_never_fatal(self, monkeypatch, tmp_path):
        from paddle_tpu.kernels import registry
        bad = tmp_path / "autotune.json"
        bad.write_text("{not json")
        win, n_measured, path = self._run_winner(
            monkeypatch, tmp_path, measure_values=[0.001, 0.002],
            cache_file=bad)
        assert win == "xla" and n_measured == 2     # measured fallback
        # and the table was REWRITTEN healthy
        assert json.load(open(path))["winners"]
        registry.clear()

    def test_stale_winner_outside_viable_set_ignored(self, monkeypatch,
                                                     tmp_path):
        """A table copied from another backend naming a non-viable impl
        must not poison this host: the entry is ignored and re-measured."""
        from paddle_tpu.kernels import registry
        path = tmp_path / "autotune.json"
        # seed the file with the right KEY but a winner this backend
        # cannot run
        self._run_winner(monkeypatch, tmp_path, measure_values=[0.002, 0.001],
                         cache_file=path)
        table = json.load(open(path))
        k = next(iter(table["winners"]))
        table["winners"][k] = "pallas_tpu_only"
        path.write_text(json.dumps(table))
        win, n_measured, _ = self._run_winner(
            monkeypatch, tmp_path, measure_values=[0.001, 0.002],
            cache_file=path)
        assert win == "xla" and n_measured == 2
        registry.clear()

    def test_no_env_knob_no_file(self, monkeypatch, tmp_path):
        from paddle_tpu.kernels import paged_attention as pa, registry
        registry.clear()
        monkeypatch.delenv("PADDLE_AUTOTUNE_CACHE", raising=False)
        monkeypatch.chdir(tmp_path)
        monkeypatch.setattr(registry.ops()["paged_attention"], "candidates",
                            lambda ctx: ["xla", "alt"])
        monkeypatch.setattr(registry, "measure",
                            lambda fn, args, **kw: 0.001)
        key, measure = pa._paged_selection(1, 2, 2, 1, 2, "float32")
        registry.dispatch("paged_attention", key=key, measure=measure)
        assert registry.table()[key][1] == {"xla": 0.001, "alt": 0.001}
        assert not list(tmp_path.iterdir())
        registry.clear()


class TestServeKnobs:
    """GENERATE wire op carries per-request cache=/speculate= flags."""

    def test_wire_options_reach_the_engine(self):
        import threading
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        from paddle_tpu.inference.serve import (InferenceServer,
                                                RemotePredictor)
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8, speculate_k=2))
        srv = InferenceServer(None, engine=eng, auth_name="knobs")
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        cli = RemotePredictor(port=srv.port, secret="knobs")
        prompt = np.random.RandomState(12).randint(0, 97, 16)\
            .astype(np.int32)
        ref = _fast_ref(m, prompt, 6)
        # knob-less call: defaults on (back-compat wire shape, 2 arrays)
        np.testing.assert_array_equal(
            cli.generate(prompt, max_new_tokens=6), ref)
        hits0 = _counter("engine.prefix_hit")
        drafted0 = _counter("engine.spec_drafted")
        # opted out: same tokens, no cache hit, no drafting
        np.testing.assert_array_equal(
            cli.generate(prompt, max_new_tokens=6, cache=False,
                         speculate=False), ref)
        assert _counter("engine.prefix_hit") == hits0
        assert _counter("engine.spec_drafted") == drafted0
        # opted in: the earlier submission's pages hit
        np.testing.assert_array_equal(
            cli.generate(prompt, max_new_tokens=6, cache=True), ref)
        assert _counter("engine.prefix_hit") == hits0 + 1
        cli.shutdown_server()
        cli.close()
