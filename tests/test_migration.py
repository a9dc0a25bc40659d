"""Live request migration (docs/SERVING.md "Live migration"): a draining
replica exports its in-flight work instead of waiting it out.

The contract under test, at every layer: a migrated mid-decode request's
final token sequence is IDENTICAL to the uninterrupted run (engine- and
wire-level), migration never finishes the source future early or leaks
pages, queued/chunk-prefilling requests travel cold, and the serve-layer
shipping has bounded per-peer fallback (`serve.migrate_drop` fault site) —
all peers dead answers ONE typed error, never a hang. The routed drill at
the bottom is the acceptance scenario: drain a replica with 8 in-flight
ROUTED requests and every client gets its normal answer, zero errors.

Deterministic like the chaos suite: no random kills, faults fire exact
counts at named sites (marker ``chaos``)."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics
from paddle_tpu.testing import faults

pytestmark = pytest.mark.chaos

FLEET_SECRET = "migrate-fleet"


def _tiny_model(seed=7):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _engine(model, **ekw):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    ekw.setdefault("page_size", 4)
    ekw.setdefault("max_slots", 2)
    ekw.setdefault("min_bucket", 8)
    return DecodeEngine(model, EngineConfig(**ekw))


def _fast_ref(model, prompt, n):
    ids = paddle.Tensor(np.asarray(prompt)[None].astype(np.int32),
                        _internal=True)
    return np.asarray(model.fast_generate(ids, max_new_tokens=n).numpy())[0]


def _assert_pool_baseline(eng):
    assert eng.allocator.free_pages == eng.allocator.num_pages - 1, (
        f"leaked pages: "
        f"{eng.allocator.num_pages - 1 - eng.allocator.free_pages}")


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def _wait_for(pred, timeout=30.0, msg="condition"):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.02)


def _stop_server(srv):
    """Stop an InferenceServer's engine thread (its serve_loop re-steps
    every idle_wait even when idle — a leaked loop would consume faults
    armed by later tests in the same process)."""
    srv._stop.set()
    if srv._engine_thread is not None:
        srv._engine_thread.join(timeout=30)
    srv._sock.close()


def _migrate_once(src, n_steps):
    """Drive ``src`` ``n_steps`` steps, then drain with migration and
    return the exported items."""
    for _ in range(n_steps):
        src.step()
    src.drain(migrate=True)
    src.step()
    return src.take_migrated(timeout=10)


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    yield
    faults.disarm()


# --------------------------------------------------------- engine level


class TestEngineMigration:
    def test_mid_decode_export_resumes_token_identical(self):
        model = _tiny_model()
        prompt = np.arange(3, 9, dtype=np.int32)
        ref = _fast_ref(model, prompt, 12)
        src, dst = _engine(model), _engine(model)
        req = src.submit(prompt, max_new_tokens=12)
        items = _migrate_once(src, 4)
        assert len(items) == 1 and items[0].handoff is not None
        assert not req.done, "migration must NOT finish the source future"
        delivered = len(req.generated)
        assert delivered >= 1
        # context = prompt + delivered[:-1]; the last sampled token rides
        # as the seed; peer budget counts the seed as its first emission
        item = items[0]
        assert item.handoff.prompt.size == prompt.size + delivered - 1
        assert item.handoff.first_token == req.generated[-1]
        assert item.max_new_tokens == 12 - delivered + 1
        _assert_pool_baseline(src)
        out = self._resume(dst, item)
        np.testing.assert_array_equal(out, ref)
        _assert_pool_baseline(dst)

    @staticmethod
    def _resume(dst, item):
        r = dst.submit_import(item.handoff,
                              max_new_tokens=item.max_new_tokens)
        dst.run_until_idle(max_steps=200)
        return r.result(timeout=30)

    @pytest.mark.slow      # tier-1 wall audit (PR 12): the 1/2/5/8-step
    #   boundary SWEEP is the redundant tail — one boundary stays pinned
    #   every tier-1 run by test_mid_decode_export_resumes_token_identical
    #   above (plus the int8/speculative variants below); the full sweep
    #   runs in the nightly --runslow pass.
    def test_every_migration_step_boundary_is_token_identical(self):
        """Migrating after ANY number of steps resumes identically — the
        seed/context split holds at every boundary, deferred-readback
        window included."""
        model = _tiny_model()
        prompt = np.arange(5, 12, dtype=np.int32)
        ref = _fast_ref(model, prompt, 10)
        for n_steps in (1, 2, 5, 8):
            src, dst = _engine(model), _engine(model)
            src.submit(prompt, max_new_tokens=10)
            items = _migrate_once(src, n_steps)
            assert len(items) == 1
            out = self._resume(dst, items[0])
            np.testing.assert_array_equal(
                out, ref, err_msg=f"diverged after {n_steps} steps")

    def test_queued_requests_migrate_cold(self):
        model = _tiny_model()
        src = _engine(model, max_slots=1)
        dst = _engine(model, max_slots=2)
        p0 = np.arange(1, 7, dtype=np.int32)
        p1 = np.arange(11, 16, dtype=np.int32)
        ref1 = _fast_ref(model, p1, 8)
        src.submit(p0, max_new_tokens=8)
        q = src.submit(p1, max_new_tokens=8)   # queued: one slot only
        items = _migrate_once(src, 2)
        assert len(items) == 2
        warm = [i for i in items if i.handoff is not None]
        cold = [i for i in items if i.handoff is None]
        assert len(warm) == 1 and len(cold) == 1
        assert cold[0].request is q
        np.testing.assert_array_equal(cold[0].prompt, p1)
        assert cold[0].max_new_tokens == 8      # nothing delivered yet
        _assert_pool_baseline(src)
        # a cold item re-enters a peer through plain submit
        r = dst.submit(cold[0].prompt, cold[0].max_new_tokens)
        dst.run_until_idle(max_steps=200)
        np.testing.assert_array_equal(r.result(timeout=30), ref1)

    def test_chunk_prefilling_slot_migrates_cold(self):
        model = _tiny_model()
        src = _engine(model, prefill_chunk_tokens=4, max_slots=1)
        prompt = np.arange(2, 22, dtype=np.int32)   # 20 tokens: 5 chunks
        src.submit(prompt, max_new_tokens=4)
        src.step()                    # one chunk in — mid-prefill
        assert src._prefilling, "slot should still be chunk-prefilling"
        src.drain(migrate=True)
        src.step()
        (item,) = src.take_migrated(timeout=10)
        assert item.handoff is None, "partial prefill must migrate cold"
        np.testing.assert_array_equal(item.prompt, prompt)
        _assert_pool_baseline(src)

    def test_speculating_source_migrates_token_identical(self):
        model = _tiny_model()
        prompt = np.tile(np.arange(1, 5, dtype=np.int32), 3)   # repetitive
        ref = _fast_ref(model, prompt, 12)
        src = _engine(model, speculate_k=2)
        dst = _engine(model)
        src.submit(prompt, max_new_tokens=12)
        items = _migrate_once(src, 3)
        assert len(items) == 1 and items[0].handoff is not None
        out = self._resume(dst, items[0])
        np.testing.assert_array_equal(out, ref)

    def test_int8_kv_migration_matches_uninterrupted_int8(self):
        model = _tiny_model()
        prompt = np.arange(4, 10, dtype=np.int32)
        un = _engine(model, kv_dtype="int8")
        r = un.submit(prompt, max_new_tokens=10)
        un.run_until_idle(max_steps=200)
        ref = r.result(timeout=30)
        src = _engine(model, kv_dtype="int8")
        dst = _engine(model, kv_dtype="int8")
        src.submit(prompt, max_new_tokens=10)
        items = _migrate_once(src, 3)
        assert items[0].handoff.k_scales is not None
        out = self._resume(dst, items[0])
        np.testing.assert_array_equal(out, ref)

    def test_dtype_mismatch_refused_on_posting_thread(self):
        model = _tiny_model()
        src = _engine(model, kv_dtype="int8")
        dst = _engine(model)                       # f32 pool
        src.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=6)
        items = _migrate_once(src, 2)
        with pytest.raises(ValueError, match="dtype mismatch"):
            dst.submit_import(items[0].handoff,
                              max_new_tokens=items[0].max_new_tokens)

    def test_deadline_budget_rides_the_item(self):
        model = _tiny_model()
        src = _engine(model)
        src.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=12,
                   deadline_s=60.0)
        items = _migrate_once(src, 2)
        assert items[0].deadline_ms is not None
        assert 0 < items[0].deadline_ms <= 60_000

    def test_wire_blob_roundtrip_warm_and_cold(self):
        from paddle_tpu.inference.engine import (MigrationItem,
                                                 pack_migration,
                                                 unpack_migration)
        model = _tiny_model()
        src = _engine(model)
        src.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=8)
        (warm,) = _migrate_once(src, 2)
        w2 = unpack_migration(pack_migration(warm))
        assert w2.max_new_tokens == warm.max_new_tokens
        assert w2.request is None, "futures never cross the wire"
        np.testing.assert_array_equal(w2.handoff.prompt,
                                      warm.handoff.prompt)
        np.testing.assert_array_equal(w2.handoff.k_pages,
                                      warm.handoff.k_pages)
        assert w2.handoff.first_token == warm.handoff.first_token
        assert w2.tag is None
        cold = MigrationItem(max_new_tokens=5,
                             prompt=np.arange(4, dtype=np.int32),
                             deadline_ms=1234, tag=b"cancel-me")
        c2 = unpack_migration(pack_migration(cold))
        assert c2.handoff is None and c2.deadline_ms == 1234
        assert c2.tag == b"cancel-me", "cancel tag must ride the blob"
        np.testing.assert_array_equal(c2.prompt, cold.prompt)
        with pytest.raises(ValueError, match="bad magic"):
            unpack_migration(b"NOPE" + b"\x00" * 16)

    def test_cache_opt_out_survives_migration(self):
        """A ``cache=False`` submit promised its KV would never enter a
        shared prefix store — the promise must hold on the PEER too: the
        opt-outs ride the item and the PTMG1 header, and the import
        neither hashes nor registers the migrated context."""
        from paddle_tpu.inference.engine import (pack_migration,
                                                 unpack_migration)
        model = _tiny_model()
        prompt = np.arange(3, 9, dtype=np.int32)
        ref = _fast_ref(model, prompt, 10)
        src, dst = _engine(model), _engine(model)
        src.submit(prompt, max_new_tokens=10, cache=False,
                   speculate=False)
        (item,) = _migrate_once(src, 3)
        assert item.cache is False and item.speculate is False
        w2 = unpack_migration(pack_migration(item))
        assert w2.cache is False and w2.speculate is False
        r = dst.submit_import(w2.handoff,
                              max_new_tokens=w2.max_new_tokens,
                              cache=w2.cache, speculate=w2.speculate)
        assert not r.page_hashes, \
            "opted-out context must not be hashed for the peer's store"
        dst.run_until_idle(max_steps=200)
        np.testing.assert_array_equal(r.result(timeout=30), ref)
        assert not dst._prefix_pages, \
            "opted-out context registered into the peer's prefix cache"

    def test_abort_finishes_exported_but_untaken_futures(self):
        """If take_migrated never runs (serve's drain deadline expired)
        the exported futures live only in the engine's _migrated list —
        abort must answer them too, or each blocked client burns its
        full wait budget on a future nobody will ever finish."""
        model = _tiny_model()
        src = _engine(model)
        req = src.submit(np.arange(1, 7, dtype=np.int32),
                         max_new_tokens=8)
        for _ in range(2):
            src.step()
        src.drain(migrate=True)
        src.step()                 # exported; take_migrated NOT called
        assert not req.done
        src.abort("engine stopped: teardown mid-migrate")
        with pytest.raises(RuntimeError, match="teardown mid-migrate"):
            req.result(timeout=1.0)

    def test_cancel_in_export_window_is_recorded_and_honored(self):
        """A cancel landing between the driver's export (the engine no
        longer knows the request) and _migrate_items registering it in
        the migration tracking must not vanish: while draining it is
        recorded unconditionally, and the migration path finishes the
        request typed-Cancelled instead of shipping it to a peer that
        would decode for a gone client."""
        from paddle_tpu.inference.errors import Cancelled
        from paddle_tpu.inference.serve import InferenceServer
        model = _tiny_model()
        src = _engine(model)
        req = src.submit(np.arange(1, 7, dtype=np.int32),
                         max_new_tokens=8)
        for _ in range(2):
            src.step()
        src.drain(migrate=True)
        src.step()                 # exported: engine.cancel now misses it
        assert not src.cancel(req.request_id)
        # server created AFTER the manual driving: its serve_loop thread
        # must never race the steps above (one driver at a time)
        srv = InferenceServer(None, engine=src, auth_name=FLEET_SECRET)
        srv._draining = True       # plain drain: NO export window, so a
        # cancel for an unknown request stays a clean miss
        assert not srv._cancel_request(req.request_id, "x")
        assert not srv._mig_cancelled
        srv._migrating = True      # migrating drain: record it
        assert srv._cancel_request(req.request_id, "client disconnected")
        items = src.take_migrated(timeout=10)
        assert len(items) == 1
        # the pre-recorded cancel is honored BEFORE any peer is tried
        # (the endpoint below is unreachable — contacting it would fail)
        assert srv._migrate_items(items, ["127.0.0.1:9"],
                                  time.monotonic() + 5.0)
        with pytest.raises(Cancelled, match="client disconnected"):
            req.result(timeout=5.0)
        _stop_server(srv)

    def test_migrating_cancel_records_even_when_engine_claims_it(self):
        """engine.cancel's slot read is a documented benign race: mid
        _do_migrate_out it can answer a stale True for a request the
        driver is detaching. While a migrating drain is underway the
        cancel must therefore be recorded REGARDLESS of the engine's
        answer — leftovers are swept at drain end."""
        from paddle_tpu.inference.serve import InferenceServer
        model = _tiny_model()
        src = _engine(model)
        srv = InferenceServer(None, engine=src, auth_name=FLEET_SECRET)
        req = src.submit(np.arange(1, 7, dtype=np.int32),
                         max_new_tokens=8)   # the serve_loop thread drives
        _wait_for(lambda: len(req.generated) >= 1,
                  msg="first decoded token")
        srv._draining = srv._migrating = True
        assert srv._cancel_request(req.request_id, "gone")  # engine True
        assert srv._mig_cancelled.get(req.request_id) == "gone"
        _stop_server(srv)

    def test_cancel_one_of_two_deferred_imports_no_crash(self):
        """Cancelling a DEFERRED import while another same-shape import
        sits in the mailbox must not crash the driver: removing by
        tuple equality compared the KVHandoffs' numpy arrays ("truth
        value is ambiguous") — the reap filters by request identity.
        The cancelled future ends typed-Cancelled; the survivor still
        applies and completes once a slot frees."""
        from paddle_tpu.inference.errors import Cancelled
        model = _tiny_model()
        prompt_a = np.arange(1, 7, dtype=np.int32)
        prompt_b = np.arange(11, 17, dtype=np.int32)   # same SHAPE as a
        ref_a = _fast_ref(model, prompt_a, 8)
        items = []
        for p in (prompt_a, prompt_b):
            src = _engine(model)
            src.submit(p, max_new_tokens=8)
            items += _migrate_once(src, 2)
        dst = _engine(model, max_slots=1)
        occupier = dst.submit(np.arange(30, 34, dtype=np.int32),
                              max_new_tokens=6)
        dst.step()                       # slot taken: imports will defer
        r1 = dst.submit_import(items[0].handoff,
                               max_new_tokens=items[0].max_new_tokens)
        r2 = dst.submit_import(items[1].handoff,
                               max_new_tokens=items[1].max_new_tokens)
        assert dst.cancel(r2.request_id)
        dst.step()                       # reap runs — used to ValueError
        with pytest.raises(Cancelled):
            r2.result(timeout=10)
        dst.run_until_idle(max_steps=300)
        occupier.result(timeout=30)
        np.testing.assert_array_equal(r1.result(timeout=30), ref_a)
        _assert_pool_baseline(dst)

    def test_migrating_engine_refuses_submit_import(self):
        model = _tiny_model()
        a, b = _engine(model), _engine(model)
        b.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=8)
        (item,) = _migrate_once(b, 2)
        a.drain(migrate=True)
        with pytest.raises(RuntimeError, match="draining"):
            a.submit_import(item.handoff,
                            max_new_tokens=item.max_new_tokens)

    def test_drain_without_migrate_keeps_waiting_semantics(self):
        model = _tiny_model()
        eng = _engine(model)
        r = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=6)
        eng.step()
        eng.drain()                      # PR 8 semantics: wait it out
        eng.run_until_idle(max_steps=200)
        assert r.result(timeout=30).size == 12
        _assert_pool_baseline(eng)


# ---------------------------------------------------------- wire level


def _replica(model, **ekw):
    from paddle_tpu.inference.serve import InferenceServer
    srv = InferenceServer(None, engine=_engine(model, **ekw),
                          auth_name=FLEET_SECRET)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


class TestWireMigration:
    def test_drain_splices_peer_tokens_into_original_future(self):
        model = _tiny_model()
        prompt = np.arange(3, 9, dtype=np.int32)
        ref = _fast_ref(model, prompt, 16)
        a = _replica(model)
        b = _replica(model)
        from paddle_tpu.inference.serve import RemotePredictor
        outs = {}

        def client():
            cli = RemotePredictor(port=a.port, secret=FLEET_SECRET)
            outs["x"] = cli.generate(prompt, max_new_tokens=16)
            cli.close()

        t = threading.Thread(target=client)
        t.start()
        base_out = _counter("serve.migrations_out")
        # pin the timing: slowed steps guarantee the drain lands while the
        # request is MID-decode, not after it finished (deterministic — the
        # fault stays armed through the drain; it only stretches steps)
        with faults.scoped("engine.step_delay", times=-1, delay_s=0.01):
            _wait_for(lambda: any(
                r is not None and len(r.generated) >= 2
                for r in a._engine._slot_req), msg="mid-decode on A")
            clean = a.drain(migrate_peers=[f"127.0.0.1:{b.port}"])
        t.join(timeout=60)
        assert clean is True
        np.testing.assert_array_equal(outs["x"], ref)
        assert _counter("serve.migrations_out") == base_out + 1
        b.drain(deadline_s=5.0)

    def test_peer_death_falls_back_to_next_peer(self):
        model = _tiny_model()
        prompt = np.arange(2, 8, dtype=np.int32)
        ref = _fast_ref(model, prompt, 16)
        a = _replica(model)
        b = _replica(model)
        c = _replica(model)
        from paddle_tpu.inference.serve import RemotePredictor
        outs = {}

        def client():
            cli = RemotePredictor(port=a.port, secret=FLEET_SECRET)
            outs["x"] = cli.generate(prompt, max_new_tokens=16)
            cli.close()

        t = threading.Thread(target=client)
        t.start()
        base_drop = _counter("serve.migrate_drops")
        with faults.scoped("engine.step_delay", times=-1, delay_s=0.01):
            _wait_for(lambda: any(
                r is not None and len(r.generated) >= 2
                for r in a._engine._slot_req), msg="mid-decode on A")
            # first peer attempt dies (injected) -> item lands on the next
            with faults.scoped("serve.migrate_drop", times=1):
                clean = a.drain(migrate_peers=[f"127.0.0.1:{b.port}",
                                               f"127.0.0.1:{c.port}"])
        t.join(timeout=60)
        assert clean is True
        np.testing.assert_array_equal(outs["x"], ref)
        assert _counter("serve.migrate_drops") == base_drop + 1
        for srv in (b, c):
            srv.drain(deadline_s=5.0)

    def test_all_peers_dead_is_bounded_typed_error(self):
        model = _tiny_model()
        prompt = np.arange(2, 8, dtype=np.int32)
        a = _replica(model)
        dead = _replica(model)
        dead_port = dead.port
        dead._stop.set()
        dead._sock.close()               # nothing listens here anymore
        time.sleep(0.1)
        from paddle_tpu.inference.serve import RemotePredictor
        errs = {}

        def client():
            cli = RemotePredictor(port=a.port, secret=FLEET_SECRET)
            try:
                cli.generate(prompt, max_new_tokens=16)
            except RuntimeError as e:
                errs["x"] = str(e)
            finally:
                cli.close()

        t = threading.Thread(target=client)
        t.start()
        base_fail = _counter("serve.migrate_failed")
        with faults.scoped("engine.step_delay", times=-1, delay_s=0.01):
            _wait_for(lambda: any(
                r is not None and len(r.generated) >= 2
                for r in a._engine._slot_req), msg="mid-decode on A")
            clean = a.drain(deadline_s=10.0,
                            migrate_peers=[f"127.0.0.1:{dead_port}"])
        t.join(timeout=60)
        assert clean is False
        assert "migration failed" in errs["x"], errs
        assert _counter("serve.migrate_failed") == base_fail + 1
        # the source engine is still page-clean: detach freed everything
        _assert_pool_baseline(a._engine)

    def test_cancel_tag_follows_the_migration_to_the_peer(self):
        """A request's CANCEL tag rides the PTMG1 blob and the peer
        re-registers it, so a cancel that reaches the PEER (the router
        broadcasts CANCEL to every replica) stops the migrated decode —
        the client gets a typed Cancelled, never a full answer from an
        engine it told to stop."""
        from paddle_tpu.inference.errors import Cancelled
        from paddle_tpu.inference.serve import RemotePredictor
        model = _tiny_model()
        prompt = np.arange(3, 9, dtype=np.int32)
        a = _replica(model)
        b = _replica(model)
        res, drained = {}, {}

        def client():
            cli = RemotePredictor(port=a.port, secret=FLEET_SECRET)
            try:
                res["out"] = cli.generate(prompt, max_new_tokens=40,
                                          tag="mig-cancel")
            except Exception as e:  # noqa: BLE001 — recorded
                res["err"] = e
            cli.close()

        t = threading.Thread(target=client)
        t.start()
        with faults.scoped("engine.step_delay", times=-1, delay_s=0.03):
            _wait_for(lambda: any(
                r is not None and len(r.generated) >= 2
                for r in a._engine._slot_req), msg="mid-decode on A")
            dt = threading.Thread(target=lambda: drained.update(
                ok=a.drain(deadline_s=60.0,
                           migrate_peers=[f"127.0.0.1:{b.port}"])))
            dt.start()
            # the peer registered the travelled tag: cancellable there
            _wait_for(lambda: b._tags, msg="tag registered on B")
            _wait_for(lambda: b._engine._occupied(),
                      msg="migrated decode running on B")
            ctl = RemotePredictor(port=b.port, secret=FLEET_SECRET)
            assert ctl.cancel("mig-cancel") is True
            ctl.close()
            dt.join(timeout=60)
            t.join(timeout=60)
        assert not t.is_alive(), "client hung after cancel"
        assert drained.get("ok") is True, \
            "a cancelled migration is still a CLEAN drain outcome"
        assert isinstance(res.get("err"), Cancelled), res
        _wait_for(lambda: not b._engine._has_work(), msg="B quiesce")
        _assert_pool_baseline(b._engine)
        _assert_pool_baseline(a._engine)
        b.drain(deadline_s=5.0)

    def test_victim_cancel_drops_the_peer_exchange(self):
        """The other half of the chain: a cancel landing on the VICTIM
        after its drain exported the request — its engine no longer owns
        it — marks the migrating item and drops the OP_MIGRATE socket;
        the peer's disconnect watch turns the EOF into an engine cancel
        (client -> victim -> peer -> engine composes) and the client
        gets a typed Cancelled, not a silently-burning decode."""
        from paddle_tpu.inference.errors import Cancelled
        from paddle_tpu.inference.serve import RemotePredictor
        model = _tiny_model()
        prompt = np.arange(2, 8, dtype=np.int32)
        a = _replica(model)
        b = _replica(model)
        res, drained = {}, {}

        def client():
            cli = RemotePredictor(port=a.port, secret=FLEET_SECRET)
            try:
                res["out"] = cli.generate(prompt, max_new_tokens=40,
                                          tag="mig-cancel-2")
            except Exception as e:  # noqa: BLE001 — recorded
                res["err"] = e
            cli.close()

        t = threading.Thread(target=client)
        t.start()
        base_dc = _counter("serve.disconnect_cancels")
        with faults.scoped("engine.step_delay", times=-1, delay_s=0.03):
            _wait_for(lambda: any(
                r is not None and len(r.generated) >= 2
                for r in a._engine._slot_req), msg="mid-decode on A")
            dt = threading.Thread(target=lambda: drained.update(
                ok=a.drain(deadline_s=60.0,
                           migrate_peers=[f"127.0.0.1:{b.port}"])))
            dt.start()
            _wait_for(lambda: b._engine._occupied(),
                      msg="migrated decode running on B")
            ctl = RemotePredictor(port=a.port, secret=FLEET_SECRET)
            assert ctl.cancel("mig-cancel-2") is True, \
                "the victim must still answer for an exported request"
            ctl.close()
            dt.join(timeout=60)
            t.join(timeout=60)
        assert not t.is_alive(), "client hung after cancel"
        assert drained.get("ok") is True
        assert isinstance(res.get("err"), Cancelled), res
        # the peer's disconnect watch fired: the decode was stopped, not
        # left burning steps nobody will read
        _wait_for(lambda: _counter("serve.disconnect_cancels")
                  > base_dc, msg="peer disconnect cancel")
        _wait_for(lambda: not b._engine._has_work(), msg="B quiesce")
        _assert_pool_baseline(b._engine)
        _assert_pool_baseline(a._engine)
        b.drain(deadline_s=5.0)

    def test_routed_8_inflight_drain_zero_client_errors(self):
        """THE acceptance drill: a replica fronted by the router drains
        with 8 requests mid-decode — all 8 complete elsewhere,
        token-identical, zero client-visible errors."""
        from paddle_tpu.inference.serve import RemotePredictor
        from paddle_tpu.serving import Router
        model = _tiny_model()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 97, 4 + (i % 3)).astype(np.int32)
                   for i in range(8)]
        refs = [_fast_ref(model, p, 40) for p in prompts]
        a = _replica(model, max_slots=8)
        b = _replica(model, max_slots=8)
        router = Router(replicas={"a": f"127.0.0.1:{a.port}"},
                        replica_secret=FLEET_SECRET,
                        auth_name="front", evict_cooldown_s=600.0)
        threading.Thread(target=router.serve_forever, daemon=True).start()
        outs, errs = {}, []

        def client(i):
            try:
                cli = RemotePredictor(port=router.port, secret="front")
                outs[i] = cli.generate(prompts[i], max_new_tokens=40)
                cli.close()
            except Exception as e:  # noqa: BLE001 — the drill counts these
                errs.append((i, f"{type(e).__name__}: {e}"))

        ths = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
        for t in ths:
            t.start()
        a_eng = a._engine
        base_out = _counter("serve.migrations_out")
        with faults.scoped("engine.step_delay", times=-1, delay_s=0.01):
            _wait_for(lambda: sum(
                1 for r in a_eng._slot_req
                if r is not None and len(r.generated) >= 2) == 8,
                msg="8 requests mid-decode on the victim")
            clean = a.drain(deadline_s=60.0,
                            migrate_peers=[f"127.0.0.1:{b.port}"])
        for t in ths:
            t.join(timeout=120)
        assert not errs, f"client-visible errors: {errs}"
        assert clean is True
        assert _counter("serve.migrations_out") == base_out + 8
        for i in range(8):
            np.testing.assert_array_equal(
                outs[i], refs[i],
                err_msg=f"request {i} diverged across migration")
        _assert_pool_baseline(a_eng)
        router.stop()
        b.drain(deadline_s=10.0)


# PTKV1 blobs of conftest's seeded pages [5, 2, 7] (seed 2600), packed by
# the commit before the pool was stored merged: blake2b-128 of the blob
PTKV1_DIGESTS = {"f32": "b9ff500902fc28c5b711b4bf51cab0ae",
                 "int8": "3d7a4b1c07ddf111fa150d06c06bade3"}


@pytest.mark.parametrize("kv", sorted(PTKV1_DIGESTS))
def test_ptkv1_blob_is_byte_identical_to_the_unmerged_pools(
        kv, seeded_kv_pages):
    """The pool is stored ``[nl, P, ps, nh*dh]``; what leaves it is still
    ``[nl, n, ps, nh, dh]`` and the same row-major bytes: pages scattered
    in come back bit-identical, and the handoff blob they pack to has the
    digest the ``[..., nh, dh]`` pool gave (header's ``pages_shape``
    included)."""
    import hashlib
    from paddle_tpu.inference.engine import KVHandoff
    eng = _engine(_tiny_model(), kv_dtype=kv)
    pages = [5, 2, 7]
    want = seeded_kv_pages(eng, pages, 2600)
    assert eng._kc.shape[2:] == (4, eng._nh * eng._dh)
    got = eng._cache.export_pages(pages)
    assert got[0].shape == (eng._nl, 3, 4, eng._nh, eng._dh)
    for a, b in zip(want, got):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(a, b)
    blob = KVHandoff(prompt=np.arange(10, dtype=np.int32), first_token=7,
                     k_pages=got[0], v_pages=got[1], page_size=4,
                     cache_dtype=np.dtype(eng._cdtype).name,
                     k_scales=got[2], v_scales=got[3]).pack()
    assert hashlib.blake2b(blob, digest_size=16).hexdigest() \
        == PTKV1_DIGESTS[kv]
    assert KVHandoff.unpack(blob).k_pages.shape == got[0].shape

