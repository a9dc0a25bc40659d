"""Shape-dynamism tripwire for the decode engine.

Continuous batching only pays off if slot churn (sequences joining,
retiring, different active sets, different prompt lengths within a bucket)
NEVER changes a program shape. These tests warm the engine up, then push it
through every churn pattern and assert the registry's compile counters are
frozen — a regression that sneaks a host value into a traced shape fails
here instead of as a silent 100x serving slowdown.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics


def _tiny_model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    intermediate_size=64, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _compile_counters():
    snap = metrics.snapshot()["counters"]
    return (snap.get("engine.compile_count", 0),
            snap.get("jit.compile_count", 0),
            snap.get("generate.compile_count", 0))


def test_slot_churn_zero_recompiles():
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=3,
                                       min_bucket=8))
    rng = np.random.RandomState(0)

    # ---- warmup: compile the decode step + the one prefill bucket the
    # traffic below uses (prompt lengths 3..8 all pad to bucket 8)
    eng.warmup(prompt_lens=[8])
    r = eng.submit(rng.randint(0, 64, 5).astype(np.int32), 3)
    eng.run_until_idle(max_steps=20)
    assert r.done
    frozen = _compile_counters()

    # ---- churn: different slot counts, different active sets, staggered
    # retirement, late joins — every shape the engine sees is warm
    reqs = [eng.submit(rng.randint(0, 64, 3 + i).astype(np.int32), 2 + i)
            for i in range(3)]                       # fills all 3 slots
    for _ in range(2):
        eng.step()
    late = eng.submit(rng.randint(0, 64, 8).astype(np.int32), 4)
    eng.run_until_idle(max_steps=100)
    for req in reqs + [late]:
        assert req.done

    assert _compile_counters() == frozen, (
        "decode engine recompiled after warmup: slot churn must be "
        "shape-invariant")


def test_new_bucket_compiles_exactly_once():
    """A prompt length outside the warm bucket set compiles ONE new prefill
    program; re-using that bucket afterwards is free."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                       min_bucket=8))
    rng = np.random.RandomState(1)
    eng.submit(rng.randint(0, 64, 4).astype(np.int32), 2)
    eng.run_until_idle(max_steps=20)             # decode + bucket-8 compiled
    base = _compile_counters()

    eng.submit(rng.randint(0, 64, 12).astype(np.int32), 2)   # bucket 16
    eng.run_until_idle(max_steps=20)
    after_new = _compile_counters()
    assert after_new[0] == base[0] + 1

    eng.submit(rng.randint(0, 64, 9).astype(np.int32), 2)    # bucket 16 again
    eng.submit(rng.randint(0, 64, 6).astype(np.int32), 2)    # bucket 8 again
    eng.run_until_idle(max_steps=40)
    assert _compile_counters() == after_new


def test_chunked_prefill_compiles_once():
    """Decode-priority chunked prefill keeps the AOT discipline: ONE chunk
    program regardless of prompt length (every chunk, tail included, pads
    to the fixed chunk size), and chunked traffic after warmup never
    retraces — prompts at/below the chunk size still ride the warm
    bucketed path."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=3,
                                       min_bucket=8,
                                       prefill_chunk_tokens=8))
    rng = np.random.RandomState(4)
    # warmup compiles decode + the chunk program (len 20 > chunk 8) + the
    # bucket-8 program (len 5 takes the one-shot path)
    eng.warmup(prompt_lens=[5, 20])
    r = eng.submit(rng.randint(0, 64, 20).astype(np.int32), 3)
    eng.run_until_idle(max_steps=60)
    assert r.done
    frozen = _compile_counters()

    # churn: chunked prompts of different lengths (2, 3, 5 chunks with
    # ragged tails), short one-shot prompts, decode running throughout
    reqs = [eng.submit(rng.randint(0, 64, s).astype(np.int32), 3)
            for s in (13, 24, 37, 5, 17)]
    eng.run_until_idle(max_steps=300)
    for req in reqs:
        assert req.done
    assert metrics.snapshot()["counters"].get("engine.prefill_chunks", 0) \
        >= 3 + 2 + 3 + 5, "chunked path did not run"
    assert _compile_counters() == frozen, (
        "chunked prefill recompiled after warmup: every chunk must be one "
        "fixed program shape")


def test_verify_step_compiles_once():
    """Speculative decoding keeps the AOT discipline: ONE verify program
    per k (draft contents and draft_len ride the packed upload, never a
    shape), and draft-availability churn — slots with full drafts, partial
    drafts, and none in the same step — never retraces."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=3,
                                       min_bucket=8, speculate_k=3,
                                       prefix_cache=False))
    rng = np.random.RandomState(5)
    eng.warmup(prompt_lens=[8])
    r = eng.submit(rng.randint(0, 64, 5).astype(np.int32), 3)
    eng.run_until_idle(max_steps=30)
    assert r.done
    frozen = _compile_counters()

    # churn: repetitive prompts (drafts accepted), random prompts (drafts
    # rejected), staggered joins — every step is the one warm verify shape
    reqs = [eng.submit(np.tile(rng.randint(0, 64, 2).astype(np.int32), 3),
                       8)]
    reqs += [eng.submit(rng.randint(0, 64, 3 + i).astype(np.int32), 4 + i)
             for i in range(2)]
    eng.step()
    reqs.append(eng.submit(rng.randint(0, 64, 7).astype(np.int32), 5))
    eng.run_until_idle(max_steps=120)
    for req in reqs:
        assert req.done
    assert metrics.snapshot()["counters"].get("engine.spec_steps", 0) > 0
    assert _compile_counters() == frozen, (
        "speculative engine recompiled after warmup: draft churn must be "
        "shape-invariant")


def test_prefix_hit_skips_prefill_programs():
    """A prefix-cached resubmission performs ZERO prefill-program work for
    the cached pages (counter-pinned via engine.prefill_tokens): the first
    hit compiles exactly one tail-chunk program (a new pow-2 bucket), and
    every later hit runs entirely warm."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                       min_bucket=8))
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 64, 16).astype(np.int32)
    r = eng.submit(prompt, 3)                    # miss: bucket-16 prefill
    eng.run_until_idle(max_steps=30)
    assert r.done
    base = _compile_counters()
    tok0 = metrics.snapshot()["counters"].get("engine.prefill_tokens", 0)

    r2 = eng.submit(prompt, 3)                   # hit: 3 pages shared,
    eng.run_until_idle(max_steps=30)             # 4-token tail re-prefilled
    assert r2.done
    after = _compile_counters()
    assert after[0] == base[0] + 1, (
        "first prefix hit should compile exactly the tail-chunk program")
    toks = metrics.snapshot()["counters"]["engine.prefill_tokens"] - tok0
    assert toks == 4, (
        f"prefill ran {toks} tokens for a 16-token prompt with 12 cached — "
        "cached pages must cost zero prefill-program work")

    r3 = eng.submit(prompt, 3)                   # warm hit: nothing compiles
    eng.run_until_idle(max_steps=30)
    assert r3.done
    assert _compile_counters() == after, (
        "a warm prefix hit must not compile anything")


def test_tier_reupload_zero_recompiles():
    """The KV-tier round trip — spill to host RAM, re-upload on the next
    submit — is eager `export_pages`/`import_pages` + framing, no traced
    program: a tier hit runs the SAME warm tail-chunk program as an HBM
    prefix hit, with zero new compiles anywhere in the cycle."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                       min_bucket=8,
                                       kv_host_tier_bytes=1 << 20))
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 64, 16).astype(np.int32)
    r = eng.submit(prompt, 3)                    # miss: bucket-16 prefill
    eng.run_until_idle(max_steps=30)
    assert r.done
    r2 = eng.submit(prompt, 3)                   # HBM hit compiles the
    eng.run_until_idle(max_steps=30)             # tail-chunk program once
    assert r2.done
    eng._shrink_prefix()                         # evict -> spill to host
    base = _compile_counters()
    tok0 = metrics.snapshot()["counters"].get("engine.prefill_tokens", 0)
    r3 = eng.submit(prompt, 3)                   # tier hit: re-upload
    eng.run_until_idle(max_steps=30)
    assert r3.done
    assert metrics.snapshot()["counters"]["engine.kvtier.reuploads_host"]
    toks = metrics.snapshot()["counters"]["engine.prefill_tokens"] - tok0
    assert toks == 4, (
        f"tier hit prefilled {toks} tokens — re-uploaded pages must cost "
        "zero prefill-program work, exactly like an HBM hit")
    assert _compile_counters() == base, (
        "the spill/re-upload cycle must not compile anything: export, "
        "framing, and import are eager ops outside every program cache")


def test_int8_engine_zero_recompiles_same_program_count():
    """Quantization keeps the AOT discipline (docs/QUANTIZATION.md): an
    int8-KV + int8-weight engine compiles the SAME number of programs as
    the f32 engine for the same traffic shape (scales ride the cache
    pytree, QuantizedLeaf is pytree structure — neither is a new program),
    and slot churn after warmup never retraces."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    rng = np.random.RandomState(7)

    def drive(eng):
        eng.warmup(prompt_lens=[8])
        r = eng.submit(rng.randint(0, 64, 5).astype(np.int32), 3)
        eng.run_until_idle(max_steps=30)
        assert r.done
        return len(eng._programs)

    f32_programs = drive(DecodeEngine(m, EngineConfig(
        page_size=4, max_slots=3, min_bucket=8)))
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=3,
                                       min_bucket=8, kv_dtype="int8",
                                       weight_dtype="int8"))
    assert drive(eng) == f32_programs, (
        "quantized engine compiled a different program count than f32")
    frozen = _compile_counters()

    # churn: staggered joins/retires, all warm shapes — zero recompiles
    reqs = [eng.submit(rng.randint(0, 64, 3 + i).astype(np.int32), 2 + i)
            for i in range(3)]
    for _ in range(2):
        eng.step()
    late = eng.submit(rng.randint(0, 64, 8).astype(np.int32), 4)
    eng.run_until_idle(max_steps=100)
    for req in reqs + [late]:
        assert req.done
    assert len(eng._programs) == f32_programs
    assert _compile_counters() == frozen, (
        "int8 engine recompiled after warmup: quantization must be "
        "shape-invariant")


def test_scan_train_step_compiles_once_and_donates():
    """The captured scan-over-layers train step (paddle_tpu/train): exactly
    ONE compile across N steps with changing batch CONTENTS, frozen
    jit.compile_count, and real buffer donation (the pre-step param and
    opt-state arrays are deleted, not copied)."""
    from paddle_tpu.train import ScanTrainStep
    m = _tiny_model()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    step = ScanTrainStep(m, opt, microbatches=2)
    rng = np.random.RandomState(3)

    def batch():
        ids = rng.randint(0, 64, (4, 9))
        return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)

    x, y = batch()
    old_param = step._params["blocks"]["mlp.fc_in.weight"]
    old_moment = step._opt_state["blocks"]["mlp.fc_in.weight"]["moment1"]
    step.step(x, y)
    # donation check: the old buffers are DELETED, the step did not copy
    assert old_param.is_deleted(), "params were copied, not donated"
    assert old_moment.is_deleted(), "opt state was copied, not donated"

    frozen_jit = metrics.snapshot()["counters"].get("jit.compile_count", 0)
    for _ in range(4):
        step.step(*batch())          # new contents, same shapes
    assert step.compile_count == 1, (
        f"train step recompiled: {step.compile_count} compiles")
    assert metrics.snapshot()["counters"].get("jit.compile_count", 0) \
        == frozen_jit, "jit.compile_count grew on batch-content churn"

    # a different microbatch count is a new program shape: exactly one more
    step.step(*batch(), microbatches=4)
    assert step.compile_count == 2


def test_pallas_path_compiles_once_per_bucket():
    """FLAGS_tpu_paged_impl=pallas must be exactly as shape-stable as the
    XLA path: one decode program, one program per prefill bucket, and slot
    churn after warmup never retraces the Pallas call."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    set_flags({"tpu_paged_impl": "pallas"})
    try:
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8))
        rng = np.random.RandomState(2)
        eng.warmup(prompt_lens=[8])
        r = eng.submit(rng.randint(0, 64, 5).astype(np.int32), 3)
        eng.run_until_idle(max_steps=30)
        assert r.done
        frozen = _compile_counters()

        reqs = [eng.submit(rng.randint(0, 64, 3 + i).astype(np.int32), 2 + i)
                for i in range(2)]                   # churn both slots
        eng.step()
        late = eng.submit(rng.randint(0, 64, 7).astype(np.int32), 3)
        eng.run_until_idle(max_steps=80)
        for req in reqs + [late]:
            assert req.done
        assert _compile_counters() == frozen, (
            "pallas paged decode recompiled after warmup")

        eng.submit(rng.randint(0, 64, 12).astype(np.int32), 2)  # bucket 16
        eng.run_until_idle(max_steps=30)
        after_new = _compile_counters()
        assert after_new[0] == frozen[0] + 1         # exactly ONE new program
    finally:
        set_flags({"tpu_paged_impl": "auto"})


def test_cancel_and_deadline_paths_zero_recompiles():
    """Cancellation and deadline expiry retire slots BETWEEN fixed-shape
    steps (docs/ROBUSTNESS.md): reclaiming a slot early, re-admitting into
    it, and expiring a queued request must all leave every compile counter
    frozen — containment must never cost a retrace."""
    import time

    import pytest

    from paddle_tpu.inference.engine import (Cancelled, DeadlineExceeded,
                                             DecodeEngine, EngineConfig)
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=3,
                                       min_bucket=8))
    rng = np.random.RandomState(6)
    eng.warmup(prompt_lens=[8])
    r = eng.submit(rng.randint(0, 64, 5).astype(np.int32), 3)
    eng.run_until_idle(max_steps=30)
    assert r.done
    frozen = _compile_counters()

    # cancel one of two running decodes mid-flight; the survivor decodes
    # on, and a later submit reuses the reclaimed slot — all warm shapes
    a = eng.submit(rng.randint(0, 64, 6).astype(np.int32), 20)
    b = eng.submit(rng.randint(0, 64, 6).astype(np.int32), 20)
    for _ in range(2):
        eng.step()
    assert eng.cancel(a.request_id)
    # a queued request expires (deadline passes before admission is even
    # attempted) and a slotted one expires mid-decode
    c = eng.submit(rng.randint(0, 64, 7).astype(np.int32), 20,
                   deadline_s=0.01)
    time.sleep(0.03)
    late = eng.submit(rng.randint(0, 64, 8).astype(np.int32), 4)
    eng.run_until_idle(max_steps=200)
    with pytest.raises(Cancelled):
        a.result(timeout=5)
    with pytest.raises(DeadlineExceeded):
        c.result(timeout=5)
    assert b.result(timeout=30) is not None
    assert late.result(timeout=30) is not None
    assert _compile_counters() == frozen, (
        "cancel/deadline retirement recompiled after warmup: containment "
        "must be shape-invariant")


def test_bad_step_skip_and_rollback_zero_recompiles(tmp_path):
    """Bad-step containment is IN-PROGRAM (paddle_tpu/train): a non-finite
    step selects the old params/opt-state inside the same donated program,
    and a checkpoint rollback re-places arrays under identical shardings —
    neither may ever retrace the train step after warmup."""
    import pytest
    from paddle_tpu.testing import faults
    from paddle_tpu.train import (CheckpointManager, ScanTrainStep,
                                  TooManyBadSteps)
    m = _tiny_model()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    step = ScanTrainStep(m, opt, microbatches=1)
    mgr = CheckpointManager(str(tmp_path), step, max_consecutive_bad=2)
    rng = np.random.RandomState(3)

    def batch():
        ids = rng.randint(0, 64, (2, 9))
        return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)

    step.step(*batch())                        # warmup: the ONE compile
    mgr.save(data_cursor=1, sync=True)
    frozen = _compile_counters()
    try:
        faults.arm("train.step_nan", times=3)
        step.step(*batch())                    # bad: skip path, warm program
        step.step(*batch())                    # bad again: ladder trips
        with pytest.raises(TooManyBadSteps):
            mgr.after_step()                   # rollback to the checkpoint
    finally:
        faults.disarm()
    step.step(*batch())                        # post-rollback healthy step
    assert step.compile_count == 1, (
        f"bad-step/rollback retraced the train step: {step.compile_count}")
    assert _compile_counters() == frozen, (
        "bad-step skip or checkpoint rollback recompiled after warmup")


def test_migration_import_zero_recompiles():
    """A live-migration import on a WARM engine compiles nothing
    (docs/SERVING.md "Live migration"): the mailbox placement is a page
    scatter + the same fixed-shape decode step, applied between steps —
    exactly the cancellation discipline, so neither the export on the
    source nor the import on the destination may touch a compile
    counter."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    ekw = dict(page_size=4, max_slots=2, min_bucket=8)
    src = DecodeEngine(m, EngineConfig(**ekw))
    dst = DecodeEngine(m, EngineConfig(**ekw))
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, 64, 6).astype(np.int32)
    # warm BOTH engines through a full request (prefill bucket + decode)
    for eng in (src, dst):
        r = eng.submit(prompt, 4)
        eng.run_until_idle(max_steps=40)
        assert r.done

    src.submit(prompt, 12)
    for _ in range(3):
        src.step()
    frozen = _compile_counters()
    src.drain(migrate=True)
    src.step()
    (item,) = src.take_migrated(timeout=10)
    assert item.handoff is not None
    r2 = dst.submit_import(item.handoff,
                           max_new_tokens=item.max_new_tokens)
    dst.run_until_idle(max_steps=60)
    assert r2.done
    assert _compile_counters() == frozen, (
        "live migration compiled a program: export/import must ride the "
        "warm fixed-shape steps")


def test_disagg_decode_replica_never_compiles_prefill():
    """Disaggregated-serving no-retrace pin (docs/SERVING.md
    "Disaggregated serving"): a decode-tier engine fed only by KV page
    streams compiles its decode step ONCE and never anything
    prefill-shaped — and once warm, further stream imports are
    zero-recompile (the same mailbox discipline as migration)."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.serving.disagg import KVStreamAssembler
    m = _tiny_model()
    ekw = dict(page_size=4, max_slots=2, min_bucket=8)
    src = DecodeEngine(m, EngineConfig(prefill_chunk_tokens=4, **ekw))
    dst = DecodeEngine(m, EngineConfig(**ekw))
    rng = np.random.RandomState(3)

    def stream_once(prompt, n):
        sink = src.submit_prefill_stream(prompt)
        src.step()
        asm, h = KVStreamAssembler(), None
        while True:
            kind, val = sink.get(timeout=10)
            if kind in ("done", "err"):
                assert kind == "done", val
                break
            if kind == "rec":
                h = asm.feed(val)
        r = dst.submit_import(h, max_new_tokens=n)
        dst.run_until_idle(max_steps=60)
        assert r.done
        return r

    stream_once(rng.randint(0, 64, 10).astype(np.int32), 4)   # warm
    assert not any(k[0] in ("prefill", "prefill_chunk")
                   for k in dst._programs), (
        "decode-tier engine compiled a prefill program: the stream "
        "import path must be a page scatter + the warm decode step")
    frozen = _compile_counters()
    # churn: different prompt lengths, a second in-flight import
    stream_once(rng.randint(0, 64, 7).astype(np.int32), 5)
    stream_once(rng.randint(0, 64, 13).astype(np.int32), 3)
    assert _compile_counters() == frozen, (
        "a warm stream import compiled a program")
    assert not any(k[0] in ("prefill", "prefill_chunk")
                   for k in dst._programs)


def test_dedup_attach_and_replay_zero_recompiles():
    """Idempotency dedup (docs/ROBUSTNESS.md "Control-plane HA") touches
    no programs: an in-flight attach returns the existing future before
    any device work, and a completed-key replay answers straight from
    the table — neither may touch a compile counter (the acceptance pin
    for the exactly-once tentpole)."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                       min_bucket=8))
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 64, 6).astype(np.int32)
    key = bytes(range(16))
    r1 = eng.submit(prompt, 6, request_key=key)
    for _ in range(2):
        eng.step()
    frozen = _compile_counters()
    attach = eng.submit(prompt, 6, request_key=key)   # in-flight attach
    assert attach is r1
    eng.run_until_idle(max_steps=40)
    replay = eng.submit(prompt, 6, request_key=key)   # completed replay
    assert replay is r1
    np.testing.assert_array_equal(replay.result(timeout=10),
                                  r1.result(timeout=10))
    assert _compile_counters() == frozen, (
        "dedup attach/replay compiled a program: the table must answer "
        "without touching the device")


def test_elastic_split_step_compiles_once_then_never():
    """The elastic split train step (paddle_tpu/train/elastic.py: local
    grads program -> host fleet reduce -> donated apply program) compiles
    each of its TWO programs exactly once; batch-content churn and stop-
    vote churn through the reducer never retrace — the 'zero recompiles
    after the one post-reform compile' half of the elastic-restart
    contract, pinned without spawning a fleet."""
    from paddle_tpu.train import FleetReducer, ScanTrainStep
    m = _tiny_model()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    reducer = FleetReducer()          # world-1 degenerate fleet
    step = ScanTrainStep(m, opt, microbatches=2, grad_reducer=reducer)
    rng = np.random.RandomState(3)

    def batch():
        ids = rng.randint(0, 64, (4, 9))
        return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)

    step.step(*batch())               # the ONE compile step (both programs)
    assert step.compile_count == 1
    frozen = _compile_counters()
    for i in range(4):
        reducer.request_stop = bool(i % 2)   # stop-vote churn rides the
        step.step(*batch())                  # reduce payload, not a shape
    assert step.compile_count == 1, (
        f"split step recompiled: {step.compile_count}")
    assert _compile_counters() == frozen, (
        "jit.compile_count grew on batch/stop-vote churn through the "
        "split grads/apply pipeline")


def test_ragged_prefill_pallas_compiles_once_per_bucket_class():
    """FLAGS_tpu_prefill_impl=pallas (the authored ragged prefill kernel,
    r15) must be exactly as shape-stable as the XLA arm: one one-shot
    program per prefill bucket, one chunk program per chunk width, and
    prompt-length churn WITHIN a bucket class never retraces the Pallas
    call — the scalar-prefetched (start, valid) carry the raggedness."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    set_flags({"tpu_prefill_impl": "pallas"})
    try:
        m = _tiny_model()
        eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                           min_bucket=8,
                                           prefill_chunk_tokens=4))
        rng = np.random.RandomState(5)
        # warm the chunk program (every prompt > 4 tokens routes through
        # chunks of 4) and the decode step
        r = eng.submit(rng.randint(0, 64, 9).astype(np.int32), 3)
        eng.run_until_idle(max_steps=40)
        assert r.done
        frozen = _compile_counters()
        # ragged churn: different true lengths, different chunk counts,
        # different (start, valid) per chunk — SAME programs
        for s0 in (5, 7, 11, 13):
            rq = eng.submit(rng.randint(0, 64, s0).astype(np.int32), 2)
            eng.run_until_idle(max_steps=60)
            assert rq.done
        assert _compile_counters() == frozen, (
            "pallas ragged prefill recompiled on prompt-length churn")
    finally:
        set_flags({"tpu_prefill_impl": "auto"})


def test_fused_sampler_adds_zero_programs():
    """The fused on-device sampler (EngineConfig.sampling, r15) must add
    ZERO programs to the decode/verify counts: one decode program serves
    every (temperature, top_k, seed) — the params ride the packed upload
    — and per-request knob churn after warmup never recompiles. Same
    contract for the speculative verify program."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    m = _tiny_model()
    rng = np.random.RandomState(9)

    eng = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                       min_bucket=8, sampling=True))
    r = eng.submit(rng.randint(0, 64, 5).astype(np.int32), 3,
                   temperature=0.8, top_k=5, seed=1)
    eng.run_until_idle(max_steps=30)
    assert r.done
    # exactly the greedy engine's program set: 1 decode + 1 prefill bucket
    assert len(eng._programs) == 2, sorted(eng._programs)
    frozen = _compile_counters()
    for i, (t, k) in enumerate([(1.0, 0), (0.5, 3), (2.0, 0), (1.0, 7)]):
        rq = eng.submit(rng.randint(0, 64, 4 + i).astype(np.int32), 2,
                        temperature=t, top_k=k, seed=i)
        eng.run_until_idle(max_steps=40)
        assert rq.done
    assert _compile_counters() == frozen, (
        "sampling-param churn recompiled a step program")

    spec = DecodeEngine(m, EngineConfig(page_size=4, max_slots=2,
                                        min_bucket=8, sampling=True,
                                        speculate_k=2))
    r2 = spec.submit(np.tile(rng.randint(0, 64, 3), 3).astype(np.int32), 4,
                     temperature=0.7, top_k=4, seed=2)
    spec.run_until_idle(max_steps=40)
    assert r2.done
    assert len(spec._programs) == 2, sorted(spec._programs)  # verify+prefill
    frozen2 = _compile_counters()
    # greedy mix, same prefill bucket (len 10 pads to 16 like the warmup 9)
    r3 = spec.submit(rng.randint(0, 64, 10).astype(np.int32), 3)
    spec.run_until_idle(max_steps=40)
    assert r3.done
    assert _compile_counters() == frozen2, (
        "greedy/sampled mix recompiled the verify program")


class _OneMoreLeaf:
    """A model whose family keeps one array more than the real one's: a
    ``[slots]`` int32 that every step function bumps (decode: every slot;
    a prefill: its slot). Nothing of the engine or its programs knows."""

    def __init__(self, model):
        self.cfg, self._model = model.cfg, model

    def engine_family(self):
        import dataclasses
        import jax.numpy as jnp
        fam = self._model.engine_family()
        real, had = fam.steps, fam.state is not None

        def state(slots, page, dtype):
            return (*(fam.state(slots, page, dtype) if had else ()),
                    ("calls", "recurrent", (slots,), jnp.int32))

        def prefill(step):
            def wrapped(*args, cfg, state, slot, **kw):
                *rest, calls = state
                if had:
                    kw.update(state=tuple(rest), slot=slot)
                return (*step(*args, cfg=cfg, **kw), calls.at[slot].add(1))
            return wrapped

        class steps:
            @staticmethod
            def decode_step(params, ids, cache, mask, *, cfg):
                *rest, calls = cache.pop("state")
                if had:
                    cache["state"] = tuple(rest)
                logits, cache = real.decode_step(params, ids, cache, mask,
                                                 cfg=cfg)
                cache["state"] = (*cache.get("state", ()), calls + 1)
                return logits, cache
            prefill_step = staticmethod(prefill(real.prefill_step))
            prefill_chunk_step = staticmethod(
                prefill(real.prefill_chunk_step))

        return dataclasses.replace(
            fam, steps=steps, state=state,
            params=lambda m: fam.params(m._model))


@pytest.mark.parametrize("kind", ["bf16", "int8", "sampling", "hybrid"])
def test_every_program_takes_and_returns_the_cache_whole(kind):
    """The one calling convention (inference/programs.py): every program
    in ``eng._programs`` is ``exe(params, cache, *small) -> (*lead,
    cache)``, the cache its one donated argument, every leaf of it
    aliased to a result. And what is in the cache is the cache module's
    business alone: an array added through the family's ``state`` reaches
    every program, donated and returned, with no program edited. (An int8
    pool refuses a family with state, so it is held to the convention
    alone: its scale pools are the leaves a float pool has not.)"""
    import re
    import jax
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    ecfg = dict(page_size=4, max_slots=3, min_bucket=8, max_seq_len=64,
                prefill_chunk_tokens=8, prefix_cache=False, donate=True)
    if kind == "hybrid":
        from paddle_tpu.models import phi4flash as phi
        cfg = phi.tiny_config()
        model = phi.Phi4FlashForCausalLM(
            cfg, phi.init_params(cfg, seed=7, std=0.1))
    else:
        model = _tiny_model()
        ecfg.update({"bf16": dict(kv_dtype="bf16"),
                     "int8": dict(kv_dtype="int8"),
                     "sampling": dict(sampling=True)}[kind])
    extra = kind != "int8"
    eng = DecodeEngine(_OneMoreLeaf(model) if extra else model,
                       EngineConfig(**ecfg))
    state = model.engine_family().state
    want = {"bf16": 2, "int8": 4, "sampling": 3}.get(kind) \
        or 2 + len(state(3, 4, np.float32))
    want += extra
    leaves = jax.tree_util.tree_leaves
    assert len(leaves(eng._cache)) == want
    reqs = [eng.submit(np.arange(1, n + 1, dtype=np.int32), 3)
            for n in (5, 21)]           # one one-shot prefill, three chunks
    eng.run_until_idle(max_steps=60)
    assert all(r.done for r in reqs)
    assert sorted(k[0] for k in eng._programs) == \
        ["decode", "prefill", "prefill_chunk"]
    tree = jax.tree_util.tree_structure(eng._cache)
    for key, exe in eng._programs.items():
        args = exe.in_tree.children()[0].children()
        assert args[1] == tree, key                    # ONE argument
        assert exe.out_tree.children()[-1] == tree, key  # and ONE result
        donated = [[i.donated for i in leaves(a)] for a in exe.args_info[0]]
        assert all(donated[1]) and not any(
            d for i, ds in enumerate(donated) if i != 1 for d in ds), key
        head = exe.as_text().split("\n", 1)[0]
        n_params = len(leaves(exe.args_info[0][0]))
        aliased = sorted(int(p) for p in re.findall(
            r"\{\d+\}: \((\d+), \{\}", head))
        assert aliased == list(range(n_params, n_params + want)), (key, head)
    if extra:
        calls = np.asarray(eng._cache.state[-1])
        # two prefilled slots: 1 one-shot + 3 chunks; every decode step
        # bumps all three slots
        steps = int(calls[2])
        assert steps > 0 and sorted(calls - steps) == [0, 1, 3]
