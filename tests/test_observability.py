"""Runtime telemetry layer: registry semantics + thread safety, Chrome-trace
round-trip, ProgramCache hit/miss accounting through to_static, collective
byte accounting on the CPU mesh, the profiler step scheduler's state machine,
and the bench.py structured-emission contract (`--smoke`)."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.profiler as profiler
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import (MetricsRegistry, metrics)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ registry


def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # same (name, labels) -> same object; different labels -> different
    assert reg.counter("x.count") is c
    assert reg.counter("x.count", op="a") is not c
    reg.counter("x.count", op="a").inc(2)
    snap = reg.snapshot()
    assert snap["counters"]["x.count"] == 5
    assert snap["counters"]["x.count{op=a}"] == 2


def test_gauge_semantics():
    reg = MetricsRegistry()
    g = reg.gauge("x.gauge")
    g.set(3.5)
    assert g.value == 3.5
    g.inc()
    g.dec(0.5)
    assert g.value == 4.0
    assert reg.snapshot()["gauges"]["x.gauge"] == 4.0


def test_histogram_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("x.hist")
    for v in [1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["total"] == 10.0
    assert s["min"] == 1.0 and s["max"] == 4.0
    assert s["mean"] == 2.5
    assert s["p50"] in (2.0, 3.0)
    assert s["p99"] == 4.0
    empty = reg.histogram("x.empty").summary()
    assert empty["count"] == 0 and empty["p50"] is None


def test_timer_records_histogram_and_span():
    reg = MetricsRegistry()
    with reg.timer("x.op", kind="k"):
        pass
    snap = reg.snapshot()
    assert snap["histograms"]["x.op{kind=k}"]["count"] == 1
    trace = reg.chrome_trace()
    assert any(e["name"] == "x.op{kind=k}" for e in trace["traceEvents"])


def test_registry_thread_safety():
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 2000

    def work():
        c = reg.counter("t.count")
        h = reg.histogram("t.hist")
        for _ in range(n_iter):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("t.count").value == n_threads * n_iter
    assert reg.histogram("t.hist").count == n_threads * n_iter


def test_reset_keeps_cached_handles_live():
    reg = MetricsRegistry()
    c = reg.counter("r.count")
    c.inc(7)
    reg.reset()
    assert reg.snapshot()["counters"]["r.count"] == 0
    c.inc()  # a handle cached before reset must still be observed
    assert reg.snapshot()["counters"]["r.count"] == 1


# -------------------------------------------------------- chrome trace export


def test_chrome_trace_roundtrip(tmp_path):
    reg = MetricsRegistry()
    with reg.timer("span.a"):
        pass
    reg.add_span("span.b", 0.0, 1e-3, cat="test")
    reg.counter("c").inc(3)
    path = reg.export_chrome_trace(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    # Chrome trace schema: traceEvents with complete ('X') events
    assert isinstance(data["traceEvents"], list)
    for e in data["traceEvents"]:
        assert e["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "cat"} <= set(e)
    names = {e["name"] for e in data["traceEvents"]}
    assert {"span.a", "span.b"} <= names
    assert data["metrics"]["counters"]["c"] == 3
    # round-trip through the profiler loader
    res = profiler.load_profiler_result(path)
    assert res.durations("span.b") == pytest.approx([1e-3])
    assert res.metrics["counters"]["c"] == 3


def test_load_profiler_result_rejects_non_trace(tmp_path):
    p = tmp_path / "not_a_trace.json"
    p.write_text(json.dumps({"hello": 1}))
    with pytest.raises(ValueError):
        profiler.load_profiler_result(str(p))
    with pytest.raises(ValueError):
        profiler.load_profiler_result(str(tmp_path))


def test_profiler_export_and_summary_cover_registry(tmp_path, capsys):
    p = profiler.Profiler(timer_only=True)
    p.start()
    with profiler.RecordEvent("unit_event"):
        pass
    metrics.counter("unit.count").inc(2)
    p.step()
    p.stop()
    table = p.summary()
    out = str(table)
    assert "unit_event" in out
    assert "unit.count: +2" in out
    path = p.export(str(tmp_path / "host_trace.json"))
    res = profiler.load_profiler_result(path)
    assert "unit_event" in res.host_events
    assert len(res.step_times) == 1
    assert any(e["name"] == "unit_event" for e in res.trace_events)


# ------------------------------------------------- jit / ProgramCache metrics


def test_program_cache_hit_miss_counters():
    base = metrics.snapshot()["counters"]

    @paddle.jit.to_static
    def f(x):
        return x * 2.0 + 1.0

    t = paddle.to_tensor(np.ones((2, 3), np.float32))
    f(t)
    f(t)
    f(t)
    t2 = paddle.to_tensor(np.ones((4, 3), np.float32))
    f(t2)  # new signature -> second compile

    def delta(name):
        return metrics.snapshot()["counters"].get(name, 0) - base.get(name, 0)

    assert delta("jit.compile_count") == 2
    assert delta("jit.cache_miss") == 2
    assert delta("jit.cache_hit") == 2
    snap = metrics.snapshot()
    assert snap["histograms"]["jit.compile_seconds"]["count"] >= 2
    assert snap["histograms"]["jit.dispatch_seconds"]["count"] >= 4


def test_train_step_records_compile_and_donation():
    """Acceptance: metrics.snapshot() after a to_static train step shows
    nonzero compile and ProgramCache counters (+ donated bytes)."""
    base = metrics.snapshot()["counters"]
    paddle.seed(0)
    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=lin.parameters())

    @paddle.jit.to_static
    def step(x, y):
        loss = ((lin(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.random.randn(2, 4).astype(np.float32))
    y = paddle.to_tensor(np.random.randn(2, 4).astype(np.float32))
    float(step(x, y))
    float(step(x, y))
    cur = metrics.snapshot()["counters"]
    assert cur.get("jit.compile_count", 0) - base.get("jit.compile_count", 0) == 1
    assert cur.get("jit.cache_hit", 0) - base.get("jit.cache_hit", 0) == 1
    assert cur.get("jit.donated_bytes", 0) - base.get("jit.donated_bytes", 0) > 0


# --------------------------------------------------- collective byte metrics


def test_collective_byte_accounting_cpu_mesh():
    """Acceptance: after a CPU-mesh collective the registry shows nonzero
    per-primitive payload bytes (trace-time accounting for in-graph mode)."""
    base = metrics.snapshot()["counters"]
    mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
    g = dist.new_group(axis_name="x")
    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)

    def body(a):
        t = Tensor(a, _internal=True)
        dist.all_reduce(t, group=g)
        return t._data

    f = shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                  check_rep=False)
    out = np.asarray(jax.jit(f)(x))
    np.testing.assert_allclose(out, np.tile(x.sum(axis=0), (8, 1)))

    cur = metrics.snapshot()["counters"]
    calls = "collective.calls{mode=in_graph,op=all_reduce}"
    nbytes = "collective.bytes{mode=in_graph,op=all_reduce}"
    assert cur.get(calls, 0) - base.get(calls, 0) >= 1
    # per-rank payload: 4 f32 = 16 bytes per traced insertion
    moved = cur.get(nbytes, 0) - base.get(nbytes, 0)
    assert moved > 0 and moved % 16 == 0


def test_collective_local_mode_accounting():
    base = metrics.snapshot()["counters"]
    t = paddle.to_tensor(np.ones((3, 2), np.float32))
    dist.all_reduce(t)  # world size 1 -> local identity, still accounted
    cur = metrics.snapshot()["counters"]
    nbytes = "collective.bytes{mode=local,op=all_reduce}"
    assert cur.get(nbytes, 0) - base.get(nbytes, 0) == 3 * 2 * 4


# ------------------------------------------------------- scheduler semantics


def test_scheduler_basic_cycle():
    sched = profiler.make_scheduler(closed=2, ready=1, record=2)
    S = profiler.ProfilerState
    expect = [S.CLOSED, S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN]
    got = [sched(i) for i in range(5)]
    assert got == expect
    # periodic: the cycle repeats
    assert [sched(i) for i in range(5, 10)] == expect


def test_scheduler_skip_first_and_repeat():
    sched = profiler.make_scheduler(closed=1, ready=1, record=1, repeat=2,
                                    skip_first=3)
    S = profiler.ProfilerState
    # steps 0-2 skipped
    assert [sched(i) for i in range(3)] == [S.CLOSED] * 3
    # two full cycles of (closed, ready, record-and-return)
    cycle = [S.CLOSED, S.READY, S.RECORD_AND_RETURN]
    assert [sched(i) for i in range(3, 9)] == cycle * 2
    # after `repeat` cycles: closed forever
    assert [sched(i) for i in range(9, 15)] == [S.CLOSED] * 6


def test_scheduler_record_only_edge():
    # record=1, no closed/ready: every step is the record-and-return edge
    sched = profiler.make_scheduler(record=1)
    S = profiler.ProfilerState
    assert [sched(i) for i in range(3)] == [S.RECORD_AND_RETURN] * 3


# ------------------------------------------------------- dataloader metrics


def test_dataloader_fetch_metrics():
    from paddle_tpu.io import DataLoader

    class DS:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.full((2,), i, np.float32)

    base = metrics.snapshot()["counters"].get("dataloader.batches", 0)
    dl = DataLoader(DS(), batch_size=4, num_workers=0)
    batches = list(dl)
    assert len(batches) == 2
    snap = metrics.snapshot()
    assert snap["counters"]["dataloader.batches"] - base == 2
    assert snap["histograms"]["dataloader.fetch_seconds"]["count"] >= 2


# ------------------------------------------------------------ serve payload


def test_serve_stats_payload_schema():
    from paddle_tpu.inference.serve import stats_payload
    metrics.counter("serve.requests").inc(0)
    payload = stats_payload()
    assert payload.dtype == np.uint8
    decoded = json.loads(payload.tobytes().decode())
    assert {"counters", "gauges", "histograms"} <= set(decoded)


# --------------------------------------------------------------- bench smoke


def test_bench_smoke_emits_structured_json():
    """CI satellite: `bench.py --smoke` is a behaviour check that runs on a
    TPU-less host: it exits 0 and emits one JSON line naming the platform
    it ran on, with step-time, compile-count, and cache hit/miss."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=420, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][-1]
    d = json.loads(line)
    assert d["ok"] is True
    assert d["platform"] == "cpu"
    assert d["metric"] == "smoke_step_time_seconds"
    assert d["value"] > 0
    assert d["compile_count"] >= 1
    assert d["cache_misses"] >= 1 and d["cache_hits"] >= 1
    assert d["metrics"]["counters"]["jit.compile_count"] >= 1
    # r6: the smoke line pins the SLO layer end-to-end — per-request
    # ttft/tpot/e2e percentiles from the engine run and a clean watchdog;
    # a CPU run reports no model FLOP/s utilization
    assert d["watchdog_clean"] is True
    for k in ("ttft_p50", "ttft_p99", "tpot_p50", "tpot_p99",
              "e2e_p50", "e2e_p99"):
        assert d["slo"][k] > 0, (k, d["slo"])
    assert d["slo"]["ttft_p50"] <= d["slo"]["e2e_p50"]
    assert not d["train_mfu"]
    assert d["metrics"]["histograms"]["serve.ttft_seconds"]["count"] >= 3
    # r6: the smoke run routes one request through the serving router (2
    # wire hops, static membership) and chunk-prefills every engine prompt
    assert d["router_ok"] is True
    assert d["prefill_chunks"] >= 3
    assert d["metrics"]["counters"]["router.requests"] >= 1
    # r7: the smoke run exercises one prefix-cache HIT (a resubmitted
    # prompt attaches its cached pages by reference) and at least one
    # speculative verify step (n-gram draft, k-token verify)
    assert d["prefix_hits"] >= 1
    assert d["spec_accepted"] >= 0
    assert d["metrics"]["counters"]["engine.spec_steps"] >= 1
    assert d["metrics"]["counters"]["engine.prefix_pages_reused"] >= 1
    # r8: the smoke run exercises one typed SHED (admission control) and
    # one CANCEL (failure containment, docs/ROBUSTNESS.md)
    assert d["shed"] >= 1
    assert d["cancelled"] >= 1
    assert d["metrics"]["counters"]["engine.shed"] >= 1
    assert d["metrics"]["counters"]["engine.cancelled"] >= 1
    # r9: the smoke run exercises one save -> kill -> resume cycle on the
    # scanned train step (train fault tolerance, docs/ROBUSTNESS.md): the
    # resumed step's loss matched the uninterrupted continuation exactly
    assert d["resume_ok"] is True
    assert d["metrics"]["counters"]["train.checkpoints"] >= 1
    assert d["metrics"]["counters"]["train.resumes"] >= 1
    # r10: the smoke run decodes through an int8-KV engine and pins the
    # documented parity contract (docs/QUANTIZATION.md): prefill logits
    # within the bound of f32, margin-gated top-1 agreement
    assert d["kv_quant_ok"] is True
    assert d["metrics"]["gauges"].get("engine.kv_bytes_per_token", 0) > 0
    # r11: the smoke run exercises one LIVE MIGRATION (a mid-decode
    # request exported as a warm KV handoff resumes on a second engine
    # TOKEN-IDENTICAL to the uninterrupted run, docs/SERVING.md)
    assert d["migrate_ok"] is True
    assert d["metrics"]["counters"]["engine.migrations_out"] >= 1
    assert d["metrics"]["counters"]["engine.migrations_in"] >= 1
    # r14: the smoke run drives one typed PeerLost through the liveness
    # monitor (a silent peer past the heartbeat deadline — the collective
    # hang watchdog of docs/ROBUSTNESS.md "Multi-host training")
    assert d["peer_lost_typed_ok"] is True
    assert d["metrics"]["counters"]["train.peer_lost"] >= 1
    # r12: the smoke run drives a 2-iteration soak micro drill
    # (paddle_tpu/testing/soak.py — rotated fault orderings, typed
    # outcomes, page-clean pool) which includes an idempotency-dedup
    # REPLAY (docs/ROBUSTNESS.md "Control-plane HA")
    assert d["soak_ok"] is True
    assert d["dedup_replays"] >= 1
    assert d["metrics"]["counters"]["engine.dedup_replays"] >= 1
    # r13: the smoke run routes one DISAGGREGATED request — a prefill
    # worker streams PTKS1 page records through the router to a decode
    # replica (token-identical to the symmetric route, and the decode
    # engine compiled zero prefill programs; docs/SERVING.md
    # "Disaggregated serving")
    assert d["disagg_ok"] is True
    assert d["metrics"]["counters"]["router.disagg_requests"] >= 1
    assert d["metrics"]["counters"]["serve.prefill_streams"] >= 1
    assert d["metrics"]["counters"]["serve.kv_stream_in"] >= 1
    assert d["metrics"]["counters"]["engine.kv_stream_exports"] >= 1
    # r15: the smoke run samples one request through the FUSED ON-DEVICE
    # sampler (kernels/sampling.py) bit-identically to fast_generate's
    # host sampler, with zero logits readbacks, and every kernel
    # selection routed through the ONE registry (kernels/registry.py —
    # kernel.dispatch.* counters fired for paged/prefill/sampling/ce)
    assert d["fused_sampler_ok"] is True
    assert d["logits_readback"] == 0
    kd = {k: v for k, v in d["metrics"]["counters"].items()
          if k.startswith("kernel.dispatch.") and v}
    for op in ("paged_attention", "prefill_attention", "fused_sampling",
               "fused_ce", "flash_attention"):
        assert any(k.startswith(f"kernel.dispatch.{op}.") for k in kd), \
            (op, sorted(kd))
    # r16: the smoke run routes one TRACED request — the minted context
    # chains client -> router -> replica spans, exports over the
    # TRACE_EXPORT wire op, and stitches into one Chrome trace — and the
    # router's STATS poll feeds the attached fleet metrics plane (rollup,
    # re-labeled rows, shared snapshot API; docs/OBSERVABILITY.md "Fleet
    # tracing" / "Fleet metrics plane")
    assert d["fleet_trace_ok"] is True
    assert d["fleet_metrics_ok"] is True
    # round 17: one KV-tier spill -> re-upload cycle answered
    # token-identically with tail-only prefill work and zero typed
    # refusals (docs/SERVING.md "KV tiering")
    assert d["kvtier_ok"] is True
    assert d["metrics"]["counters"].get("engine.kvtier.reuploads_host",
                                        0) >= 2
    # round 18: one SLO alert lifecycle on an injected clock — a latency
    # objective fires under the armed engine.step_delay fault and
    # resolves on clean traffic (observability/slo.py) — and every
    # terminated request emitted a usage record whose token fields agree
    # with the engine's aggregate counters (observability/usage.py)
    assert d["slo_alert_ok"] is True
    assert d["usage_ok"] is True
    assert d["metrics"]["counters"].get("slo.alerts_fired", 0) >= 1
    assert d["metrics"]["counters"].get("slo.alerts_resolved", 0) >= 1
    assert d["metrics"]["counters"].get("usage.requests", 0) >= 1
    assert d["metrics"]["counters"].get("usage.generated_tokens", 0) >= 1


@pytest.mark.parametrize("argv", [["--smoke"], []], ids=["smoke", "ladder"])
def test_bench_dead_backend_exits_nonzero_and_names_the_error(argv):
    """A backend that initialises and then dies on first USE (the
    `bench.preflight` fault site plays it) is not papered over with CPU
    rungs: `bench.py` emits one `ok: false` record naming the error and
    exits non-zero, with or without `--smoke`."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_FAULTS"] = "bench.preflight:exc=RuntimeError:times=1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *argv],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, (proc.stdout, proc.stderr[-2000:])
    d = json.loads(lines[0])
    assert d["ok"] is False and d["value"] == 0.0
    assert "RuntimeError" in d["backend_error"]
    assert "bench.preflight" in d["backend_error"]


def test_bench_ladder_refuses_a_host_without_a_chip():
    """The ladder measures a chip: on the CPU platform it says so and exits
    non-zero instead of emitting CPU rates under device metric names."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert proc.returncode != 0, proc.stdout[-2000:]
    d = json.loads([ln for ln in proc.stdout.splitlines()
                    if ln.startswith("{")][-1])
    assert d["ok"] is False and d["platform"] == "cpu"
    assert "no accelerator" in d["backend_error"]


def test_bench_failing_platform_plugin_exits_nonzero():
    """A CONFIGURED platform whose plugin cannot initialise is an error the
    record names — one parseable `ok: false` line and a non-zero exit, not
    a silent CPU run and not a raw traceback with no artifact."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "definitely_not_a_backend"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, (proc.stdout, proc.stderr[-2000:])
    d = json.loads(lines[0])
    assert d["metric"] == "smoke_step_time_seconds"
    assert d["ok"] is False and d["platform"] is None
    assert "definitely_not_a_backend" in d["backend_error"]
