"""Request-scoped tracing, SLO accounting, flight recorder + watchdog, and
Prometheus exposition (docs/OBSERVABILITY.md, r6 tentpole).

What must hold:
- a CPU engine run with >= 8 concurrent requests produces per-request
  Chrome-trace spans sharing a ``request_id``, non-empty
  `serve.ttft/tpot/e2e_seconds` histograms, ordered ttft <= e2e, unique ids;
- a stalled step loop triggers EXACTLY ONE watchdog dump holding the event
  ring and the stalled requests' traces;
- `metrics.to_prometheus()` passes a strict exposition-format line checker
  (and the serve wire op + stdlib HTTP exporter serve the same document);
- the scanned train step's `train.mfu` gauge lands in (0, 1] from the
  model's ANALYTIC flop count.
"""
import glob
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import metrics


def _tiny_model(vocab=97):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=16, num_layers=2,
                    num_heads=2, intermediate_size=32,
                    max_position_embeddings=32, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _engine(model, **kw):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    kw.setdefault("page_size", 4)
    kw.setdefault("min_bucket", 4)
    return DecodeEngine(model, EngineConfig(**kw))


# ------------------------------------------------------------ request traces


class TestRequestTracing:

    def test_eight_concurrent_requests_slo_and_spans(self):
        """The acceptance run: 8 concurrent requests through a CPU engine.
        Unique request ids, per-request spans grouped by request_id in the
        Chrome trace, non-empty SLO histograms, ttft <= e2e per request."""
        hist_base = {k: metrics.snapshot()["histograms"].get(k, {})
                     .get("count", 0)
                     for k in ("serve.ttft_seconds", "serve.tpot_seconds",
                               "serve.e2e_seconds")}
        m = _tiny_model()
        eng = _engine(m, max_slots=8)
        rng = np.random.RandomState(0)
        reqs = [eng.submit(rng.randint(0, 97, 3 + i % 5).astype(np.int32),
                           max_new_tokens=6) for i in range(8)]
        eng.run_until_idle()
        for r in reqs:
            assert r.result(timeout=60) is not None

        ids = [r.request_id for r in reqs]
        assert len(set(ids)) == 8, f"request ids not unique: {ids}"

        snap = metrics.snapshot()["histograms"]
        for k, base in hist_base.items():
            assert snap[k]["count"] - base == 8, (k, snap[k])
            assert snap[k]["min"] > 0, (k, snap[k])

        # per-request ordering straight off the traces: first token cannot
        # come after the end, queue wait cannot start after admission
        for r in reqs:
            t = r.trace
            ttft = t.t_first_token - t.t_accept
            e2e = t.t_done - t.t_accept
            assert 0 < ttft <= e2e, (r.request_id, ttft, e2e)
            assert t.t_submit <= t.t_admit <= t.t_first_token <= t.t_done
            assert t.n_tokens == 6

        # Chrome-trace grouping: each request contributes its phase spans,
        # all tagged with its request_id in args
        events = metrics.chrome_trace()["traceEvents"]
        for rid in ids:
            names = {e["name"] for e in events
                     if e.get("args", {}).get("request_id") == rid}
            assert {"request.queue", "request.prefill", "request.decode",
                    "request.e2e"} <= names, (rid, names)

    def test_trace_threads_through_serve_wire(self):
        """A GENERATE over TCP rides ONE trace from wire-accept to
        retirement; STATS and the PROMETHEUS wire op both expose the SLO
        series."""
        from paddle_tpu.inference.serve import InferenceServer, \
            RemotePredictor
        base = metrics.snapshot()["histograms"].get(
            "serve.e2e_seconds", {}).get("count", 0)
        m = _tiny_model()
        eng = _engine(m, max_slots=2)
        srv = InferenceServer(None, engine=eng, auth_name="trace-test")
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        rng = np.random.RandomState(1)
        cli = RemotePredictor(port=srv.port, secret="trace-test")
        out = cli.generate(rng.randint(0, 97, 5).astype(np.int32),
                           max_new_tokens=4)
        assert out.shape == (9,)
        stats = cli.stats()
        assert stats["histograms"]["serve.e2e_seconds"]["count"] > base
        prom = cli.prometheus()
        assert "serve_ttft_seconds_count" in prom
        assert "serve_e2e_seconds_count" in prom
        # a GENERATE that dies BEFORE engine retirement (submit validation)
        # still closes its trace as an error
        err_base = stats["counters"].get("serve.request_errors", 0)
        with pytest.raises(RuntimeError, match="max_seq_len"):
            cli.generate(rng.randint(0, 97, 5).astype(np.int32),
                         max_new_tokens=10 ** 6)
        cli.close()              # server drops the conn after an error
        cli2 = RemotePredictor(port=srv.port, secret="trace-test")
        assert cli2.stats()["counters"]["serve.request_errors"] \
            - err_base == 1
        cli2.shutdown_server()
        cli2.close()

    def test_failed_request_counts_errors_not_slo(self):
        """A request the engine fails (pool too small) closes its trace
        with an error: serve.request_errors increments, e2e stays clean."""
        c_base = metrics.snapshot()["counters"].get(
            "serve.request_errors", 0)
        h_base = metrics.snapshot()["histograms"].get(
            "serve.e2e_seconds", {}).get("count", 0)
        m = _tiny_model()
        eng = _engine(m, max_slots=1, num_pages=3)   # 2 usable pages
        req = eng.submit(np.arange(1, 5, dtype=np.int32),
                         max_new_tokens=12)          # needs 4 pages
        with pytest.raises(RuntimeError, match="pages"):
            eng.run_until_idle()
            req.result(timeout=10)
        snap = metrics.snapshot()
        assert snap["counters"]["serve.request_errors"] - c_base == 1
        assert snap["histograms"].get("serve.e2e_seconds", {}) \
            .get("count", 0) == h_base
        assert req.trace.error is not None
        assert req.trace.phase() == "error"


# ------------------------------------------------- flight recorder / watchdog


class TestFlightRecorder:

    def test_ring_is_bounded_and_ordered(self):
        from paddle_tpu.observability.flight_recorder import FlightRecorder
        fr = FlightRecorder(capacity=8)
        for i in range(20):
            fr.record("tick", i=i)
        evs = fr.events()
        assert len(evs) == 8
        assert [e["i"] for e in evs] == list(range(12, 20))
        assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)

    def test_engine_records_lifecycle_events(self):
        from paddle_tpu.observability.flight_recorder import flight
        m = _tiny_model()
        eng = _engine(m, max_slots=2)
        req = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=3)
        eng.run_until_idle()
        req.result(timeout=30)
        kinds = {e["kind"] for e in flight.events()
                 if e.get("request_id") == req.request_id
                 or e["kind"] == "engine.step"}
        assert {"engine.submit", "engine.admit", "engine.retire",
                "engine.step"} <= kinds

    def test_stalled_step_loop_dumps_exactly_once(self, tmp_path):
        """The acceptance stall: work pending, step loop frozen. One dump
        file appears, holding the event ring, the stalled requests' traces,
        and the metrics snapshot; the stall persisting does NOT dump again."""
        m = _tiny_model()
        eng = _engine(m, max_slots=2)
        req = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=8)
        eng.step()                      # admit + dispatch once, then STALL
        wd = eng.start_watchdog(deadline_s=0.25, dump_dir=str(tmp_path),
                                interval_s=0.05)
        try:
            deadline = time.time() + 10
            while wd.dump_count == 0 and time.time() < deadline:
                time.sleep(0.05)
            time.sleep(0.6)             # stall persists: still one dump
        finally:
            wd.stop()
        files = glob.glob(str(tmp_path / "watchdog_engine_*.json"))
        assert wd.dump_count == 1 and len(files) == 1, (wd.dump_count, files)
        payload = json.load(open(files[0]))
        assert payload["watchdog"] == "engine"
        assert payload["stalled_for_s"] >= 0.25
        kinds = {e["kind"] for e in payload["events"]}
        assert "engine.submit" in kinds and "engine.step" in kinds
        stalled = [t["request_id"] for t in payload["traces"]]
        assert req.request_id in stalled
        assert {"counters", "gauges", "histograms"} <= \
            set(payload["metrics"])
        # loop resumes -> drains; a fresh watchdog sees a healthy engine
        eng.run_until_idle()
        assert req.result(timeout=30).shape == (13,)

    def test_idle_engine_never_dumps(self, tmp_path):
        m = _tiny_model()
        eng = _engine(m, max_slots=1)
        wd = eng.start_watchdog(deadline_s=0.1, dump_dir=str(tmp_path),
                                interval_s=0.03)
        try:
            time.sleep(0.5)             # no work: busy() is False
        finally:
            wd.stop()
        assert wd.dump_count == 0

    def test_deadline_env_disable(self, monkeypatch):
        monkeypatch.setenv("PADDLE_WATCHDOG_S", "0")
        m = _tiny_model()
        eng = _engine(m, max_slots=1)
        assert eng.start_watchdog() is None

    def test_train_step_watchdog_and_flight_events(self, tmp_path):
        from paddle_tpu.observability.flight_recorder import flight
        from paddle_tpu.train import ScanTrainStep
        m = _tiny_model()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = ScanTrainStep(m, opt, microbatches=1)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 97, (2, 9))
        x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)
        wd = step.start_watchdog(deadline_s=60, dump_dir=str(tmp_path))
        step.step(x, y)
        step.step(x, y)
        wd.stop()
        assert wd.dump_count == 0       # healthy loop: no dump
        train_evs = [e for e in flight.events() if e["kind"] == "train.step"]
        assert train_evs and train_evs[-1]["ms"] > 0
        assert train_evs[-1]["mfu"] is None     # no utilization off-TPU


# ------------------------------------------------------- train.mfu / analytic


class TestMFU:

    def test_analytic_param_count_matches_model(self):
        from paddle_tpu.models.gpt import analytic_param_count
        m = _tiny_model()
        actual = sum(int(np.prod(p.shape)) for p in m.parameters())
        assert analytic_param_count(m.cfg) == actual

    def _two_steps(self):
        from paddle_tpu.train import ScanTrainStep
        m = _tiny_model()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = ScanTrainStep(m, opt, microbatches=2)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 97, (2, 9))
        x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int64)
        step.step(x, y)                 # compile step (gauges stay steady)
        step.step(x, y)                 # steady step sets them
        return metrics.snapshot()["gauges"]

    def test_mfu_gauge_in_unit_interval_on_a_known_chip(self, monkeypatch):
        """The device kind is steered from the test (the CPU has no peak
        on record): against a v5e's published peak the gauge lands in
        (0, 1]."""
        from paddle_tpu.train import scan_step
        monkeypatch.setattr(scan_step, "_tpu_kind", lambda: "TPU v5 lite")
        snap = self._two_steps()
        assert 0.0 < snap["train.mfu"] <= 1.0, snap["train.mfu"]
        assert snap["train.goodput_tokens_per_s"] > 0

    def test_no_mfu_gauge_off_tpu(self):
        metrics.reset()                 # zeroes gauges in place
        snap = self._two_steps()
        assert not snap.get("train.mfu")
        assert snap["train.goodput_tokens_per_s"] > 0

    def test_unknown_device_kind_has_no_peak(self):
        from paddle_tpu.train.scan_step import PEAK_BF16_FLOPS, peak_flops
        assert peak_flops("TPU v5 lite") == PEAK_BF16_FLOPS["TPU v5 lite"]
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            peak_flops("TPU v99")
        with pytest.raises(ValueError, match="'cpu'"):
            peak_flops()                # this process's device is a CPU


# ------------------------------------------------------- prometheus rendering

# strict exposition line grammar (format 0.0.4): a sample line is
#   name{label="value",...} value
# with the metric/label name charsets the spec mandates; values are a float,
# +Inf/-Inf, or NaN. Comment lines are # TYPE / # HELP only.
_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
_VALUE = r"(?:[+-]?(?:\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|Inf)|NaN)"
SAMPLE_RE = re.compile(
    rf"^{_NAME}(?:\{{{_LABEL}(?:,{_LABEL})*\}})? {_VALUE}$")
TYPE_RE = re.compile(
    rf"^# TYPE {_NAME} (?:counter|gauge|summary|histogram|untyped)$")
HELP_RE = re.compile(rf"^# HELP {_NAME} .*$")


def check_exposition(text):
    """Line-format check + structural rules: every sample's base name must
    be under a preceding # TYPE, each name TYPE'd at most once."""
    typed, current = {}, None
    assert text.endswith("\n"), "exposition must end with a newline"
    for i, line in enumerate(text.splitlines()):
        if not line:
            continue
        if line.startswith("#"):
            if TYPE_RE.match(line):
                name = line.split()[2]
                assert name not in typed, f"duplicate TYPE for {name}"
                typed[name] = line.split()[3]
                current = name
                continue
            assert HELP_RE.match(line), f"line {i}: bad comment {line!r}"
            continue
        assert SAMPLE_RE.match(line), f"line {i}: bad sample {line!r}"
        base = re.match(_NAME, line).group(0)
        if typed.get(current) == "summary":
            assert base in (current, current + "_sum",
                            current + "_count"), \
                f"line {i}: {base} outside summary {current}"
        else:
            assert base == current, f"line {i}: {base} under TYPE {current}"
    return typed


class TestPrometheus:

    def test_exposition_passes_strict_checker(self):
        # make sure every metric kind and a labelled metric are present
        metrics.counter("promtest.count", mode="a b").inc(3)
        metrics.gauge("promtest.gauge").set(-1.5)
        h = metrics.histogram("promtest.seconds")
        for v in (0.1, 0.2, 0.3):
            h.observe(v)
        text = metrics.to_prometheus()
        typed = check_exposition(text)
        assert typed["promtest_count"] == "counter"
        assert typed["promtest_gauge"] == "gauge"
        assert typed["promtest_seconds"] == "summary"
        assert 'promtest_count{mode="a b"} 3' in text
        assert "promtest_seconds_count 3" in text
        assert 'promtest_seconds{quantile="0.5"} 0.2' in text

    def test_name_sanitization(self):
        from paddle_tpu.observability.prometheus import _name
        assert _name("engine.steps") == "engine_steps"
        assert _name("9weird-name!") == "_9weird_name_"

    def test_label_value_escaping(self):
        metrics.counter("promtest.esc", path='a"b\\c\nd').inc()
        text = metrics.to_prometheus()
        check_exposition(text)
        assert 'path="a\\"b\\\\c\\nd"' in text

    def test_empty_histogram_renders_without_quantiles(self):
        metrics.histogram("promtest.empty_seconds")
        text = metrics.to_prometheus()
        check_exposition(text)
        assert "promtest_empty_seconds_count 0" in text
        assert 'promtest_empty_seconds{quantile' not in text

    def test_http_exporter_serves_metrics(self):
        import urllib.request
        from paddle_tpu.observability.prometheus import (CONTENT_TYPE,
                                                         start_http_exporter)
        metrics.counter("promtest.http").inc()
        srv = start_http_exporter(port=0)
        try:
            port = srv.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                assert r.status == 200
                assert r.headers["Content-Type"] == CONTENT_TYPE
                body = r.read().decode()
            check_exposition(body)
            assert "promtest_http 1" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/bogus", timeout=10)
        finally:
            srv.shutdown()
