"""paddle.profiler (ref: `python/paddle/profiler/profiler.py:339` — step-scheduled
Profiler, RecordEvent at `profiler/utils.py:37`, chrome-trace export at :210).

TPU-native: host annotations are jax.profiler TraceAnnotations (XPlane), device
activity comes from the XLA/TPU profiler; export lands a TensorBoard-compatible
trace directory instead of the reference's CUPTI chrome json.
"""
from __future__ import annotations

import contextlib
import enum
import json
import os
import time

import jax

from paddle_tpu.observability import metrics as _metrics


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Build the CLOSED/READY/RECORD step state machine (ref make_scheduler)."""
    period = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof.export(dir_name)
    return handler


def export_protobuf(dir_name, worker_name=None):
    return export_chrome_tracing(dir_name)


# host-side event aggregation feeding Profiler.summary() — the analog of the
# reference's HostTracer ring buffers + profiler_statistic.py tables
_host_events: dict = {}
_collecting = False


def _record_host_event(name, seconds):
    if not _collecting:
        return
    cnt, total, mx = _host_events.get(name, (0, 0.0, 0.0))
    _host_events[name] = (cnt + 1, total + seconds, max(mx, seconds))


class RecordEvent:
    """Host-side named range (≈ platform::RecordEvent -> TraceMe); durations
    also feed the host statistics table while a Profiler is active."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()

    def begin(self):
        # the registry's span: on its ring (Profiler.export(path) /
        # observability.chrome_trace() see it) and a TraceAnnotation
        self._span = _metrics.span(self.name, cat="host")
        self._span.__enter__()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            _record_host_event(self.name, self._span.dur)
            self._span = None


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            start, end = scheduler
            self._scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                             record=end - start, repeat=1)
        else:
            self._scheduler = None  # always record
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._running = False
        self._logdir = None
        self._step_times = []
        self._last_step_time = None
        self._metrics_base = {}

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()

    def start(self):
        global _collecting
        _collecting = True
        _host_events.clear()
        # counter baseline: summary() reports the registry DELTA over the
        # profiled region, so compile counts / cache hits / collective bytes
        # from warmup don't pollute the table
        self._metrics_base = _metrics.snapshot().get("counters", {})
        self._last_step_time = time.perf_counter()
        if self._timer_only:
            return
        self._logdir = os.environ.get("PADDLE_TPU_PROFILE_DIR",
                                      "/tmp/paddle_tpu_profile")
        os.makedirs(self._logdir, exist_ok=True)
        try:
            jax.profiler.start_trace(self._logdir)
            self._running = True
        except Exception:
            self._running = False

    def stop(self):
        global _collecting
        _collecting = False
        if self._running:
            try:
                jax.profiler.stop_trace()
            finally:
                self._running = False
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_time is not None:
            self._step_times.append((now - self._last_step_time, num_samples))
        self._last_step_time = now
        self._step += 1

    def step_info(self, unit="samples"):
        if not self._step_times:
            return ""
        dt, n = self._step_times[-1]
        ips = (n / dt) if (n and dt > 0) else (1.0 / dt if dt > 0 else 0.0)
        return (f"step_time: {dt * 1000:.2f} ms, ips: {ips:.2f} {unit}/s")

    def export(self, path=None, format=None):
        """With no arguments: the device trace already landed in the logdir
        (TensorBoard/XPlane format) — return it. With a ``path``: write the
        HOST-side trace as one Chrome-trace JSON file (RecordEvent ranges,
        jit capture / pipeline / decode spans off the observability ring,
        metric snapshot, host-event aggregates, step times) — the file
        `load_profiler_result` reads back."""
        if path is None:
            return self._logdir
        data = _metrics.chrome_trace()
        data["hostEvents"] = {
            name: {"count": cnt, "total": total, "max": mx}
            for name, (cnt, total, mx) in _host_events.items()}
        data["stepTimes"] = [t for t, _ in self._step_times]
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(data, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        if not self._step_times:
            return "no steps recorded"
        times = [t for t, _ in self._step_times]
        import statistics
        return (f"steps: {len(times)}, mean: {statistics.mean(times) * 1e3:.2f} ms"
                f", p50: {statistics.median(times) * 1e3:.2f} ms, "
                f"min: {min(times) * 1e3:.2f} ms, max: {max(times) * 1e3:.2f} ms")


@contextlib.contextmanager
def profile(*args, **kwargs):
    p = Profiler(*args, **kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()


class ProfilerResult:
    """Parsed host-trace export (`Profiler.export(path)` /
    `observability.export_chrome_trace`): Chrome ``traceEvents`` plus the
    metric snapshot and host-event aggregates that rode along."""

    def __init__(self, data: dict):
        self._data = data

    @property
    def trace_events(self) -> list:
        return self._data.get("traceEvents", [])

    @property
    def metrics(self) -> dict:
        return self._data.get("metrics", {})

    @property
    def host_events(self) -> dict:
        return self._data.get("hostEvents", {})

    @property
    def step_times(self) -> list:
        return self._data.get("stepTimes", [])

    def events(self, name=None) -> list:
        if name is None:
            return self.trace_events
        return [e for e in self.trace_events if e.get("name") == name]

    def durations(self, name) -> list:
        """Durations (seconds) of every span with ``name``."""
        return [e["dur"] / 1e6 for e in self.events(name) if "dur" in e]

    def save(self, path):
        with open(path, "w") as f:
            json.dump(self._data, f)
        return path


def load_profiler_result(path) -> ProfilerResult:
    """Load a host-trace JSON export back into a queryable result.

    Device traces remain XPlane DIRECTORIES for TensorBoard's profile
    plugin; this reads the single-file host trace `Profiler.export(path)`
    writes (Chrome-trace schema + ``metrics``/``hostEvents`` extensions)."""
    if os.path.isdir(path):
        raise ValueError(
            f"{path} is an XPlane trace directory — open it with "
            "TensorBoard's profile plugin; load_profiler_result reads the "
            "host-trace JSON file written by Profiler.export(path)")
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(
            f"{path} is not a host-trace export (no traceEvents key)")
    return ProfilerResult(data)


def _fmt_time(seconds):
    if seconds >= 1.0:
        return f"{seconds:.3f}s"
    if seconds >= 1e-3:
        return f"{seconds * 1e3:.2f}ms"
    return f"{seconds * 1e6:.1f}us"


class SummaryTable:
    """Aggregated host-event statistics (ref `profiler_statistic.py`'s event
    summary tables): one row per RecordEvent name, followed by the process
    metric registry — counter DELTAS over the profiled region plus histogram
    summaries — so one summary() covers the whole stack (compiles, cache
    hits, collective bytes, dataloader latency, decode tokens/s)."""

    def __init__(self, events, step_times, metrics_snapshot=None,
                 counter_base=None):
        self.rows = sorted(
            ((name, cnt, total, total / cnt, mx)
             for name, (cnt, total, mx) in events.items()),
            key=lambda r: -r[2])
        self.step_times = [t for t, _ in step_times]
        snap = metrics_snapshot or {}
        base = counter_base or {}
        self.counter_deltas = {
            name: val - base.get(name, 0)
            for name, val in snap.get("counters", {}).items()
            if val - base.get(name, 0)}
        self.gauges = dict(snap.get("gauges", {}))
        self.histograms = {name: h for name, h in
                           snap.get("histograms", {}).items() if h["count"]}

    def __str__(self):
        lines = []
        if self.step_times:
            ts = self.step_times
            lines.append(
                f"steps: {len(ts)}  avg {_fmt_time(sum(ts) / len(ts))}  "
                f"min {_fmt_time(min(ts))}  max {_fmt_time(max(ts))}")
        if self.rows:
            name_w = max(len("event"), *(len(r[0]) for r in self.rows))
            lines.append(f"{'event'.ljust(name_w)}  {'count':>7}  "
                         f"{'total':>10}  {'avg':>10}  {'max':>10}")
            for name, cnt, total, avg, mx in self.rows:
                lines.append(
                    f"{name.ljust(name_w)}  {cnt:>7}  "
                    f"{_fmt_time(total):>10}  {_fmt_time(avg):>10}  "
                    f"{_fmt_time(mx):>10}")
        if self.counter_deltas:
            lines.append("-- counters (delta over profiled region) --")
            for name in sorted(self.counter_deltas):
                lines.append(f"{name}: +{self.counter_deltas[name]}")
        if self.gauges or self.histograms:
            # gauges/histograms cannot be baselined the way counters can
            # (min/max/percentiles don't subtract) — label them honestly
            lines.append("-- gauges/histograms (process lifetime) --")
            for name in sorted(self.gauges):
                lines.append(f"{name}: {self.gauges[name]}")
            for name in sorted(self.histograms):
                h = self.histograms[name]
                lines.append(
                    f"{name}: n={h['count']} mean={_fmt_time(h['mean'])} "
                    f"p50={_fmt_time(h['p50'])} p99={_fmt_time(h['p99'])} "
                    f"max={_fmt_time(h['max'])}")
        return "\n".join(lines) or "(no host events recorded)"


def _profiler_summary(self, sorted_by=None, op_detail=False, thread_sep=False,
                      time_unit="ms", views=None):
    """Print + return the host-event statistics table
    (ref `paddle.profiler.Profiler.summary`)."""
    table = SummaryTable(dict(_host_events), self._step_times,
                         metrics_snapshot=_metrics.snapshot(),
                         counter_base=getattr(self, "_metrics_base", {}))
    print(table)
    return table


Profiler.summary = _profiler_summary
