"""Process-wide runtime telemetry: the metrics registry every layer reports to.

The reference ships per-subsystem introspection (profiler CUPTI tables, the
`flops` API, DataLoader worker logs); this build centralizes it: one
thread-safe, zero-dependency registry of counters / gauges / histograms that
the hot layers (jit capture, collectives, pipeline engines, DataLoader,
inference serving, decode) write into, and that `paddle.profiler`, the hapi
VisualDL callback, `bench.py`, and the serve stats endpoint all read from.

Design:
- **Counter** — monotonically increasing float/int (`inc`).
- **Gauge** — last-write-wins scalar (`set`).
- **Histogram** — count/sum/min/max plus a bounded reservoir of recent
  observations for p50/p99.
- **Span** — ``metrics.span(name, cat=..., **args)``: the ONE way a host
  range is recorded. A context manager that lands on the registry's span
  ring (start, duration, thread, args, its own id and the id of the span
  open on the same thread when it began) AND, while a profiler session
  runs, is a ``jax.profiler.TraceAnnotation`` of the same name for its whole
  life, so the session shows it on the device trace's clock. ``timer()`` is
  a span that also observes a histogram; ``add_span()`` records a range
  whose start lies in the past (ring only); ``spans()`` is the public read.
- Metrics are keyed by ``(name, sorted(labels))``; the flat snapshot key is
  ``name{k=v,...}`` (Prometheus-style).
- ``snapshot()`` → plain dict (JSON-ready); ``to_json()`` serializes it;
  ``chrome_trace()`` / ``export_chrome_trace(path)`` emit the recorded spans
  in Chrome ``traceEvents`` format (load with `chrome://tracing`, Perfetto,
  or `paddle.profiler.load_profiler_result`).

Everything here is stdlib-only ON PURPOSE: instrumented modules import this
at module scope, so it must never create an import cycle or pull in jax.
(A span looks jax's annotation class up in ``sys.modules`` once somebody
else has imported jax; before that, and where jax is absent, it is ring
only.)

Semantics note for in-graph instrumentation: counters incremented inside a
jax trace (e.g. `collective.bytes` for the lax.psum path) count **trace-time
insertions**, not device executions — one per compiled program, not one per
step. Eager-path counters count real calls. `docs/OBSERVABILITY.md` carries
the full metric inventory.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import NamedTuple

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "metrics",
    "counter", "gauge", "histogram", "timer", "snapshot", "reset",
    "chrome_trace", "export_chrome_trace", "to_prometheus",
    "set_node_identity", "node_identity", "spans_for_trace",
    "Span", "SpanRecord", "span", "spans", "add_span", "open_span",
]

# perf_counter origin for span timestamps — one epoch per process so spans
# from every subsystem land on a shared timeline
_EPOCH = time.perf_counter()
# wall clock at the same instant: per-trace span exports are rebased onto
# unix time so the fleet collector can stitch spans from MANY processes
# (each with its own perf_counter origin) onto one timeline
_EPOCH_UNIX_US = time.time() * 1e6

_RESERVOIR = 512       # recent observations kept per histogram (percentiles)
# bounded span ring: old spans drop (counted: metrics.spans_dropped), the
# process never grows. Sized for a benchmark window on top of set-up: 550
# engine steps a second x 6 spans a step x 45 s (ramp + window) = 148,500
# (the GPT-2 decode cell wrote 47,000 at 135 steps a second and writes
# 100,000 at the 300 it runs since PR 28), plus six spans a request and
# set-up's few hundred; about 60 MB when full.
_MAX_SPANS = 262144
_MAX_TRACES = 64       # per-trace span rings kept (LRU; fleet TRACE_EXPORT)
_MAX_TRACE_SPANS = 256  # spans kept per traced request
_MAX_LABELED_SERIES = 256  # LRU cap on LABELED series (membership churn)


def _pct_index(n: int, q: float) -> int:
    """Clamped nearest-rank reservoir index for the q-th percentile of n
    sorted values — the ONE place the index math lives, so
    ``Histogram.percentile`` and ``summary()`` can never drift."""
    return min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))


def _labelkey(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _flatname(name: str, labelkey: tuple) -> str:
    if not labelkey:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labelkey)
    return f"{name}{{{inner}}}"


class Counter:
    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n=1):
        with self._lock:
            self._value += n

    def reset(self):
        with self._lock:
            self._value = 0

    @property
    def value(self):
        return self._value


class Gauge:
    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def set(self, v):
        with self._lock:
            self._value = v

    def inc(self, n=1):
        with self._lock:
            self._value += n

    def dec(self, n=1):
        with self._lock:
            self._value -= n

    def reset(self):
        with self._lock:
            self._value = 0

    @property
    def value(self):
        return self._value


class Histogram:
    """count/sum/min/max + bounded reservoir of the most recent observations
    (enough for p50/p99 on step-time-scale series without unbounded memory)."""

    __slots__ = ("_lock", "count", "total", "min", "max", "_recent")

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._recent = collections.deque(maxlen=_RESERVOIR)

    def observe(self, v):
        v = float(v)
        with self._lock:
            self.count += 1
            self.total += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)
            self._recent.append(v)

    def reset(self):
        with self._lock:
            self.count = 0
            self.total = 0.0
            self.min = self.max = None
            self._recent.clear()

    def percentile(self, q):
        with self._lock:
            vals = sorted(self._recent)
        if not vals:
            return None
        return vals[_pct_index(len(vals), q)]

    def summary(self):
        with self._lock:
            vals = sorted(self._recent)
            count, total, mn, mx = self.count, self.total, self.min, self.max
        out = {"count": count, "total": total, "min": mn, "max": mx,
               "mean": (total / count) if count else None}
        if vals:
            out["p50"] = vals[_pct_index(len(vals), 50.0)]
            out["p99"] = vals[_pct_index(len(vals), 99.0)]
        else:
            out["p50"] = out["p99"] = None
        return out


class SpanRecord(NamedTuple):
    """One recorded span as :meth:`MetricsRegistry.spans` returns it:
    ``t0`` and ``dur`` in ``time.perf_counter`` seconds, ``id`` unique in
    the process, ``parent`` the id of the enclosing span (None at a root)."""
    name: str
    cat: str
    t0: float
    dur: float
    tid: int
    args: dict | None
    id: int
    parent: int | None


_span_ids = itertools.count(1)    # next() is atomic under the GIL
_open = threading.local()         # .stack: this thread's open spans
_TRACE_ANNOTATION = None          # jax.profiler.TraceAnnotation, once found


def _find_annotation():
    """jax's host annotation class, or None while nobody has imported jax
    (or where it is absent): a span is then ring only. Never imports jax
    itself: a process that does no device work pays nothing for it."""
    global _TRACE_ANNOTATION
    jax = sys.modules.get("jax")
    try:
        _TRACE_ANNOTATION = jax.profiler.TraceAnnotation
    except AttributeError:      # not imported, or only half way
        return None
    return _TRACE_ANNOTATION


class Span:
    """Context manager for one host range (``metrics.span``): on exit it
    lands on the registry's ring with its id and its parent's, and while a
    profiler session runs it is, for its whole life, a ``TraceAnnotation``
    of the same name (with no session the annotation is not built: 0.06 us
    to ask against 0.75 us to build, enter and leave one). ``args`` may be
    filled in while it is open (``sp.args["admitted"] = 3``); ``discard()``
    keeps it off the ring (an idle poll). ``t0``/``dur`` are readable after
    exit. ``fleet`` is a traced request's ``(trace_id, parent, span_id)``
    hex context: the span then lands in that trace's ring too."""

    __slots__ = ("_reg", "name", "cat", "args", "id", "parent", "t0", "dur",
                 "_hist", "_fleet", "_ann", "_keep")

    def __init__(self, reg, name, cat="host", args=None, hist=None,
                 fleet=None):
        self._reg = reg
        self.name = name
        self.cat = cat
        self.args = args
        self._hist = hist
        self._fleet = fleet
        self._ann = None
        self._keep = True

    def discard(self):
        self._keep = False

    def __enter__(self):
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        self.parent = stack[-1].id if stack else None
        self.id = next(_span_ids)
        stack.append(self)
        ann = _TRACE_ANNOTATION or _find_annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        stack = getattr(_open, "stack", ())
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:         # ended out of order (RecordEvent.end)
            stack.remove(self)
        if self._hist is not None:
            self._hist.observe(self.dur)
        if self._keep:
            self._reg._record(self.name, self.cat, self.t0, self.dur,
                              self.args or None, self.id, self.parent,
                              self._fleet)
        return False


class MetricsRegistry:
    """Process-wide metric store. Creation is locked; each metric carries its
    own lock, so hot-path updates never contend on the registry lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict = {}
        self._gauges: dict = {}
        self._histograms: dict = {}
        self._spans = collections.deque(maxlen=_MAX_SPANS)
        self._span_lock = threading.Lock()
        # fleet tracing: trace-id-hex -> deque of spans, LRU-evicted so a
        # process pays a bounded footprint no matter how many traced
        # requests pass through (guarded by _span_lock)
        self._trace_spans = collections.OrderedDict()
        # LRU over LABELED series only: (kind, name, labelkey) -> store.
        # Unlabeled series are module-lifetime handles and never evict;
        # labeled ones (replica=..., op=...) churn with fleet membership
        # and must not grow without bound (guarded by _lock).
        self._labeled = collections.OrderedDict()
        self._series_evictions = Counter()
        self._counters[("metrics.series_evictions", ())] = \
            self._series_evictions
        # spans the ring evicted to make room: a reader of an interval
        # that lost spans must say so, not report a smaller number
        self.spans_dropped = Counter()
        self._counters[("metrics.spans_dropped", ())] = self.spans_dropped
        # who this process is in the fleet (role + registry-lease id);
        # stamped by serve/router startup, exported with every trace pull
        self._node = {"role": None, "node_id": None}

    # ------------------------------------------------------------- identity

    def set_node_identity(self, role=None, node_id=None):
        """Record this process's fleet identity (role + replica/router id
        from its registry lease). Rides every TRACE_EXPORT / DEBUG_DUMP
        payload so the collector can label spans by process."""
        if role is not None:
            self._node["role"] = str(role)
        if node_id is not None:
            self._node["node_id"] = str(node_id)

    def node_identity(self) -> dict:
        return {"role": self._node["role"], "node_id": self._node["node_id"],
                "pid": os.getpid()}

    # -------------------------------------------------------------- creation

    def _get(self, store, kind, name, labels, factory):
        key = (name, _labelkey(labels))
        m = store.get(key)
        if m is None:
            with self._lock:
                m = store.get(key)
                if m is None:
                    m = store[key] = factory()
                    if key[1]:
                        self._labeled[(kind,) + key] = store
                        while len(self._labeled) > _MAX_LABELED_SERIES:
                            (_, n2, lk2), st2 = \
                                self._labeled.popitem(last=False)
                            st2.pop((n2, lk2), None)
                            self._series_evictions.inc()
        elif key[1]:
            # labeled hit: refresh recency so ACTIVE replicas' series
            # outlive departed ones (labeled access is request-rate at
            # worst, so the lock here never touches a step-loop hot path)
            with self._lock:
                lru_key = (kind,) + key
                if lru_key in self._labeled:
                    self._labeled.move_to_end(lru_key)
        return m

    def counter(self, name, **labels) -> Counter:
        return self._get(self._counters, "c", name, labels, Counter)

    def gauge(self, name, **labels) -> Gauge:
        return self._get(self._gauges, "g", name, labels, Gauge)

    def histogram(self, name, **labels) -> Histogram:
        return self._get(self._histograms, "h", name, labels, Histogram)

    def timer(self, name, **labels) -> Span:
        """A span that also observes its seconds into the histogram
        ``name{labels}``."""
        return Span(self, _flatname(name, _labelkey(labels)),
                    hist=self.histogram(name, **labels))

    # ----------------------------------------------------------------- spans

    def span(self, name, cat="host", fleet=None, **args) -> Span:
        """``with metrics.span("engine.step", cat="engine", step_seq=7):``
        — see :class:`Span`. A few microseconds an enter and exit with no
        profiler session running (PERF.md, PR 24): once an engine step or
        once a request, never once a token."""
        return Span(self, name, cat, args, fleet=fleet)

    def open_span(self) -> Span | None:
        """The innermost span open on the calling thread, or None: what a
        range reported from a callback passes to :meth:`add_span` as
        ``under`` (framework/compile_cache.py files JAX's compile events
        under the span that caused them)."""
        stack = getattr(_open, "stack", None)
        return stack[-1] if stack else None

    def _record(self, name, cat, t0_perf, dur_s, args, span_id, parent_id,
                fleet=None):
        """The one append to the ring. Entries begin ``(name, cat, ts_us,
        dur_us, tid, args)`` (timestamps in microseconds from the process
        epoch) and end with the span's id and its parent's. ``fleet`` is a
        traced request's ``(trace_id, parent, span_id)`` hex context: its
        copy in that trace's ring ends with the two hex ids instead."""
        entry = (name, cat, (t0_perf - _EPOCH) * 1e6, dur_s * 1e6,
                 threading.get_ident(), args, span_id, parent_id)
        with self._span_lock:
            if len(self._spans) == self._spans.maxlen:
                self.spans_dropped.inc()
            self._spans.append(entry)
            if fleet is not None:
                trace_id = fleet[0]
                ring = self._trace_spans.get(trace_id)
                if ring is None:
                    ring = self._trace_spans[trace_id] = \
                        collections.deque(maxlen=_MAX_TRACE_SPANS)
                    while len(self._trace_spans) > _MAX_TRACES:
                        self._trace_spans.popitem(last=False)
                else:
                    self._trace_spans.move_to_end(trace_id)
                ring.append(entry[:6] + tuple(fleet[1:]))

    def add_span(self, name, t0_perf, dur_s, cat="host", args=None,
                 trace_id=None, parent=None, span_id=None, under=None):
        """Record one completed host-side range whose START LIES IN THE
        PAST (a phase closed by a mark, a reply measured from a stamp):
        ring only, since no annotation can begin in the past. A range that
        a ``with`` can bracket uses :meth:`span`. ``t0_perf`` is a
        time.perf_counter() value. ``args`` (a small dict, e.g.
        ``{"request_id": "req-7"}``) lands on the Chrome-trace event's
        ``args`` field so Perfetto can group/filter spans by request.
        ``under`` is the open :class:`Span` this range belongs to (its
        parent on the ring).

        When ``trace_id`` (hex string) is given the span ALSO lands in that
        trace's bounded ring for the fleet collector (TRACE_EXPORT);
        ``parent``/``span_id`` are the upstream hop's span id and this
        process's own (hex)."""
        self._record(name, cat, t0_perf, dur_s, args, next(_span_ids),
                     under.id if under is not None else None,
                     None if trace_id is None
                     else (trace_id, parent, span_id))

    def spans(self, name=None, prefix=None, since=None, until=None) -> list:
        """The public read of the ring: :class:`SpanRecord` tuples, oldest
        first, of the spans that BEGAN in ``[since, until)``
        (``time.perf_counter`` seconds; None leaves that side open), named
        ``name`` or starting with ``prefix`` (a string or a tuple of
        them). Whether the ring still holds all of an interval:
        ``metrics.spans_dropped``."""
        with self._span_lock:
            raw = list(self._spans)
        # the edges in the ring's own unit, through the arithmetic that
        # stored a start: an edge given as a span's own start then always
        # holds it (back through seconds, the round trip can land an ulp
        # under it)
        lo = None if since is None else (since - _EPOCH) * 1e6
        hi = None if until is None else (until - _EPOCH) * 1e6
        out = []
        for n, cat, ts, dur, tid, args, sid, pid in raw:
            if name is not None and n != name:
                continue
            if prefix is not None and not n.startswith(prefix):
                continue
            if (lo is not None and ts < lo) or (hi is not None and ts >= hi):
                continue
            out.append(SpanRecord(n, cat, _EPOCH + ts * 1e-6, dur * 1e-6,
                                  tid, args, sid, pid))
        return out

    def spans_for_trace(self, trace_id) -> list:
        """Chrome-trace events recorded under ``trace_id`` (hex string) by
        THIS process. Timestamps are unix-epoch microseconds (wall-rebased),
        so the fleet collector can merge exports from many processes onto
        one timeline without knowing their perf_counter origins."""
        with self._span_lock:
            ring = self._trace_spans.get(trace_id)
            spans = list(ring) if ring is not None else []
        events = []
        for name, cat, ts, dur, tid, args, parent, span_id in spans:
            a = dict(args) if args else {}
            a["trace_id"] = trace_id
            if parent is not None:
                a["parent"] = parent
            if span_id is not None:
                a["span"] = span_id
            events.append({"name": name, "cat": cat, "ph": "X",
                           "pid": os.getpid(), "tid": tid,
                           "ts": round(ts + _EPOCH_UNIX_US, 3),
                           "dur": round(dur, 3), "args": a})
        return events

    # --------------------------------------------------------------- exports

    def snapshot(self) -> dict:
        """Flat JSON-ready dict of everything the process has recorded."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._histograms)
        return {
            "counters": {_flatname(n, lk): c.value
                         for (n, lk), c in counters.items()},
            "gauges": {_flatname(n, lk): g.value
                       for (n, lk), g in gauges.items()},
            "histograms": {_flatname(n, lk): h.summary()
                           for (n, lk), h in hists.items()},
        }

    def to_json(self, indent=None) -> str:
        return json.dumps(self.snapshot(), indent=indent)

    def chrome_trace(self) -> dict:
        """Spans in Chrome ``traceEvents`` format plus the metric snapshot
        under the top-level ``metrics`` key (round-trips through
        `paddle.profiler.load_profiler_result`)."""
        with self._span_lock:
            spans = list(self._spans)
        events = []
        for name, cat, ts, dur, tid, args, sid, pid in spans:
            ev = {"name": name, "cat": cat, "ph": "X", "pid": os.getpid(),
                  "tid": tid, "ts": round(ts, 3), "dur": round(dur, 3),
                  "span_id": sid}
            if pid is not None:
                ev["parent_id"] = pid
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "metrics": self.snapshot()}

    def to_prometheus(self) -> str:
        """Zero-dependency Prometheus text exposition (format 0.0.4) of
        every counter/gauge/histogram — histograms render as summaries
        (p50/p99 quantiles + _sum/_count). Standard scrapers consume this
        via the serve PROMETHEUS wire op or the stdlib http exporter
        (`observability/prometheus.py`)."""
        from paddle_tpu.observability.prometheus import render
        return render(self)

    def export_chrome_trace(self, path) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def reset(self):
        """Zero every metric IN PLACE and drop the spans (tests / bench rung
        isolation). Metrics are zeroed rather than dropped because the
        instrumented modules cache their handles at import time — dropping
        entries would orphan those handles and silently lose their counts."""
        with self._lock:
            stores = (list(self._counters.values()),
                      list(self._gauges.values()),
                      list(self._histograms.values()))
        for store in stores:
            for m in store:
                m.reset()
        with self._span_lock:
            self._spans.clear()
            self._trace_spans.clear()


# the process-wide default registry every instrumented layer reports to
metrics = MetricsRegistry()

# module-level conveniences bound to the default registry
counter = metrics.counter
gauge = metrics.gauge
histogram = metrics.histogram
timer = metrics.timer
snapshot = metrics.snapshot
reset = metrics.reset
chrome_trace = metrics.chrome_trace
export_chrome_trace = metrics.export_chrome_trace
to_prometheus = metrics.to_prometheus
set_node_identity = metrics.set_node_identity
node_identity = metrics.node_identity
spans_for_trace = metrics.spans_for_trace
span = metrics.span
spans = metrics.spans
add_span = metrics.add_span
open_span = metrics.open_span
