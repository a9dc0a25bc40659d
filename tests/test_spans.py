"""The span primitive (`metrics.span`) and where the program places it.

What must hold (docs/OBSERVABILITY.md "Spans"):
- one primitive: a span lands on the registry's ring with its id and the id
  of the span open on the same thread when it began, and is a profiler
  annotation of the same name for its whole life; `spans()` is the public
  read; the ring is bounded and counts what it drops;
- a working engine step is `engine.step` over one `engine.admit`, at most
  one `engine.dispatch`, an `engine.prefill_launch` a prefill program and an
  `engine.harvest` a blocking readback; `engine.prefill_launches` counts
  the launches; idle polls leave nothing;
- over the wire, `serve.reply` (retirement to last byte written) lies under
  its `serve.request`;
- the kernel registry says what each selection cost and where its answer
  came from.
"""
import collections
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import MetricsRegistry, metrics


# ------------------------------------------------------------- the primitive


def test_nested_spans_record_id_and_parent():
    reg = MetricsRegistry()
    with reg.span("outer", cat="t", k=1) as outer:
        with reg.span("first") as first:
            pass
        with reg.span("second") as second:
            with reg.span("leaf") as leaf:
                pass
    got = {s.name: s for s in reg.spans()}
    assert set(got) == {"outer", "first", "second", "leaf"}
    assert got["outer"].parent is None and got["outer"].args == {"k": 1}
    assert got["first"].parent == got["second"].parent == outer.id
    assert got["leaf"].parent == second.id
    assert len({s.id for s in got.values()}) == 4
    assert (got["outer"].id, got["first"].id, got["leaf"].id) == \
        (outer.id, first.id, leaf.id)
    assert got["outer"].cat == "t" and got["first"].cat == "host"
    # children lie inside their parent on one clock
    o = got["outer"]
    for name in ("first", "second", "leaf"):
        c = got[name]
        assert o.t0 <= c.t0 and c.t0 + c.dur <= o.t0 + o.dur


def test_parent_is_per_thread():
    """A span opened on another thread while this one has a span open is a
    root there: the stack of open spans is thread-local."""
    reg = MetricsRegistry()
    seen = {}

    def other():
        with reg.span("other.root") as sp:
            with reg.span("other.child") as ch:
                pass
        seen.update(root=sp, child=ch, tid=threading.get_ident())

    with reg.span("main.root") as main:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with reg.span("main.child") as mc:
            pass
    assert seen["root"].parent is None
    assert seen["child"].parent == seen["root"].id
    assert mc.parent == main.id
    by_name = {s.name: s for s in reg.spans()}
    assert by_name["other.root"].tid == seen["tid"] != by_name["main.root"].tid


def test_span_is_a_profiler_annotation_for_its_whole_life(monkeypatch):
    """Ring and annotation are both entered: the annotation opens before
    the clock starts and closes after it stops, under the span's name."""
    log = []

    class Annotation:
        session = True

        def __init__(self, name):
            self.name = name

        @classmethod
        def is_enabled(cls):
            return cls.session

        def __enter__(self):
            log.append(("enter", self.name, time.perf_counter()))

        def __exit__(self, *exc):
            log.append(("exit", self.name, time.perf_counter()))

    monkeypatch.setattr(obs, "_TRACE_ANNOTATION", Annotation)
    reg = MetricsRegistry()
    with reg.span("a.b"):
        with reg.span("c"):
            pass
    assert [(k, n) for k, n, _ in log] == [
        ("enter", "a.b"), ("enter", "c"), ("exit", "c"), ("exit", "a.b")]
    s = {x.name: x for x in reg.spans()}["a.b"]
    assert log[0][2] <= s.t0 and s.t0 + s.dur <= log[-1][2]
    # with no session running nothing is built: the ring alone
    Annotation.session = False
    del log[:]
    with reg.span("quiet"):
        pass
    assert log == [] and reg.spans(name="quiet")


def test_real_annotation_is_resolved_once_jax_is_imported():
    import jax
    assert obs._find_annotation() is jax.profiler.TraceAnnotation
    assert jax.profiler.TraceAnnotation.is_enabled() is False


def test_without_jax_a_span_is_ring_only(monkeypatch):
    import sys
    monkeypatch.setattr(obs, "_TRACE_ANNOTATION", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    assert obs._find_annotation() is None
    reg = MetricsRegistry()
    with reg.span("no.jax"):
        pass
    assert [s.name for s in reg.spans()] == ["no.jax"]
    assert obs._TRACE_ANNOTATION is None      # looked up again next time


def test_spans_filters_by_name_prefix_and_start():
    reg = MetricsRegistry()
    t = time.perf_counter()
    reg.add_span("engine.step", t + 1.0, 0.5)
    reg.add_span("engine.harvest", t + 1.2, 0.1)
    reg.add_span("kernel.select:paged", t + 2.0, 0.3)
    reg.add_span("jit.capture:f", t + 3.0, 0.3)
    assert [s.name for s in reg.spans(name="engine.step")] == ["engine.step"]
    assert [s.name for s in reg.spans(prefix="engine.")] == \
        ["engine.step", "engine.harvest"]
    assert [s.name for s in reg.spans(prefix=("kernel.select:",
                                              "jit.capture:"))] == \
        ["kernel.select:paged", "jit.capture:f"]
    # a span belongs to the interval in which it BEGAN; [since, until)
    assert [s.name for s in reg.spans(since=t + 1.2, until=t + 3.0)] == \
        ["engine.harvest", "kernel.select:paged"]
    assert reg.spans(since=t + 4.0) == []
    one = reg.spans(name="engine.harvest")[0]
    assert one.t0 == pytest.approx(t + 1.2, abs=1e-6)
    assert one.dur == pytest.approx(0.1, abs=1e-9)


def test_add_span_takes_its_parent_explicitly():
    """A range whose start lies in the past cannot know what was open when
    it began: `under=` says which open span it belongs to."""
    reg = MetricsRegistry()
    with reg.span("serve.request") as sp:
        t_done = time.perf_counter() - 0.25
        reg.add_span("serve.reply", t_done, 0.25, cat="serve", under=sp,
                     args={"request_id": "req-1", "bytes": 64})
        reg.add_span("request.e2e", t_done - 1.0, 1.0, cat="request")
    got = {s.name: s for s in reg.spans()}
    assert got["serve.reply"].parent == sp.id
    assert got["serve.reply"].args == {"request_id": "req-1", "bytes": 64}
    assert got["request.e2e"].parent is None
    assert got["serve.reply"].t0 < got["serve.request"].t0


def test_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    reg = MetricsRegistry()
    monkeypatch.setattr(reg, "_spans", collections.deque(maxlen=4))
    for i in range(4):
        with reg.span(f"s{i}"):
            pass
    assert reg.spans_dropped.value == 0
    for i in range(4, 7):
        with reg.span(f"s{i}"):
            pass
    assert reg.spans_dropped.value == 3
    assert [s.name for s in reg.spans()] == ["s3", "s4", "s5", "s6"]
    assert reg.snapshot()["counters"]["metrics.spans_dropped"] == 3
    reg.reset()
    assert reg.spans_dropped.value == 0 and reg.spans() == []


def test_ring_holds_setup_and_a_window_at_150_steps_a_second():
    # 150 steps/s x 6 spans x (a 40 s window + 5 s of ramp) and set-up
    assert obs._MAX_SPANS >= 150 * 6 * 45 + 5000


def test_ring_holds_a_window_of_100000_spans_on_top_of_setup():
    """What the decode cell writes at 1.5-2x its old step rate (70,000 to
    95,000 spans in a window): a registry's own ring keeps set-up's spans
    and all of such a window, so a span reader that asks for the window
    (`metrics.spans(since=...)`) is told nothing was lost."""
    reg = MetricsRegistry()
    assert reg._spans.maxlen == obs._MAX_SPANS
    for i in range(5000):
        reg.add_span("setup", float(i), 0.5)
    opened = 5000.0
    for i in range(100_000):
        reg.add_span("engine.step", opened + i, 0.5)
    assert reg.spans_dropped.value == 0
    assert len(reg.spans()) == 105_000
    assert reg.spans()[0].name == "setup"
    window = reg.spans(since=opened - 0.25)
    assert len(window) == 100_000 and window[0].name == "engine.step"


def test_discarded_span_leaves_no_entry_but_keeps_its_children_sound():
    reg = MetricsRegistry()
    with reg.span("poll") as sp:
        sp.discard()
    assert reg.spans() == []
    with reg.span("kept"):
        pass
    assert reg.spans()[0].parent is None      # the stack was unwound


def test_timer_is_a_span_with_a_histogram():
    reg = MetricsRegistry()
    with reg.span("outer") as outer:
        with reg.timer("x.op", kind="k") as t:
            pass
    assert reg.snapshot()["histograms"]["x.op{kind=k}"]["count"] == 1
    s = {x.name: x for x in reg.spans()}["x.op{kind=k}"]
    assert s.parent == outer.id and s.dur == t.dur


def test_runner_facing_ring_layout_is_kept():
    """`benchmarks/runners/serve.py` reads `metrics._spans` under
    `_span_lock`: entries begin (name, cat, ts_us, dur_us, ...) on `_EPOCH`,
    new fields at the tail."""
    reg = MetricsRegistry()
    t0 = time.perf_counter()
    with reg.span("engine.step", cat="engine"):
        pass
    with reg._span_lock:
        (entry,) = list(reg._spans)
    name, cat, ts_us, dur_us, *tail = entry
    assert (name, cat) == ("engine.step", "engine")
    assert obs._EPOCH + ts_us * 1e-6 == pytest.approx(t0, abs=0.05)
    assert dur_us >= 0 and len(tail) == 4     # tid, args, id, parent


def test_chrome_trace_carries_ids():
    reg = MetricsRegistry()
    with reg.span("p") as p:
        with reg.span("c", n=1):
            pass
    ev = {e["name"]: e for e in reg.chrome_trace()["traceEvents"]}
    assert ev["c"]["parent_id"] == p.id == ev["p"]["span_id"]
    assert "parent_id" not in ev["p"] and ev["c"]["args"] == {"n": 1}


def test_fleet_context_lands_the_span_in_the_trace_ring_too():
    reg = MetricsRegistry()
    tid = "ab" * 16
    with reg.span("client.generate", cat="client",
                  fleet=(tid, None, "cd" * 8)):
        pass
    (ev,) = reg.spans_for_trace(tid)
    assert ev["name"] == "client.generate"
    assert ev["args"] == {"trace_id": tid, "span": "cd" * 8}
    assert [s.name for s in reg.spans()] == ["client.generate"]


# ------------------------------------------------ where the program puts them


def _tiny_model(seed=7, vocab=97, max_pos=64):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64,
                    max_position_embeddings=max_pos, hidden_dropout=0.0,
                    attention_dropout=0.0)
    return GPTForCausalLM(cfg)


def _children(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s.parent].append(s)
    return out


class TestEngineSpans:

    def _run(self, **ecfg):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        eng = DecodeEngine(_tiny_model(), EngineConfig(
            page_size=4, max_slots=2, min_bucket=8, **ecfg))
        rng = np.random.RandomState(3)
        counters = {k: metrics.counter(k) for k in (
            "engine.prefill_launches", "engine.steps",
            "engine.h2d_transfers", "engine.prefill_chunks")}
        base = {k: c.value for k, c in counters.items()}
        t0 = time.perf_counter()
        # one short prompt (a one-shot program) and one past the chunk size
        reqs = [eng.submit(rng.randint(0, 97, n).astype(np.int32), 5)
                for n in (5, 21)]
        eng.run_until_idle(max_steps=60)
        assert all(r.done and r._error is None for r in reqs)
        # idle polls after the work is done
        for _ in range(3):
            assert eng.step() is False
        delta = {k: c.value - base[k] for k, c in counters.items()}
        return metrics.spans(since=t0), delta

    def test_phases_lie_under_each_working_step(self):
        spans, delta = self._run(prefill_chunk_tokens=8, inflight=1)
        kids = _children(spans)
        steps = [s for s in spans if s.name == "engine.step"]
        assert steps and all(s.parent is None for s in steps)
        seqs = [s.args["step_seq"] for s in steps]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        n_dispatch = 0
        for st in steps:
            names = [c.name for c in kids[st.id]]
            assert names.count("engine.admit") == 1, names
            assert names.count("engine.dispatch") == (
                1 if st.args["active"] else 0), (st.args, names)
            if st.args["active"]:
                # inflight=1: every dispatched step is harvested in place
                decode = [c for c in kids[st.id] if c.name == "engine.harvest"
                          and c.args["of"] == "decode"]
                assert len(decode) == 1
                assert decode[0].args["tokens"] == st.args["active"]
            assert set(names) <= {"engine.admit", "engine.dispatch",
                                  "engine.harvest", "engine.prefill_launch"}
            n_dispatch += names.count("engine.dispatch")
            for c in kids[st.id]:
                assert st.t0 <= c.t0 and c.t0 + c.dur <= st.t0 + st.dur
        assert n_dispatch == delta["engine.steps"]
        # the budget: at most six spans a step that admits nothing (step,
        # admit, dispatch, a chunk's launch, its first-token readback, the
        # decode readback); an admission adds a launch and a readback
        def family(sp):     # without a lazy compile and what it holds
            return [sp] + [d for c in kids[sp.id] for d in family(c)
                           if not c.name.startswith("engine.compile:")]

        for st in steps:
            fam = family(st)
            (adm,) = [s for s in fam if s.name == "engine.admit"]
            assert len(fam) <= 6 + 2 * adm.args["admitted"], \
                [s.name for s in fam]

    def test_prefill_launches_are_counted_where_they_are_made(self):
        spans, delta = self._run(prefill_chunk_tokens=8, inflight=2)
        launches = [s for s in spans if s.name == "engine.prefill_launch"]
        assert len(launches) == delta["engine.prefill_launches"]
        # what prefill_step_share inferred from upload counts
        assert delta["engine.prefill_launches"] == \
            delta["engine.h2d_transfers"] - delta["engine.steps"]
        kinds = collections.Counter(s.args["kind"] for s in launches)
        assert kinds == {"oneshot": 1, "chunk": 3}      # 5; 21 = 8 + 8 + 5
        assert kinds["chunk"] == delta["engine.prefill_chunks"]
        assert sorted(s.args["tokens"] for s in launches) == [5, 5, 8, 8]
        by_id = {s.id: s for s in spans}
        for s in launches:
            parent = by_id[s.parent].name
            # a one-shot runs inside admission, a chunk beside the decode
            assert parent == ("engine.admit" if s.args["kind"] == "oneshot"
                              else "engine.step")
            assert s.args["request_id"].startswith("req-")
        # each prefill ends in one first-token readback
        first = [s for s in spans if s.name == "engine.harvest"
                 and s.args["of"] == "prefill"]
        assert len(first) == 2 and all(s.args["tokens"] == 1 for s in first)

    def test_prefix_tail_is_a_launch_of_its_own_kind(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        eng = DecodeEngine(_tiny_model(), EngineConfig(
            page_size=4, max_slots=1, min_bucket=8, prefix_cache=True))
        rng = np.random.RandomState(5)
        shared = rng.randint(0, 97, 12).astype(np.int32)
        c = metrics.counter("engine.prefill_launches")
        for own, want in ((3, "oneshot"), (5, "tail")):
            base, t0 = c.value, time.perf_counter()
            r = eng.submit(np.concatenate(
                [shared, rng.randint(0, 97, own).astype(np.int32)]), 2)
            eng.run_until_idle(max_steps=20)
            assert r.done and r._error is None
            (launch,) = metrics.spans(name="engine.prefill_launch", since=t0)
            assert launch.args["kind"] == want and c.value - base == 1
        assert launch.args["tokens"] == 5           # the uncached tail alone

    def test_idle_polls_leave_no_span(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        eng = DecodeEngine(_tiny_model(), EngineConfig(
            page_size=4, max_slots=1, min_bucket=8))
        t0 = time.perf_counter()
        for _ in range(5):
            assert eng.step() is False
        assert metrics.spans(prefix="engine.", since=t0) == []

    def test_compile_is_a_span_and_dropped_nothing(self):
        from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
        t0 = time.perf_counter()
        eng = DecodeEngine(_tiny_model(), EngineConfig(
            page_size=4, max_slots=1, min_bucket=8))
        eng.warmup(prompt_lens=(5,))
        names = [s.name for s in metrics.spans(prefix="engine.compile:",
                                               since=t0)]
        assert sorted(names) == ["engine.compile:decode",
                                 "engine.compile:prefill"]
        assert metrics.spans_dropped.value == 0


def test_reply_span_lies_under_its_request_over_loopback():
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.inference.serve import InferenceServer, RemotePredictor
    eng = DecodeEngine(_tiny_model(), EngineConfig(
        page_size=4, max_slots=2, min_bucket=8))
    srv = InferenceServer(None, engine=eng, auth_name="span-test")
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    t0 = time.perf_counter()
    cli = RemotePredictor(port=srv.port, secret="span-test")
    try:
        out = cli.generate(np.arange(5, dtype=np.int32), max_new_tokens=4)
        assert out.shape == (9,)
        cli.ping()
        # the span lands once the reply is written; the client may be back
        # before the connection thread gets there
        deadline = time.perf_counter() + 10
        while not metrics.spans(name="serve.reply", since=t0 - 60) \
                and time.perf_counter() < deadline:
            time.sleep(0.01)
    finally:
        cli.shutdown_server()
        cli.close()
        t.join(timeout=30)
    assert not t.is_alive()
    (reply,) = [s for s in metrics.spans(name="serve.reply")
                if s.t0 >= t0]
    requests = {s.id: s for s in metrics.spans(name="serve.request",
                                               since=t0)}
    req = requests[reply.parent]
    assert reply.args["request_id"].startswith("req-")
    assert reply.args["bytes"] == out.nbytes
    # from retirement (inside the request) to the last byte written
    assert req.t0 < reply.t0 and reply.dur > 0
    assert reply.t0 + reply.dur <= req.t0 + req.dur + 1e-6
    e2e = [s for s in metrics.spans(name="request.e2e", since=t0 - 1)
           if s.args["request_id"] == reply.args["request_id"]]
    assert e2e and e2e[0].t0 + e2e[0].dur == pytest.approx(reply.t0,
                                                           abs=1e-4)


def test_kernel_selection_says_what_it_cost_and_where_it_came_from():
    from paddle_tpu.kernels import registry
    key = ("span-test", "cpu", 1)
    calls = []

    def measure(impl):
        calls.append(impl)
        return {"a": 0.002, "b": 0.001}[impl]

    t0 = time.perf_counter()
    try:
        assert registry.select("paged_attention", key, ["a", "b"],
                               measure) == "b"
        assert registry.select("paged_attention", key, ["a", "b"],
                               measure) == "b"
        assert registry.select("paged_attention", key + (2,), ["a"],
                               measure) == "a"
    finally:
        registry._TABLE.pop(key, None)
        registry._TABLE.pop(key + (2,), None)
    assert calls == ["a", "b"]
    got = metrics.spans(name="kernel.select:paged_attention", since=t0)
    assert [s.args["source"] for s in got] == ["measured", "memory",
                                               "single"]
    assert [s.args["pick"] for s in got] == ["b", "b", "a"]
    assert got[0].args["timings_ms"] == {"a": 2.0, "b": 1.0}
    assert got[0].cat == "kernel" and got[0].parent is None


def test_capture_is_a_span():
    import paddle_tpu.nn as nn
    lin = nn.Linear(4, 2)

    @paddle.jit.to_static
    def tiny_span_fn(x):
        return lin(x).sum()

    t0 = time.perf_counter()
    tiny_span_fn(paddle.randn([3, 4]))
    tiny_span_fn(paddle.randn([3, 4]))           # cached: no second capture
    got = metrics.spans(name="jit.capture:tiny_span_fn", since=t0)
    assert len(got) == 1 and got[0].cat == "compile" and got[0].dur > 0
