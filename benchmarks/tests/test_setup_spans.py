"""The set-up metrics over a recorded start-up: spans written by hand on
three threads, replayed into the program's registry, then ``span_union``
and each of the seven ``setup_*`` metric files checked by hand; and a
rehearsal of every tiny cell reads all of them (0.0 is a reading)."""
import collections
import json

import pytest

from harness import spec
from paddle_tpu.observability import metrics

T = 2000.0                      # the window opens here, on the ring's clock
OBS = {"t_open": T, "t_close": T + 40.0}
SETUP = ("setup_xla_compile_s", "setup_xla_compile_miss_s",
         "setup_xla_trace_s", "setup_engine_init_s", "setup_ramp_s",
         "setup_import_s", "setup_spanned_s")
MAIN, ENGINE, CLIENT = 1, 2, 3   # thread ids

# (name, start, duration, args, thread)
RECORDED = [
    ("package.import", T - 50.0, 0.8, {"jax_preloaded": True}, MAIN),
    # an eager op's compile while the harness builds the model: under no span
    ("xla.compile", T - 48.0, 0.5, {"cache": "hit"}, MAIN),
    ("engine.init", T - 40.0, 3.0, None, MAIN),
    ("engine.load_params", T - 39.9, 1.0, {"leaves": 108}, MAIN),
    ("xla.compile", T - 39.5, 0.25, {"cache": "miss"}, MAIN),
    ("engine.cache_alloc", T - 38.5, 1.5, {"bytes": 1 << 30}, MAIN),
    ("engine.warmup", T - 36.0, 20.0, {"compiled": 2}, MAIN),
    # decode: an outer trace with a nested jit's trace inside it, the
    # lowering, a cache hit
    ("engine.compile:decode", T - 36.0, 12.0, None, MAIN),
    ("xla.trace", T - 36.0, 8.0, {"fun_name": "program"}, MAIN),
    ("xla.trace", T - 34.0, 1.0, {"fun_name": "_where"}, MAIN),
    ("xla.lower", T - 28.0, 2.0, {"fun_name": "jit(program)"}, MAIN),
    ("xla.compile", T - 26.0, 2.0, {"cache": "hit"}, MAIN),
    # the chunk program misses the cache
    ("engine.compile:prefill_chunk", T - 24.0, 8.0, None, MAIN),
    ("xla.trace", T - 24.0, 1.0, {"fun_name": "program"}, MAIN),
    ("xla.lower", T - 23.0, 1.0, {"fun_name": "jit(program)"}, MAIN),
    ("xla.compile", T - 22.0, 6.0, {"cache": "miss"}, MAIN),
    # the ramp: the engine's thread steps while a client's request is open
    # on another; the request and one step reach into the window
    ("serve.request", T - 6.0, 9.0, {"request_id": "req-1"}, CLIENT),
    ("engine.step", T - 5.0, 2.0, {"step_seq": 1}, ENGINE),
    ("engine.dispatch", T - 4.9, 1.5, {"active": 1, "first": True}, ENGINE),
    # a compile on the engine's thread while MAIN is idle: beside, not under
    ("xla.compile", T - 4.8, 1.0, {"cache": "hit"}, ENGINE),
    ("engine.step", T - 2.0, 1.0, {"step_seq": 2}, ENGINE),
    ("engine.step", T - 0.5, 1.5, {"step_seq": 3}, ENGINE),
    # the window
    ("engine.step", T + 1.0, 0.5, {"step_seq": 4}, ENGINE),
    ("xla.compile", T + 2.0, 0.5, {"cache": "miss"}, ENGINE),
]


@pytest.fixture
def recorded(monkeypatch):
    from paddle_tpu import observability as O
    ring = collections.deque(maxlen=O._MAX_SPANS)
    for i, (name, t0, dur, args, tid) in enumerate(RECORDED):
        ring.append((name, "rec", (t0 - O._EPOCH) * 1e6, dur * 1e6, tid,
                     args, i + 1, None))
    monkeypatch.setattr(metrics, "_spans", ring)
    before = metrics.spans_dropped.value
    yield ring
    metrics.spans_dropped.inc(before - metrics.spans_dropped.value)


def _read(metric, obs=OBS):
    m = spec.layer_metric(metric)
    params = {k: v for k, v in m.items() if k not in ("reader", "doc")}
    return spec.reader(m["reader"]).read(dict(obs), **params)


def _union(obs=OBS, **params):
    return spec.reader("span_union").read(dict(obs), **params)


@pytest.mark.parametrize("metric,want", [
    # 0.5 + 0.25 + 2 + 6 on MAIN and 1 on the engine's thread, none of
    # them overlapping; the compile inside the window is not set-up
    ("setup_xla_compile_s", 9.75),
    ("setup_xla_compile_miss_s", 6.25),
    # [-36, -28] holds the nested trace; then [-28, -26], [-24, -22]
    ("setup_xla_trace_s", 12.0),
    ("setup_engine_init_s", 3.0),
    # [-5, -3], [-2, -1], and [-0.5, 0] of the step that crosses the edge
    ("setup_ramp_s", 3.5),
    ("setup_import_s", 0.8),
    # 0.8 + 0.5 + [-40, -37] + [-36, -16] + the request's [-6, 0], which
    # holds the engine's steps and the compile beside them
    ("setup_spanned_s", 30.3),
])
def test_metric_over_the_recorded_start_up(recorded, metric, want):
    assert _read(metric) == pytest.approx(want, rel=1e-9)


def test_union_by_hand_nested_overlapping_and_across_threads(recorded):
    # nested: the inner trace adds nothing to the outer one
    assert _union(name="xla.trace", before_window=True) == \
        pytest.approx(8.0 + 1.0)
    # a sum would count the nested second twice
    total = spec.reader("span_sum").read(
        dict(OBS), name="xla.trace", before_window=True)
    assert total == pytest.approx(10.0)
    # overlapping across threads: the request [-6, 0] (cut at the window)
    # and the steps inside it
    assert _union(prefix=["serve.", "engine.step"], before_window=True) == \
        pytest.approx(6.0)
    # args select; a list of prefixes is any of them
    assert _union(name="xla.compile", where={"cache": "hit"},
                  before_window=True) == pytest.approx(3.5)
    assert _union(prefix=["engine.load_params", "engine.cache_alloc"],
                  before_window=True) == pytest.approx(2.5)
    # the window's side: spans that began inside it, cut at its close
    assert _union(name="engine.step") == pytest.approx(0.5)
    assert _union(name="xla.compile") == pytest.approx(0.5)
    assert _union(name="engine.step", scale=1e3) == pytest.approx(500.0)
    short = dict(OBS, t_close=T + 1.25)
    assert _union(short, name="engine.step") == pytest.approx(0.25)


def test_no_matching_span_is_zero(recorded):
    assert _union(name="jit.first_dispatch:step", before_window=True) == 0.0
    assert _union(name="xla.compile", where={"cache": "off"},
                  before_window=True) == 0.0
    early = {"t_open": T - 100.0, "t_close": T - 99.0}
    assert _union(early, before_window=True) == 0.0
    recorded.clear()
    for metric in SETUP:
        assert _read(metric) == 0.0, metric


def test_a_ring_that_lost_spans_is_nothing(recorded):
    recorded.popleft()
    metrics.spans_dropped.inc()
    for metric in SETUP:
        assert _read(metric) is None, metric
    # the window's side is sound while the oldest span left ended before it
    assert _union(name="engine.step") == pytest.approx(0.5)


def test_a_program_without_the_public_read_is_nothing(monkeypatch):
    from paddle_tpu.observability import MetricsRegistry
    monkeypatch.delattr(MetricsRegistry, "spans")
    for metric in SETUP:
        assert _read(metric) is None, metric


def test_the_seven_are_listed_with_their_cells_and_move_setup_s():
    bm = spec.benchmark()
    cells = [w["name"] for w in bm["workloads"]]
    serving = [c for c in cells if "-serve-" in c]
    listed = {m["name"]: m for m in bm["per_layer"]}
    assert [m["name"] for m in bm["per_layer"]][-7:] == list(SETUP)
    for name in SETUP:
        m = listed[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("s", "lower", "program_span", "setup_s")
        want = serving if name in ("setup_engine_init_s",
                                   "setup_ramp_s") else cells
        assert m["workloads"] == want
        f = spec.layer_metric(name)
        assert f["reader"] == "span_union" and f["before_window"] is True
        assert f["doc"]
    assert len(json.dumps(bm)) < 64 * 1024


@pytest.mark.parametrize("module,cell", [
    ("tiny", "gpt2s-train-b16s1024"),
    ("tiny", "gpt2m-serve-decode"),
    ("tiny_hybrid", "phi4flash-serve-reason"),
    ("tiny_granite", "granite4h-serve-agent"),
    ("tiny_brumby", "brumby14b-serve-longform"),
])
def test_a_traced_rehearsal_reads_every_set_up_metric_the_cell_lists(
        module, cell):
    """A rehearsal never calls ``compile_cache.enable()``: the listeners
    are there because the engine and ``to_static`` register them, and
    without a persistent cache every compile says ``cache=off``."""
    import importlib
    listed = {m["name"] for m in spec.cell(cell)["per_layer"]}
    want = listed & set(SETUP)
    assert len(want) == (5 if "train" in cell else 7)
    metrics.reset()
    out = importlib.import_module(module).rehearse(
        cell, seed=3800000031, seconds=1.0, trace=True)
    assert want <= set(out["metrics_read"])
    compiles = metrics.spans(name="xla.compile")
    assert compiles and {s.args["cache"] for s in compiles} == {"off"}
