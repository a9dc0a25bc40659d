"""The span readers over a recorded span list: a few engine steps and a
request written by hand, replayed into the program's registry (the only
place the readers look), then each reader's arithmetic checked by hand."""
import collections

import pytest

from harness import spans as S, spec
from paddle_tpu.observability import metrics

T = 1000.0                      # the window opens here, on the ring's clock
OBS = {"t_open": T, "t_close": T + 1.0}

# (name, start, duration, args, key, parent key)
RECORDED = [
    # set-up, before the window
    ("kernel.select:paged_attention", T - 9.0, 2.0,
     {"source": "measured", "pick": "pallas"}, "k1", None),
    ("kernel.select:prefill_attention", T - 7.0, 1.5,
     {"source": "measured", "pick": "xla"}, "k2", None),
    ("kernel.select:paged_attention", T - 5.0, 0.001,
     {"source": "memory", "pick": "pallas"}, "k3", None),
    ("engine.compile:decode", T - 4.0, 3.0, None, "c1", None),
    ("jit.capture:step", T - 0.9, 0.5, None, "c2", None),
    # step 1: admits one request, one-shot prefill inside admission
    ("engine.step", T + 0.000, 0.100, {"step_seq": 1}, "s1", None),
    ("engine.admit", T + 0.001, 0.030, {"admitted": 1, "queued": 0},
     "a1", "s1"),
    ("engine.prefill_launch", T + 0.002, 0.004, {"kind": "oneshot"},
     "l1", "a1"),
    ("engine.harvest", T + 0.010, 0.020, {"of": "prefill", "tokens": 1},
     "h0", "a1"),
    ("engine.dispatch", T + 0.032, 0.003, {"active": 4}, "d1", "s1"),
    ("engine.harvest", T + 0.040, 0.058, {"of": "decode", "tokens": 4},
     "h1", "s1"),
    # step 2: admits nothing
    ("engine.step", T + 0.100, 0.070, {"step_seq": 2}, "s2", None),
    ("engine.admit", T + 0.101, 0.001, {"admitted": 0, "queued": 0},
     "a2", "s2"),
    ("engine.dispatch", T + 0.103, 0.003, {"active": 5}, "d2", "s2"),
    ("engine.harvest", T + 0.108, 0.060, {"of": "decode", "tokens": 5},
     "h2", "s2"),
    # step 3: admits two
    ("engine.step", T + 0.170, 0.200, {"step_seq": 3}, "s3", None),
    ("engine.admit", T + 0.171, 0.090, {"admitted": 2, "queued": 1},
     "a3", "s3"),
    ("engine.dispatch", T + 0.262, 0.003, {"active": 7}, "d3", "s3"),
    ("engine.harvest", T + 0.266, 0.102, {"of": "decode", "tokens": 1},
     "h3", "s3"),
    # two replies inside the window, one after it
    ("serve.reply", T + 0.300, 0.0004, {"request_id": "req-1"}, "r1", None),
    ("serve.reply", T + 0.600, 0.0008, {"request_id": "req-2"}, "r2", None),
    ("serve.reply", T + 1.200, 0.0100, {"request_id": "req-3"}, "r3", None),
]


@pytest.fixture
def recorded(monkeypatch):
    """The recorded list on the registry's ring, ids wired as written."""
    from paddle_tpu import observability as O
    ring = collections.deque(maxlen=O._MAX_SPANS)
    ids = {key: i + 1 for i, (*_, key, _p) in enumerate(RECORDED)}
    for name, t0, dur, args, key, parent in RECORDED:
        ring.append((name, "rec", (t0 - O._EPOCH) * 1e6, dur * 1e6, 1, args,
                     ids[key], ids.get(parent)))
    monkeypatch.setattr(metrics, "_spans", ring)
    before = metrics.spans_dropped.value
    yield ring
    metrics.spans_dropped.inc(before - metrics.spans_dropped.value)


def _read(metric, obs=OBS):
    m = spec.layer_metric(metric)
    params = {k: v for k, v in m.items() if k not in ("reader", "doc")}
    return spec.reader(m["reader"]).read(dict(obs), **params)


@pytest.mark.parametrize("metric,want", [
    # the three decode readbacks end at 0.098, 0.168, 0.368: gaps 70 ms
    # (5 tokens) and 200 ms (1 token); the 99th percentile of 6 tokens' gaps
    ("token_gap_p99_ms", 200.0),
    ("token_gap_p99_ms.prefill", 200.0),
    # admits with admitted > 0: 30 ms and 90 ms
    ("admit_p50_ms.prefill", 60.0),
    # self: 100 - (30 + 3 + 58) = 9; 70 - (1 + 3 + 60) = 6; 200 - (90 + 3 +
    # 102) = 5; a grandchild (the launch under admit) is not taken twice
    ("step_host_self_p50_ms", 6.0),
    ("step_host_self_p50_ms.prefill", 6.0),
    # 20 + 58 + 60 + 102 ms of a 1 s window
    ("harvest_blocked_share", 24.0),
    ("harvest_blocked_share.prefill", 24.0),
    ("reply_p50_ms.closed", 0.6),
    ("reply_p50_ms.open", 0.6),
    # measured selections only, before the window
    ("select_measure_s", 3.5),
    ("setup_compile_s", 3.5),
])
def test_reader_over_the_recorded_list(recorded, metric, want):
    assert _read(metric) == pytest.approx(want, rel=1e-6)


def test_launch_share_reads_the_programs_own_counter():
    obs = {"counters_open": {"engine.prefill_launches": 10,
                             "engine.steps": 100, "engine.h2d_transfers": 110},
           "counters_close": {"engine.prefill_launches": 27,
                              "engine.steps": 183,
                              "engine.h2d_transfers": 210}}
    assert _read("prefill_launch_share", obs) == pytest.approx(17.0)
    assert _read("prefill_step_share", obs) == pytest.approx(17.0)
    # a program without the counter launched no prefill that it counted
    for edge in obs.values():
        del edge["engine.prefill_launches"]
    assert _read("prefill_launch_share.prefill", obs) == 0.0


def test_a_sum_over_no_spans_is_zero_and_a_median_of_none_is_nothing(
        recorded):
    late = {"t_open": T + 50.0, "t_close": T + 51.0}
    assert _read("harvest_blocked_share", late) == 0.0
    assert _read("reply_p50_ms.open", late) is None
    assert _read("token_gap_p99_ms", late) is None
    recorded.clear()
    assert _read("select_measure_s") == 0.0
    assert _read("setup_compile_s") == 0.0


def test_an_interval_that_lost_spans_reads_as_nothing(recorded):
    """The ring evicted spans that ended inside the window: every reader of
    the window, and of set-up before it, says None, not a smaller number.
    An interval wholly after the oldest span left is sound."""
    for _ in range(12):
        recorded.popleft()              # through step 2's admit
        metrics.spans_dropped.inc()
    for metric in ("harvest_blocked_share", "token_gap_p99_ms",
                   "step_host_self_p50_ms", "admit_p50_ms.prefill",
                   "reply_p50_ms.open", "select_measure_s",
                   "setup_compile_s"):
        assert _read(metric) is None, metric
    sound = {"t_open": T + 0.17, "t_close": T + 1.0}
    assert _read("harvest_blocked_share", sound) == pytest.approx(
        100 * 0.102 / 0.83)


def test_a_program_without_the_public_read_reads_as_nothing(monkeypatch):
    """The parent of the PR that brought these readers: no `spans`."""
    from paddle_tpu.observability import MetricsRegistry
    monkeypatch.delattr(MetricsRegistry, "spans")
    for metric in ("harvest_blocked_share", "token_gap_p99_ms.prefill",
                   "step_host_self_p50_ms", "admit_p50_ms.prefill",
                   "reply_p50_ms.closed", "select_measure_s",
                   "setup_compile_s"):
        assert _read(metric) is None, metric


def test_self_time_and_weighted_percentile_by_hand():
    Rec = collections.namedtuple("Rec", "t0 dur")
    parent = Rec(10.0, 1.0)
    # overlapping children and one that runs past the parent's end
    kids = [Rec(10.1, 0.3), Rec(10.3, 0.2), Rec(10.9, 0.5)]
    assert S.self_time(parent, kids) == pytest.approx(1.0 - 0.4 - 0.1)
    assert S.self_time(parent, []) == 1.0
    assert S.weighted_percentile([3.0, 1.0, 2.0], [1, 1, 1], 50) == 2.0
    assert S.weighted_percentile([3.0, 1.0], [1, 99], 99) == 1.0
    assert S.weighted_percentile([3.0, 1.0], [2, 98], 99) == 3.0
    assert S.weighted_percentile([], [], 50) is None
