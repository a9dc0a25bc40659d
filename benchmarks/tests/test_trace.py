"""The trace reduction on a small recorded trace: 80 ms of
``gpt2m-serve-prefill`` on one TPU v5e (one decode step and one prefill
chunk), cut from the profiler's own xplane by ``harness/trace.py::
load_xplane`` (ops under 20 us and host spans under 100 us dropped, names
clipped), and on a hand-made one for the arithmetic."""
import json
from pathlib import Path

from harness import trace as T

RECORDED = Path(__file__).parent / "data" / "trace_serve_prefill_80ms.json"


def test_recorded_serving_trace():
    red = T.reduce(json.loads(RECORDED.read_text()))
    assert red["devices"] == 1
    # busy is the union of op intervals: under the extent, and nearly all of
    # it (the device was 99% busy in the run this was cut from)
    assert 0.97 * red["window_s"] < red["busy_s"] <= red["window_s"]
    assert abs(red["busy_s"] - 0.0783757) < 1e-6
    top, seconds = red["families"][0]
    # the whole-pool relayout copy is the largest family, about 43% of busy
    assert top == "copy bf16[24,1537,16,16,64]"
    assert 0.40 < seconds / red["busy_s"] < 0.46
    assert red["collective_s"] == 0.0
    assert red["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert len(red["device_ops"]) <= 10


def test_family_names():
    f = T.family
    assert f("%copy.228 = bf16[24,1537,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} "
             "copy(bf16[24,1537,16,16,64]{1,4,3,2,0} %vc.1)") \
        == "copy bf16[24,1537,16,16,64]"
    assert f("%fusion.9 = (f32[16]{0}, bf16[16,8]{1,0}) fusion(f32[4] %a), "
             "kind=kLoop") == "fusion (f32[16], bf16[16,8])"
    assert f("%all-reduce-start.3 = f32[128]{0} all-reduce-start(f32[128] "
             "%x)") == "all-reduce-start f32[128]"
    assert T.is_collective("all-reduce-start f32[128]")
    assert T.is_collective("collective-permute bf16[2,4]")
    assert not T.is_collective("fusion f32[128]")
    assert f("fusion.123") == "fusion"


def _plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": ev}
                                    for n, ev in lines]}


def test_arithmetic_on_a_hand_made_trace():
    ops = [["%a.1 = f32[4]{0} fusion(f32[4] %p)", 0, 100_000],
           ["%b.1 = f32[4]{0} all-reduce(f32[4] %a.1)", 100_000, 50_000],
           # a gap of 250 us here
           ["%a.2 = f32[4]{0} fusion(f32[4] %p)", 400_000, 100_000]]
    asyn = [["%c.1 = f32[8]{0} all-gather-start(f32[4] %p)", 20_000, 60_000]]
    host = [["bench.wait", 140_000, 270_000], ["outer", 0, 600_000]]
    tr = {"planes": [
        _plane("/device:TPU:0", [("XLA Ops", ops), ("Async XLA Ops", asyn)]),
        _plane("/device:TPU:1", [("XLA Ops", ops[:2])]),
        _plane("/host:CPU", [("python3", host)])]}
    red = T.reduce(tr, window_s=0.001)
    assert red["devices"] == 2
    # device 0 busy 250 us, device 1 busy 150 us: the mean
    assert abs(red["busy_s"] - 200e-6) < 1e-12
    assert red["window_s"] == 0.001          # the host's window is longer
    # collectives: device 0 has 50 us sync + 60 us async span, device 1 50
    assert abs(red["collective_s"] - (110e-6 + 50e-6) / 2) < 1e-12
    assert red["families"][0][0] == "fusion f32[4]"
    # the gap goes to the narrowest host span that covers most of it
    assert red["idle_gaps"] == [["bench.wait", 250e-6]]


def test_no_device_plane_gives_nothing():
    assert T.reduce({"planes": [_plane("/host:CPU", [("t", [["x", 0, 5]])])]}) \
        is None
