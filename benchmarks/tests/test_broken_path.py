"""Drives a whole run of a serving cell (everything but the look for a
chip) at a tiny size, once sound and once with the timed path broken
underneath: every token the engine harvests from its decode step is moved
to the next id where it is produced. ``correct`` has to come out false."""
import json

import numpy as np
import pytest

import tiny


def test_sound_run_is_correct_and_names_the_cpu():
    out = tiny.rehearse("gpt2m-serve-decode", seed=21, seconds=1.0)
    assert out["checks_correct"] is True
    # a rehearsal never prints device metrics
    assert out["correct"] is False and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] > 4
    # each number compared beside its limit, under the line's last key
    assert list(out)[-1] == "checks"
    assert {c["name"]: c["limit"] for c in out["checks"]} == {
        "malformed_answers": 0.0, "served_token_gap": 0.034}
    assert all(c["ok"] and c["value"] <= c["limit"] for c in out["checks"])


@pytest.mark.parametrize("cell", sorted(tiny.TINY))
def test_every_metric_of_a_cell_finds_something_to_read(cell, capsys):
    """Untraced, a rehearsal reads every end-to-end metric the cell lists;
    traced, every per-layer metric but those that need a device: the
    profiler's device planes, the runtime's memory counter, a published peak
    (of those, the decode step's roofline share still finds its steps and
    live tokens, and leaves them on the run's ``readers`` line)."""
    from harness import spec
    listed = spec.cell(cell)
    out = tiny.rehearse(cell, seed=24, seconds=1.0)
    assert out["metrics_read"] == sorted(m["name"]
                                         for m in listed["end_to_end"])
    out = tiny.rehearse(cell, seed=24, seconds=2.5, trace=True)
    needs_device = ("top_op_share", "device_idle_share", "peak_hbm_gb",
                    "hbm_reserved_gb", "mfu", "decode_roofline_share")
    want = {m["name"] for m in listed["per_layer"]
            if not m["name"].startswith(needs_device)}
    assert want <= set(out["metrics_read"])
    if any(m["name"] == "decode_roofline_share" for m in listed["per_layer"]):
        notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith('{"note": "readers"')]
        found = notes[-1]["values"]["decode_roofline"]
        assert found["live_tokens"] > 0 and found["step_p50_ms"] > 0
        assert found["W_bytes"] > 0 and "floor_ms" not in found


def test_altered_tokens_come_out_not_correct(monkeypatch):
    from paddle_tpu.inference import engine as E
    real = E.DecodeEngine._harvest_one

    def broken(self):
        toks_dev, snapshot, t0 = self._inflight[0]
        self._inflight[0] = ((np.asarray(toks_dev) + 1) % 500, snapshot, t0)
        return real(self)

    monkeypatch.setattr(E.DecodeEngine, "_harvest_one", broken)
    out = tiny.rehearse("gpt2m-serve-decode", seed=21, seconds=1.0)
    assert out["checks_correct"] is False
    assert out["correct"] is False
    gap = next(c for c in out["checks"] if c["name"] == "served_token_gap")
    assert not gap["ok"] and gap["value"] > gap["limit"]


def test_train_rehearsal_runs_and_agrees_with_the_reference():
    out = tiny.rehearse("gpt2s-train-b16s1024", seed=22, seconds=0.5)
    assert out["checks_correct"] is True and out["metrics"] == {}


def test_the_checks_end_the_result_line_and_standard_error(monkeypatch,
                                                          capsys):
    import run as bench_run
    canned = {"correct": False, "attempted": 3, "failed": 0, "metrics": {},
              "device": {"platform": "cpu"},
              "checks": [{"name": "served_token_gap", "value": 0.05,
                          "limit": 0.034, "ok": False},
                         {"name": "malformed_answers", "value": 0.0,
                          "limit": 0.0, "ok": True}]}
    monkeypatch.setattr(bench_run, "run_cell", lambda *a, **k: dict(canned))
    bench_run.main(["--workload", "gpt2m-serve-decode", "--seed", "1",
                    "--seconds", "1"])
    got = capsys.readouterr()
    last = json.loads(got.out.splitlines()[-1])
    assert list(last)[-1] == "checks" and last == canned
    assert got.err.splitlines()[-2:] == [
        "served_token_gap: 0.05 (limit 0.034) NOT OK",
        "malformed_answers: 0.0 (limit 0.0) ok"]
