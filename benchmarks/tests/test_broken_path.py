"""Drives a whole run of a serving cell (everything but the look for a
chip) at a tiny size, once sound and once with the timed path broken
underneath: every token the engine harvests from its decode step is moved
to the next id where it is produced. ``correct`` has to come out false."""
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


@pytest.mark.parametrize("cell", sorted(tiny.TINY))
def test_every_metric_of_a_cell_finds_something_to_read(cell):
    """Untraced, a rehearsal reads every end-to-end metric the cell lists;
    traced, every per-layer metric but those that need a device: the
    profiler's device planes, the runtime's memory counter, a published peak."""
    from harness import spec
    listed = spec.cell(cell)
    out = tiny.rehearse(cell, seed=24, seconds=1.0)
    assert out["metrics_read"] == sorted(m["name"]
                                         for m in listed["end_to_end"])
    out = tiny.rehearse(cell, seed=24, seconds=2.5, trace=True)
    needs_device = ("top_op_share", "device_idle_share", "peak_hbm_gb",
                    "hbm_reserved_gb", "mfu")
    want = {m["name"] for m in listed["per_layer"]
            if not m["name"].startswith(needs_device)}
    assert want <= set(out["metrics_read"])


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


def test_train_rehearsal_runs_and_agrees_with_the_reference():
    out = tiny.rehearse("gpt2s-train-b16s1024", seed=22, seconds=0.5)
    assert out["checks_correct"] is True and out["metrics"] == {}
