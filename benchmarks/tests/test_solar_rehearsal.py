"""The ``solaropen2-serve-docqa`` cell's runner end to end at a tiny size
on the CPU: seeded weights, the engine through the model seam with twin K
and V pools and the linear layers' recurrent state, the counts, the wire,
the closed loop and the walk of the plain reference with the same share;
controls that the comparison reads far from the program; and a served path
whose tokens are altered, which the comparison has to see."""
import pytest

import tiny_solar

CELL = tiny_solar.CELL


def test_rehearsal_serves_and_agrees_with_the_reference():
    out = tiny_solar.rehearse(CELL, seed=4000000021, seconds=1.0)
    assert out["rehearsal"] and out["correct"] is False    # no device metric
    assert out["checks_correct"] and out["failed"] == 0
    assert out["attempted"] > 4
    assert set(out["metrics_read"]) == {"tpot_p50_ms", "setup_s"}
    gap = next(c for c in out["checks"] if c["name"] == "served_token_gap")
    assert gap["value"] <= 1e-4


def test_traced_rehearsal_reads_the_program_side_metrics():
    out = tiny_solar.rehearse(CELL, seed=4000000022, seconds=2.5, trace=True)
    assert out["checks_correct"]
    for name in ("step_p50_ms", "moe_held_route_share", "compiles_in_window",
                 "setup_compile_s", "prefill_launch_share",
                 "prefill_step_share", "token_gap_p99_ms"):
        assert name in out["metrics_read"], name
    # a share of a peak is a device metric: none from a CPU run
    assert not [n for n in out["metrics_read"] if "roofline" in n]


@pytest.mark.parametrize("control", ["fp8", "scalar_decay", "beta_half",
                                     "drop_state", "drop_handover"])
def test_a_control_reads_far_from_the_program(control):
    """``control=`` names a lower precision of the reference (fp8) or one
    of its wrong models, each undoing what this family brings (a scalar
    decay a head; ``beta`` without its factor 2; the carried state
    forgotten at the prompt's last chunk boundary, or before the prompt's
    last token): the token it puts first lies far below the reference's
    best where the program's lies on it."""
    out = tiny_solar.rehearse(CELL, seed=4000000024, seconds=1.0,
                              control=control)
    assert out["checks_correct"]
    assert out["control"]["served_token_gap"] > 0.01


def test_altered_tokens_come_out_not_correct(monkeypatch):
    """As ``test_broken_path.py`` does for GPT-2: every token the engine
    harvests is moved to the next id where it is produced (the counts
    behind the tokens with them); answers keep their shape, and ``correct``
    comes out false."""
    import numpy as np
    from paddle_tpu.inference import engine as E
    real = E.DecodeEngine._harvest_one

    def broken(self):
        toks_dev, snapshot, t0 = self._inflight[0]
        self._inflight[0] = ((np.asarray(toks_dev) + 1) % 160, snapshot, t0)
        return real(self)

    monkeypatch.setattr(E.DecodeEngine, "_harvest_one", broken)
    out = tiny_solar.rehearse(CELL, seed=4000000023, seconds=1.0)
    assert out["failed"] == 0 and not out["checks_correct"]
