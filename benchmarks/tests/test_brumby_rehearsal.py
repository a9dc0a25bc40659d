"""The ``brumby14b-serve-longform`` cell's runner end to end at a tiny size
on the CPU: seeded weights, the engine through the model seam with no page
pool, chunked prefill into the retention state, the wire, the closed loop
and the walk of the plain reference's attention form; and a served path
whose tokens are altered, which the comparison has to see."""
import tiny_brumby

CELL = tiny_brumby.CELL


def test_rehearsal_serves_and_agrees_with_the_reference():
    out = tiny_brumby.rehearse(CELL, seed=3600000021, seconds=1.0)
    assert out["rehearsal"] and out["correct"] is False    # no device metric
    assert out["checks_correct"] and out["failed"] == 0
    assert out["attempted"] > 4
    assert set(out["metrics_read"]) == {"out_tokens_per_s", "tpot_p50_ms",
                                        "setup_s"}
    gap = next(c for c in out["checks"] if c["name"] == "served_token_gap")
    assert gap["value"] <= 1e-4


def test_traced_rehearsal_reads_the_program_side_metrics():
    """What a traced run reads without a device: the counters and spans
    (no roofline share: those need the chip's peaks and its trace); the
    cell lists neither the pool's fill nor the prefix store."""
    out = tiny_brumby.rehearse(CELL, seed=3600000022, seconds=2.5,
                               trace=True)
    assert out["checks_correct"]
    for name in ("step_p50_ms", "batch_occupancy", "compiles_in_window",
                 "setup_compile_s", "first_token_deferred_share",
                 "prefill_launch_share"):
        assert name in out["metrics_read"], name
    assert not {"pool_fill_share", "prefix_hit_share",
                "window_pages_recycled"} & set(out["metrics_read"])


def test_the_controls_are_read_beside_the_program():
    """``control=`` names a lower precision of the reference (fp8, or the
    state held in bfloat16) whose reading lands beside the program's."""
    for control in ("fp8", "state_bf16"):
        out = tiny_brumby.rehearse(CELL, seed=3600000024, seconds=0.6,
                                   control=control)
        assert out["checks_correct"], control
        assert out["control"]["served_token_gap"] >= 0.0


def test_altered_tokens_come_out_not_correct(monkeypatch):
    """As ``test_broken_path.py`` does for GPT-2: every token the engine
    harvests is moved to the next id where it is produced; answers keep
    their shape, and ``correct`` comes out false."""
    import numpy as np
    from paddle_tpu.inference import engine as E
    real = E.DecodeEngine._harvest_one

    def broken(self):
        toks_dev, snapshot, t0 = self._inflight[0]
        self._inflight[0] = ((np.asarray(toks_dev) + 1) % 160, snapshot, t0)
        return real(self)

    monkeypatch.setattr(E.DecodeEngine, "_harvest_one", broken)
    out = tiny_brumby.rehearse(CELL, seed=3600000023, seconds=1.0)
    assert out["failed"] == 0 and not out["checks_correct"]
