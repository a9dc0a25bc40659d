"""How far a decode step of the ``kimi_k2`` family is from the chip's
peaks: the step's floor (``harness/kimi_bytes.py::decode_step_floor``:
every weight outside the routed experts once with the head's table, the
experts its tokens hit as the program counted them, and the latent walk's
floor, the larger of its operations at the bf16 peak and the bytes of the
DISTINCT rows the live sequences reach at the memory's rate) over the
median ``engine.step`` span, in %. Computed from the configuration, the
requests' marks and the program's counters over the window, from no op of
the trace. A step that also carries a tail's chunk holds its time in the
span and not in the floor: ``prefill_launch_share`` says how many steps
were such. The parts are left under ``obs["notes"]`` for the run's
``readers`` line."""
from harness import kimi_bytes
from harness.window import counter_delta, percentile


def read(obs):
    steps = obs.get("engine_steps") or []
    cfg = obs["config"]
    try:
        kimi_bytes.sizes(cfg)
    except KeyError:             # a configuration of another family
        return None
    lv = kimi_bytes.live_rows(obs.get("records") or [], obs["t_open"],
                              obs["t_close"])
    n_steps, hit, pairs = (
        counter_delta(obs["counters_open"], obs["counters_close"], name)
        for name in ("engine.steps", "engine.moe.experts_hit.decode",
                     "engine.latent.pairs.decode"))
    if not steps or not lv["rows"] or not n_steps or not pairs:
        return None
    step_s = percentile(steps, 50)
    note = {"live": lv, "step_p50_ms": 1e3 * step_s,
            "experts_hit_a_step": hit / n_steps,
            "pairs_a_step": pairs / n_steps}
    obs.setdefault("notes", {})["kimi_decode_roofline"] = note
    if obs.get("device_kind") is None:
        return None
    parts = kimi_bytes.decode_step_floor(cfg, lv, hit / n_steps,
                                         pairs / n_steps, obs["device_kind"])
    note.update(parts, floor_ms=1e3 * parts["floor_s"])
    return 100.0 * parts["floor_s"] / step_s
