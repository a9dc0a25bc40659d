"""How far a decode step of the ``brumby`` family is from the chip's
memory: the time the bytes a step must move (``harness/brumby_bytes.py``:
every layer's weights and the head once, the retention state of each live
sequence read and written in every layer) would take at the published HBM
rate, over the median ``engine.step`` span, in %. The parts are left under
``obs["notes"]`` for the run's ``readers`` line."""
from harness import brumby_bytes, device
from harness.window import percentile


def read(obs):
    steps = obs.get("engine_steps") or []
    cfg = obs["config"]
    lv = brumby_bytes.live(obs.get("records") or [], obs["t_open"],
                           obs["t_close"])
    if not steps or not lv["sequences"]:
        return None
    parts = brumby_bytes.decode_step_bytes(cfg, lv)
    step_s = percentile(steps, 50)
    note = dict(parts, live=lv, step_p50_ms=1e3 * step_s)
    obs.setdefault("notes", {})["brumby_decode_roofline"] = note
    if obs.get("device_kind") is None:
        return None
    floor_s = parts["total"] / device.peak(obs["device_kind"],
                                           "hbm_bytes_per_s")
    note["floor_ms"] = 1e3 * floor_s
    return 100.0 * floor_s / step_s
