"""How far a decode step of the ``solar_open2`` family is from the chip's
memory: the time the bytes a step must move (``harness/solar_bytes.py``:
the experts its tokens hit, as the program counted them, every other weight
once with the head's table, the linear layers' state of every live sequence
read and written, the K and V rows of the live tokens) would take at the
published HBM rate, over the median ``engine.step`` span, in %: the share
of the WHOLE step's peak that the cell reaches. Computed from the
configuration, the requests' marks and the program's counters over the
window, from no op of the trace, so it reads whatever kernels the program
runs. A step that also carries a prefill chunk holds its time in the span
and not in the bytes: ``prefill_launch_share`` says how many steps were
such. The parts are left under ``obs["notes"]`` for the run's ``readers``
line.

``scopes`` is not read here: the metric file lists the scopes that no
kernel share of the cell asks for (the convolutions, a chunk's grouped-query
walk), so that the traced run's ``trace.scopes`` note holds their seconds
too and the cell's breakdown by scope is whole."""
from harness import device, solar_bytes
from harness.window import counter_delta, percentile


def read(obs, scopes=()):
    del scopes
    steps = obs.get("engine_steps") or []
    cfg = obs["config"]
    try:
        solar_bytes.sizes(cfg)
    except KeyError:             # a configuration of another family
        return None
    lv = solar_bytes.live(obs.get("records") or [], obs["t_open"],
                          obs["t_close"])
    n_steps, hit = (counter_delta(obs["counters_open"],
                                  obs["counters_close"], name)
                    for name in ("engine.steps",
                                 "engine.moe.experts_hit.decode"))
    if not steps or not lv["tokens"] or not n_steps or not hit:
        return None
    parts = solar_bytes.decode_step_bytes(cfg, lv, hit / n_steps)
    step_s = percentile(steps, 50)
    note = dict(parts, live=lv, step_p50_ms=1e3 * step_s,
                experts_hit_a_step=hit / n_steps)
    obs.setdefault("notes", {})["solar_decode_roofline"] = note
    if obs.get("device_kind") is None:
        return None
    floor_s = parts["total"] / device.peak(obs["device_kind"],
                                           "hbm_bytes_per_s")
    note["floor_ms"] = 1e3 * floor_s
    return 100.0 * floor_s / step_s
