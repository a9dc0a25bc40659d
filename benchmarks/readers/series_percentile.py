"""A percentile of one of the run's series of durations in seconds
(``engine_steps``: the program's own step spans; ``step_seconds``: the
host clock between completed training steps)."""
from harness.window import percentile


def read(obs, series, q, scale=1000.0):
    vals = obs.get(series) or []
    if not vals:
        return None
    return scale * percentile(vals, q)
