"""Sum of ``num`` over sum of ``den`` (two keys of the request records),
over requests whose ``when`` mark fell inside the window, times ``scale``."""
from harness.window import inside


def read(obs, num, den, when, scale=100.0):
    rows = [r for r in obs["records"]
            if inside(r.get(when), obs["t_open"], obs["t_close"])
            and r.get(num) is not None and r.get(den) is not None]
    total = sum(r[den] for r in rows)
    if not rows or total <= 0:
        return None
    return scale * sum(r[num] for r in rows) / total
