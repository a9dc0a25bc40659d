"""A share of the traced window, from the reduced device trace:
``top_family`` (largest op family over busy time), ``idle`` (1 - busy over
the window) or ``collective`` (collective ops over the window)."""


def read(obs, what):
    tr = obs.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    if what == "top_family":
        return 100.0 * tr["families"][0][1] / tr["busy_s"]
    if what == "idle":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if what == "collective":
        return 100.0 * tr["collective_s"] / tr["window_s"]
    raise ValueError(what)
