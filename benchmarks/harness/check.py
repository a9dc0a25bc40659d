"""The comparison that decides ``correct``: each number beside its limit.

Every number compared is printed in every run as one JSON line
``{"check": name, "value": v, "limit": l, "ok": bool}``, once more under
``checks``, the last key of the result's line, and as the run's last lines
on standard error. Limits live in the
configuration file under ``limits`` (set from readings on the chip, which
PERF.md lists), one per number; ``correct`` is true when every number is
within its limit and nothing failed.
"""
from __future__ import annotations

import json
import statistics


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Largest, over leaves, of |program norm - reference norm| measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero). Both arguments
    map leaf name -> norm. Returns (gap, leaf)."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor)
        if not gap <= worst:           # NaN counts as worst
            worst, where = gap, name
    return float(worst), where


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


class Checks:
    def __init__(self, limits: dict):
        self.limits = limits
        self.rows = []

    def add(self, name: str, value: float, limit_key: str | None = None,
            note: str = ""):
        limit = self.limits[limit_key or name]
        ok = bool(value <= limit)      # NaN is not ok
        row = {"check": name, "value": float(value), "limit": float(limit),
               "ok": ok}
        if note:
            row["note"] = note
        self.rows.append(row)
        print(json.dumps(row), flush=True)
        return ok

    def fail(self, name: str, why: str):
        row = {"check": name, "value": None, "limit": None, "ok": False,
               "note": why}
        self.rows.append(row)
        print(json.dumps(row), flush=True)

    def summary(self) -> list:
        """Each number compared beside its limit, for the result's line and
        the run's last lines on standard error."""
        return [{"name": r["check"], "value": r["value"], "limit": r["limit"],
                 "ok": r["ok"]} for r in self.rows]

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)
