"""Reduction from the profiler's trace to device busy time, op families,
collective time and idle gaps.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict (``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``); ``reduce`` works on that dict alone,
so a small recorded trace kept as JSON (``tests/data``) checks it.

Device planes are those named ``/device:TPU:<n>`` (or GPU); their ``XLA
Ops`` line holds one event per executed HLO op. Busy time is the union of
those intervals per device, averaged over devices. An op's family is its
name without the trailing instance number (``fusion.123`` -> ``fusion``).
An idle gap is a stretch of the first device's timeline with no op; it is
named after the host span (a ``TraceAnnotation`` or a runtime call) that
covers most of it.
"""
from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # start-to-done spans of asynchronous ops


def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str, keep_host=True) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for p in pd.planes:
        is_dev = bool(_DEVICE.match(p.name))
        if not is_dev and not keep_host:
            continue
        lines = []
        for ln in p.lines:
            if is_dev and ln.name not in (OPS_LINE, ASYNC_LINE):
                continue
            ev = [[e.name, float(e.start_ns), float(e.duration_ns)]
                  for e in ln.events]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def family(name: str) -> str:
    """An op's family: its HLO opcode and result shape without the layout,
    ``copy bf16[24,1537,16,16,64]``. On a TPU an event's name is the whole
    HLO instruction (``%copy.228 = bf16[...]{...} copy(...)``); a bare name
    (``fusion.123``) loses its instance number."""
    if " = " not in name:
        return re.sub(r"[.\d]+$", "", name.lstrip("%")) or name
    rhs = name.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape = re.sub(r"\{[^}]*\}", "", rhs[:i + 1])
        shape = shape if len(shape) <= 72 else shape[:69] + "..)"
        rest = rhs[i + 1:].lstrip()
    else:
        shape, _, rest = rhs.partition(" ")
        shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{rest.split('(', 1)[0].strip()} {shape}"


def is_collective(fam: str) -> bool:
    return fam.startswith(COLLECTIVES)


def _union(intervals):
    """Merged [start, end] list and its total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def reduce(trace: dict, window_s: float | None = None, top=10,
           min_gap_ns=50_000.0) -> dict | None:
    devs = [p for p in trace["planes"] if _DEVICE.match(p["name"])]
    devs = [p for p in devs if p["lines"]]
    if not devs:
        return None
    busy, fam, coll = [], {}, 0.0
    lo, hi = float("inf"), 0.0
    merged0 = None
    for p in devs:
        iv, civ = [], []
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                f = family(name)
                if is_collective(f):
                    civ.append((s, s + d))     # sync ops and async spans
                if ln["name"] == ASYNC_LINE:
                    continue                   # overlaps the ops line
                iv.append((s, s + d))
                fam[f] = fam.get(f, 0.0) + d
        coll += _union(civ)[1]
        merged, total = _union(iv)
        busy.append(total)
        if merged:
            lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        if merged0 is None:
            merged0 = merged
    n = len(devs)
    extent_s = (hi - lo) * 1e-9
    out = {
        "devices": n,
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": max(extent_s, window_s or 0.0),
        "collective_s": coll / n * 1e-9,
        "families": sorted(([k, v / n * 1e-9] for k, v in fam.items()),
                           key=lambda kv: -kv[1]),
    }
    out["device_ops"] = out["families"][:top]
    # idle gaps on the first device, by what the host was doing
    host = []
    for p in trace["planes"]:
        if _DEVICE.match(p["name"]):
            continue
        for ln in p["lines"]:
            host += [(s, s + d, name) for name, s, d in ln["events"] if d > 0]
    gaps = {}
    for (_, e0), (s1, _) in zip(merged0[:-1], merged0[1:]):
        if s1 - e0 < min_gap_ns:
            continue
        best, cover = "unattributed", 0.0
        for hs, he, name in host:
            c = min(he, s1) - max(hs, e0)
            # the narrowest span that covers most of the gap names it
            if c > 0.5 * (s1 - e0) and (cover == 0.0 or he - hs < cover):
                best, cover = name, he - hs
        gaps[best] = gaps.get(best, 0.0) + (s1 - e0)
    out["idle_gaps"] = sorted(([k, v * 1e-9] for k, v in gaps.items()),
                              key=lambda kv: -kv[1])[:top]
    return out
