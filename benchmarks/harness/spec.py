"""Finds everything that belongs to one cell by the names in BENCHMARK.json.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
The configuration is ``configs/<config>.json`` (the path BENCHMARK.json
gives), the traffic mix is ``traffic/<traffic>.json``, and each per-layer
metric is ``layer_metrics/<name>.json``; an end-to-end metric is a quantity
the runner computes, under its own name or the one that
``e2e_metrics/<name>.json`` gives it. A traffic file's ``kind`` picks the
runner module ``runners/<kind>.py``; a metric file's ``reader`` picks
``readers/<reader>.py``. Nothing here knows a cell, a mix or a metric by
name, so a later PR adds any of them as new files only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry ``name`` with its configuration and traffic files
    loaded: ``{"workload", "config", "traffic", "end_to_end", "per_layer"}``.
    The metric lists hold only what this cell reports."""
    bm = benchmark(root)
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bm['workloads']]}")
    cfg_entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    config = _load_json(root / cfg_entry["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{wl['traffic']}.json")

    def mine(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bm["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if mine(m) and m["moves"] in reported]
    return {"workload": wl, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer,
            "run_seconds": bm["run_seconds"]}


def _module(kind_dir: str, kind: str):
    path = BENCH_DIR / kind_dir / f"{kind}.py"
    if not path.is_file():
        have = sorted(p.stem for p in (BENCH_DIR / kind_dir).glob("*.py"))
        raise SystemExit(f"no {kind_dir}/{kind}.py; there are {have}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind_dir}_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(kind: str):
    """``runners/<kind>.py``: has ``run(ctx) -> observations``."""
    return _module("runners", kind)


def e2e_quantity(name: str) -> str:
    """The runner's quantity that the end-to-end metric ``name`` reports:
    ``e2e_metrics/<name>.json`` names it under ``of`` where a cell reports
    a quantity under a name (and so a bound) of its own; without the file
    it is the quantity of that name."""
    path = BENCH_DIR / "e2e_metrics" / f"{name}.json"
    return _load_json(path)["of"] if path.is_file() else name


def layer_metric(name: str) -> dict:
    return _load_json(BENCH_DIR / "layer_metrics" / f"{name}.json")


def reader(kind: str):
    """``readers/<kind>.py``: has ``read(obs, **params) -> float | None``."""
    return _module("readers", kind)


def read_layer_metrics(per_layer: list, obs: dict) -> dict:
    """Each metric's reader over the run's observations. A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in per_layer:
        spec = layer_metric(m["name"])
        params = {k: v for k, v in spec.items() if k not in ("reader", "doc")}
        value = reader(spec["reader"]).read(obs, **params)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
