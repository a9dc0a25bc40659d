"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It refuses to run without the chips the cell asks
for, keeps JAX's compilation cache where ``framework/compile_cache`` puts
it (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), makes
weights and traffic from ``--seed``, warms the cell's shapes, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line. With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. See benchmarks/README.md.
"""
import time
T_START = time.perf_counter()          # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import device, spec, trace as trace_mod  # noqa: E402
from harness.tracing import Tracer  # noqa: E402


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def run_cell(workload, seed, seconds, trace, rehearsal=None, control=None,
             t_start=None):
    """Drive one cell once and return the result object (the last line).

    ``rehearsal`` (tests only) is ``{"config": {...}, "traffic": {...}}`` of
    overrides merged into the cell's files: the run then skips the look
    for a chip, drives everything else at that tiny size on whatever JAX
    has, and comes out with ``correct`` false, no metrics and the device
    named; ``checks_correct`` says what the comparison itself found.
    ``control`` names a lower precision whose reading a serving cell
    prints beside the program's (used when limits are set, never by the
    driver)."""
    t_start = T_START if t_start is None else t_start
    cell = spec.cell(workload)
    if rehearsal:
        cell["config"] = _merge(cell["config"], rehearsal.get("config", {}))
        cell["traffic"] = _merge(cell["traffic"], rehearsal.get("traffic", {}))
    chips = int(cell["workload"]["chips"])
    import jax
    if rehearsal:
        devices = jax.devices()[:chips]
    else:
        devices = device.require_chips(chips)
        from paddle_tpu.framework import compile_cache
        compile_cache.enable()
        # every program goes to the cache, also the quick ones
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    trace_dir = ROOT / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tcfg = cell["traffic"].get("trace", {})
    tracer = Tracer(trace, trace_dir, tcfg.get("start_after_s", 1.0),
                    min(tcfg.get("length_s", 4.0), max(0.5, seconds - 1.5)))
    ctx = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
           "devices": devices, "tracer": tracer, "control": control}
    obs = spec.runner(cell["traffic"]["kind"]).run(ctx)
    obs["config"] = cell["config"]
    on_chip = devices[0].platform in ("tpu", "gpu")
    obs["device_kind"] = devices[0].device_kind if on_chip else None
    setup_s = obs["t_open"] - t_start

    result = {"correct": bool(obs["checks"].correct),
              "attempted": int(obs["attempted"]), "failed": int(obs["failed"]),
              "metrics": {}}
    dev = device.describe(devices)
    print(json.dumps({"note": "memory", "at_window_close": obs["memory"],
                      "program_temp_bytes": obs.get("program_temp_bytes"),
                      "program": obs.get("program_temp_of")}), flush=True)
    dev["memory_peak_bytes"] = device.peak_with_reservation(obs["memory"])
    if trace:
        path = trace_mod.find_xplane(str(trace_dir))
        red = None
        if path:
            # a trace grows with the step rate: what reading it cost
            t_red = time.perf_counter()
            loaded = trace_mod.load_xplane(path)
            red = trace_mod.reduce(loaded, window_s=tracer.traced_s)
            print(json.dumps({
                "note": "trace_reduction",
                "seconds": time.perf_counter() - t_red,
                "events": sum(len(ln["events"]) for pl in loaded["planes"]
                              for ln in pl["lines"]),
                "traced_s": tracer.traced_s}), flush=True)
        obs["trace"] = red
        if red:
            dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = spec.read_layer_metrics(cell["per_layer"], obs)
    else:
        # every quantity the runner computed, and what the per-layer
        # readers find without a trace, on earlier lines: free to read, and
        # a spread study needs them
        if on_chip:
            print(json.dumps({"note": "e2e", "values": obs["e2e"]}),
                  flush=True)
            print(json.dumps({"note": "per_layer", "values": {
                k: v["value"] for k, v in spec.read_layer_metrics(
                    cell["per_layer"], obs).items()}}), flush=True)
        for m in cell["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else \
                obs["e2e"].get(spec.e2e_quantity(m["name"]))
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
    if obs.get("notes"):
        # what readers computed on their way (readers/decode_roofline.py)
        print(json.dumps({"note": "readers", "values": obs["notes"]}),
              flush=True)
    result["device"] = dev
    if not on_chip:
        # a rehearsal: nothing here is a device metric, so only the names
        # of what was read are kept
        result.update(correct=False, metrics={}, rehearsal=True,
                      metrics_read=sorted(result["metrics"]),
                      checks_correct=bool(obs["checks"].correct))
        result.pop("breakdown", None)
    if "control_out" in ctx:
        result["control"] = ctx["control_out"]
    # last, so that the end of a cut line still holds them
    result["checks"] = obs["checks"].summary()
    return result


def main(argv=None):
    ap = argparse.ArgumentParser("benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for c in result["checks"]:
        print(f'{c["name"]}: {c["value"]} (limit {c["limit"]}) '
              f'{"ok" if c["ok"] else "NOT OK"}', file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
