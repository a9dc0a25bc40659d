"""Tiny overrides for rehearsing each cell on the CPU: all control flow of a
run, at sizes a test can hold. Nothing a rehearsal times is a device metric,
and ``run_cell`` says so (``correct`` false, no metrics, device named)."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {"n_embd": 64, "n_layer": 2, "n_head": 2, "n_inner": 256,
         "n_positions": 128, "n_ctx": 128, "vocab_size": 500,
         "assumed": {"vocab_rows": 512}}
_UN = lambda lo, hi: {"dist": "uniform", "min": lo, "max": hi}  # noqa: E731
SERVE = {"serve": {"max_slots": 4, "max_seq_len": 128, "num_pages": 33,
                   "prefill_chunk_tokens": 32, "precision": "f32"},
         "limits_meta": {"check_requests": 3}}
TINY = {
    "gpt2s-train-b16s1024": {
        "config": dict(MODEL, train={"precision": "f32",
                                     "reference_row_block": 2}),
        "traffic": {"batch": 4, "seq": 64}},
    "gpt2m-serve-decode": {
        "config": dict(MODEL, **SERVE),
        "traffic": {"clients": 4, "table_size": 16,
                    "classes": [{"name": "unshared", "per_block": 8,
                                 "prompt": _UN(8, 48), "answer": _UN(4, 16)}]}},
    "gpt2m-serve-prefill": {
        "config": dict(MODEL, **SERVE),
        "traffic": {"table_size": 16, "ramp_s": 0.5, "max_in_flight": 8,
                    "n_prefixes": 2,
                    "schedule": {"rate_per_s": 20.0, "group_every_s": 1.0,
                                 "group": 3},
                    "classes": [
                        {"name": "hit", "per_block": 6, "shared_prefix": 32,
                         "prompt": _UN(4, 16), "answer": _UN(2, 8)},
                        {"name": "miss", "per_block": 2,
                         "prompt": _UN(34, 80), "answer": _UN(2, 8)}]}},
}


def rehearse(workload, seed=1, seconds=1.5, trace=False, **kw):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import run as bench_run
    import time
    return bench_run.run_cell(workload, seed, seconds, trace,
                              rehearsal=TINY[workload],
                              t_start=time.perf_counter(), **kw)


if __name__ == "__main__":
    import json
    names = sys.argv[1:] or list(TINY)
    for n in names:
        print(json.dumps(rehearse(n, seed=3000000019)))
