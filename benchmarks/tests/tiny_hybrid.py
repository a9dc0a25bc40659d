"""Tiny overrides for rehearsing the ``phi4flash`` cell on the CPU, as
``tiny.py`` does for the GPT-2 cells: all control flow of a run — the
seeded weights, the engine through the model seam, the wire, the closed
loop, the walk of the plain reference — at sizes a test can hold (8 layers,
so all five mixers occur; window 8, page 4, chunk 8)."""
import os

import tiny  # noqa: F401 — puts the benchmark on sys.path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = {"hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 96,
         "vocab_size": 160, "sliding_window": 8,
         "assumed": {"mamba_d_state": 8, "mamba_dt_rank": 4}}
_UN = lambda lo, hi: {"dist": "uniform", "min": lo, "max": hi}  # noqa: E731
TINY = {
    "phi4flash-serve-reason": {
        "config": dict(MODEL, serve={
            "precision": "f32", "conv_state": "f32", "page_size": 4,
            "max_slots": 4, "max_seq_len": 64, "num_pages": 65,
            "prefill_chunk_tokens": 8}, limits_meta={"check_requests": 3}),
        "traffic": {"clients": 4, "table_size": 16,
                    "classes": [{"name": "unshared", "per_block": 8,
                                 "prompt": _UN(6, 30),
                                 "answer": _UN(4, 16)}]}},
}


def rehearse(workload="phi4flash-serve-reason", seed=1, seconds=1.5,
             trace=False, **kw):
    import time
    import run as bench_run
    return bench_run.run_cell(workload, seed, seconds, trace,
                              rehearsal=TINY[workload],
                              t_start=time.perf_counter(), **kw)


if __name__ == "__main__":
    import json
    print(json.dumps(rehearse(seed=3000000019)))
