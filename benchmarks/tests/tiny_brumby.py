"""Tiny overrides for rehearsing the ``brumby`` cell on the CPU, as
``tiny_granite.py`` does for the ``granitemoehybrid`` cell: all control flow
of a run — the seeded weights, the engine through the model seam with no
page pool, chunked prefill into the retention state and decode out of it,
the wire, the closed loop, the walk of the plain reference's attention form
— at sizes a test can hold (3 layers, 4 query heads over 2 key-value heads
of 8)."""
import os

import tiny  # noqa: F401 — puts the benchmark on sys.path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = {"hidden_size": 64, "num_hidden_layers": 3,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "intermediate_size": 96, "vocab_size": 160,
         "max_position_embeddings": 512}
_UN = lambda lo, hi: {"dist": "uniform", "min": lo, "max": hi}  # noqa: E731
CELL = "brumby14b-serve-longform"
TINY = {
    CELL: {
        "config": dict(MODEL, serve={
            "precision": "f32", "page_size": 4, "max_slots": 4,
            "max_seq_len": 512, "prefill_chunk_tokens": 16,
            "reference_pad": 320}, limits_meta={"check_requests": 3}),
        "traffic": {"clients": 4, "table_size": 16,
                    "classes": [{"name": "unshared", "per_block": 8,
                                 "prompt": _UN(6, 40),
                                 "answer": _UN(4, 16)}]}},
}


def rehearse(workload=CELL, seed=1, seconds=1.5, trace=False, **kw):
    import time
    import run as bench_run
    return bench_run.run_cell(workload, seed, seconds, trace,
                              rehearsal=TINY[workload],
                              t_start=time.perf_counter(), **kw)


if __name__ == "__main__":
    import json
    print(json.dumps(rehearse(seed=3600000019)))
