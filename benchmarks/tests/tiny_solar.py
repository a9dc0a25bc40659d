"""Tiny overrides for rehearsing the ``solar_open2`` cell on the CPU, as
``tiny_giga.py`` does for the ``gigachat3_5`` cell: all control flow of a
run (the seeded weights, the engine through the model seam with twin K and
V pools and two recurrent arrays a linear layer, the counts on the tokens'
readback, the wire, the closed loop, the walk of the plain reference with
the same share of the experts) at sizes a test can hold: one period
(softmax, linear, linear, linear), 8 query heads over 2 key-value heads, 4
linear heads of 8, 32 router outputs of which 4 experts are held, 2 a
token, prompts of 12 to 44 in chunks of 8 so that the state is carried
across chunk boundaries."""
import os

import tiny  # noqa: F401 — puts the benchmark on sys.path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = {"hidden_size": 64, "moe_intermediate_size": 16, "vocab_size": 160,
         "n_routed_experts": 4, "router_outputs": 32,
         "num_experts_per_tok": 2, "num_attention_heads": 8,
         "num_key_value_heads": 2, "head_dim": 8,
         "linear_attn_config": {"head_dim": 8, "num_heads": 4}}
_UN = lambda lo, hi: {"dist": "uniform", "min": lo, "max": hi}  # noqa: E731
CELL = "solaropen2-serve-docqa"
TINY = {
    CELL: {
        "config": dict(MODEL, serve={
            "precision": "f32", "page_size": 4, "max_slots": 4,
            "max_seq_len": 64, "num_pages": 65,
            "prefill_chunk_tokens": 8}, limits_meta={"check_requests": 3}),
        "traffic": {"clients": 4, "table_size": 16, "block": 1,
                    "classes": [{"name": "unshared", "per_block": 1,
                                 "prompt": _UN(12, 44),
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
    print(json.dumps(rehearse(seed=4000000019)))
