"""Tiny overrides for rehearsing the ``granitemoehybrid`` cell on the CPU,
as ``tiny_hybrid.py`` does for the ``phi4flash`` cell: all control flow of a
run — the seeded weights, the engine through the model seam, the routing
counts on the tokens' readback, the wire, the closed loop, the walk of the
plain reference with the same share of the experts — at sizes a test can
hold (a period of 4 layers, 8 experts of which 4 are held, 3 a token)."""
import os

import tiny  # noqa: F401 — puts the benchmark on sys.path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = {"hidden_size": 64, "num_hidden_layers": 4,
         "layer_types": ["mamba", "mamba", "attention", "mamba"],
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 32, "shared_intermediate_size": 48,
         "vocab_size": 160, "num_local_experts": 4, "router_outputs": 8,
         "num_experts_per_tok": 3, "mamba_n_heads": 4, "mamba_d_head": 16,
         "mamba_d_state": 16, "mamba_chunk_size": 8,
         "attention_multiplier": 0.0625, "assumed": {"head_dim": 16}}
_UN = lambda lo, hi: {"dist": "uniform", "min": lo, "max": hi}  # noqa: E731
CELL = "granite4h-serve-agent"
TINY = {
    CELL: {
        "config": dict(MODEL, serve={
            "precision": "f32", "conv_state": "f32", "page_size": 4,
            "max_slots": 4, "max_seq_len": 64, "num_pages": 65,
            "prefill_chunk_tokens": 16}, limits_meta={"check_requests": 3}),
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
    print(json.dumps(rehearse(seed=3100000019)))
