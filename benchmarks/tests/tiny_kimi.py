"""Tiny overrides for rehearsing the ``kimi_k2`` cell on the CPU, as
``tiny_giga.py`` does for the ``gigachat3_5`` cell: all control flow of a
run (the seeded weights, the engine through the model seam with ONE latent
pool, no state and the prefix store ON, the shared contexts sent in set-up,
the hits' tails, the counts on the tokens' readback, the wire, the closed
loop, the walk of the plain reference with the same share of the experts)
at sizes a test can hold: the dense layer and two expert layers, 32 router
outputs of which 4 experts are held, 2 a token, three shared contexts of 20
tokens (five pages of 4: no multiple of the chunk of 8) + 5 to 24 of a
request's own."""
import os

import tiny  # noqa: F401 — puts the benchmark on sys.path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL = {"hidden_size": 64, "intermediate_size": 96,
         "moe_intermediate_size": 16, "vocab_size": 160,
         "num_hidden_layers": 3, "n_routed_experts": 4, "router_outputs": 32,
         "num_experts_per_tok": 2, "num_attention_heads": 4,
         "q_lora_rank": 16, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 1000,
         "rope_scaling": {"original_max_position_embeddings": 16}}
_UN = lambda lo, hi: {"dist": "uniform", "min": lo, "max": hi}  # noqa: E731
CELL = "kimik27-serve-codeagent"
SHARED = 20
TINY = {
    CELL: {
        "config": dict(MODEL, serve={
            "precision": "f32", "page_size": 4, "max_slots": 4,
            "max_seq_len": 64, "num_pages": 97,
            "prefill_chunk_tokens": 8}, limits_meta={"check_requests": 3}),
        "traffic": {"clients": 4, "table_size": 16, "block": 1,
                    "n_prefixes": 3,
                    "classes": [{"name": "codeagent", "per_block": 1,
                                 "shared_prefix": SHARED,
                                 "prompt": _UN(5, 24),
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
    print(json.dumps(rehearse(seed=4700000019)))
