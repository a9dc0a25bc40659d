"""What the program's own kernel selection picks where a configuration pins
it.

A serving configuration may pin flags that the program otherwise resolves
by timing candidates at every start (``serve.flags``; PERF.md says why the
cells pin them). So that the selection layer still has numbers, set-up
first lets the program choose, as a default start would: with the pinned
flags on ``auto`` it calls the two attention ops once at the cell's shapes
(decode over all slots, one prefill chunk, the real pool size), which makes
the registry measure its candidates, and reads the registry's table. The
pins are applied afterwards by the caller. The seconds this takes are part
of ``setup_s``, as they are for a user who starts with the defaults.
"""
from __future__ import annotations

import time


def probe(cfg: dict, dtype) -> dict:
    """``{"seconds", "auto": {kind: {"pick", "timings_ms"}}, "pinned",
    "agree"}``; ``agree`` is the share of the measured selections whose
    pick is the arm that the configuration pins."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.kernels import paged_attention as ops, registry
    sv = cfg["serve"]
    pinned = dict(sv["flags"])
    set_flags({k: "auto" for k in pinned})
    nh = cfg["n_head"]
    dh = cfg["n_embd"] // nh
    slots, page = sv["max_slots"], sv["page_size"]
    per_slot = sv["max_seq_len"] // page
    chunk = sv["prefill_chunk_tokens"]
    t0 = time.perf_counter()
    pool = jnp.zeros((sv["num_pages"], page, nh, dh), dtype)
    table = jnp.zeros((slots, per_slot), jnp.int32)      # the trash page
    jax.block_until_ready(ops.paged_attention(
        jnp.zeros((slots, nh, dh), dtype), pool, pool, table,
        jnp.zeros((slots,), jnp.int32)))
    jax.block_until_ready(ops.prefill_attention(
        jnp.zeros((1, chunk, nh, dh), dtype), pool, pool, table[0],
        jnp.int32(0), jnp.int32(chunk)))
    seconds = time.perf_counter() - t0
    auto = {}
    for key, (pick, timings) in registry.table().items():
        auto[str(key[0])] = {"pick": pick, "timings_ms": {
            k: (1e3 * v if isinstance(v, float) else str(v)[:80])
            for k, v in timings.items()}}
    same = [a["pick"] == pinned.get(f"tpu_{kind}_impl")
            for kind, a in auto.items()]
    return {"seconds": seconds, "auto": auto, "pinned": pinned,
            "agree": sum(same) / len(same) if same else None}
