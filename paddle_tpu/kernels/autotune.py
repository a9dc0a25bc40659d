"""Measured kernel selection — the op ADAPTERS over `kernels/registry.py`
(ref: `paddle/phi/kernels/autotune/` — cache.h's AutoTuneCache +
auto_tune_base.h's measured selection).

The registry owns dispatch, the winner table, persistence, and the
``kernel.dispatch.*`` counters; this module keeps what is genuinely
measurement-domain:

- the backend name winners are keyed by (`_backend_kind` —
  ``jax.default_backend()``; the Pallas arms are offered on ``"tpu"``
  only, where they compile — interpret mode off-TPU is a parity tool,
  not a serving path);
- the wall-clock measurement harness (`_measure` — best-of-reps around
  ``block_until_ready``);
- the per-op candidate lists (`_flash_candidates`, `_paged_candidates`)
  and the synthetic-workload winner adapters (`flash_winner`,
  `paged_winner`, `prefill_winner`) that build representative arrays and
  call `registry.select`.

``FLAGS_tpu_flash_impl=auto`` routes flash attention through
:func:`flash_winner`; ``FLAGS_tpu_paged_impl=auto`` routes the serving
engine's paged decode step through :func:`paged_winner` (forward only, a
ragged position mix so the measurement sees the length-aware stop);
``FLAGS_tpu_prefill_impl=auto`` routes the ragged PREFILL kernel through
:func:`prefill_winner` the same way.

The measured table can be inspected via :func:`cache_table` and persists
in-process; set ``FLAGS_autotune_verbose=1`` to log decisions.

**Persistent cache** (``PADDLE_AUTOTUNE_CACHE=/path/table.json``): measured
winners are additionally written to the registry's on-disk JSON table
keyed by the same (op, backend, shape-class, dtype[, variant]) signatures,
and consulted before measuring — a server fleet stops re-paying the
measurement wall at every startup. Legacy tables written before the
registry load as-is (and the oldest pre-version bare-mapping files are
migrated on first load); corrupt, stale, or unwritable cache files are
IGNORED, and a persisted winner naming an impl that is not viable on the
current backend is discarded — a table copied from a TPU host cannot
poison a CPU one.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from paddle_tpu.kernels import registry

_LOG = logging.getLogger("paddle_tpu.autotune")

# the ONE winner table, owned by the registry (alias kept because tests
# and tooling introspect it here; mutated in place, never rebound)
_CACHE = registry._TABLE


def cache_table():
    """{signature: (winner, {impl: seconds})} — measured decisions."""
    return registry.table()


def clear_cache():
    registry.clear()


def _backend_kind():
    import jax
    return jax.default_backend()


def _measure(fn, args, warmup=1, reps=3, calls=1):
    """Best-of-reps wall time of one call of a compiled callable (jax
    arrays in/out). ``calls`` > 1: that many calls are launched back to
    back and waited for once, and the time is a call's share: the pace
    the device keeps, as inside a step program, without the host's part
    of one launch, and a hiccup of the host's is shared by all of them."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls - 1):
            fn(*args)
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def _flash_candidates(backend, tileable, shape_q, shape_k,
                      partitioned=False):
    """Impl names viable on this backend (by name, never by execution).
    ``partitioned``: the call is traced into a program GSPMD splits over
    a multi-device mesh. A Mosaic kernel cannot be partitioned
    automatically (jax refuses to lower it outside a ``shard_map``), so
    only the XLA arms are viable there."""
    _logits_elems = (shape_q[0] * shape_q[1] * shape_q[2] * shape_k[2])
    cands = ["xla"]
    if _logits_elems <= (1 << 28):
        # full-materialization SDPA: pure XLA, safe on every backend. The
        # gate bounds the FULL [B, H, Sq, Sk] logits tensor (~1 GB f32),
        # not just Sq*Sk — a doomed OOM measurement wastes a compile per
        # shape
        cands.append("dense")
    if backend == "tpu" and not partitioned:
        if tileable:
            cands += ["mosaic", "splash", "authored"]
        else:
            cands += ["authored"]      # authored handles non-tiled shapes
    return cands


def flash_winner(shape_q, shape_k, dtype, causal, tileable, run_impl,
                 partitioned=False):
    """Pick (and cache) the fastest flash impl for this signature.

    run_impl(impl, q, k, v) must execute the named implementation on
    [B, H, S, D] jax arrays and return [B, H, S, D]. ``partitioned`` (see
    :func:`_flash_candidates`) narrows the candidates and keys its own
    table entry.
    """
    backend = _backend_kind()
    key = ("flash", backend, tuple(shape_q), tuple(shape_k), str(dtype),
           bool(causal)) + (("partitioned",) if partitioned else ())
    cands = _flash_candidates(backend, tileable, shape_q, shape_k,
                              partitioned)
    state = {}

    def measure(impl):
        import jax
        import jax.numpy as jnp
        if "args" not in state:
            rng = np.random.RandomState(0)

            def seq_major(shape):
                b, h, s, d = shape
                return jnp.asarray(rng.randn(b, s, h, d)
                                   .astype(np.float32)).astype(dtype)
            state["args"] = (seq_major(shape_q), seq_major(shape_k),
                             seq_major(shape_k))
        # the call site's arrays are [B, S, H, D] and reach the impl
        # through a swap of axes (`flash_attention_fn`): the measured step
        # does the same, so an arm is timed with the relayouts it would
        # cost there, or save
        step = jax.jit(jax.grad(
            lambda q_, k_, v_, _i=impl: (
                run_impl(_i, *(jnp.swapaxes(x, 1, 2) for x in (q_, k_, v_)))
                .astype(jnp.float32) ** 2).sum(), argnums=(0, 1, 2)))
        # a layer of a step program runs at the device's pace. A single
        # launch's wall time is a third the host's at the training cell's
        # shape (a millisecond of 3 to 4), and a busy host once read three
        # launches in a row 1.8 ms slow and hid an arm 1.5x faster
        return _measure(step, state["args"], calls=8)

    return registry.select("flash_attention", key, cands, measure,
                           verbose_tag="flash")


def _paged_candidates(backend):
    """Paged/prefill attention impls viable on this backend (by name,
    never by execution). Pallas is offered on a TPU only: interpret mode
    off-TPU is a parity tool, not a serving path."""
    return ["xla", "pallas"] if backend == "tpu" else ["xla"]


def _pool_key(num_pages):
    return () if num_pages is None else (f"pool{int(num_pages)}",)


def _synthetic_pools(num_pages, page_size, nh, dh, dtype):
    """Seeded K and V pools in the engine's stored layout, a stack of one
    layer ``[1, num_pages, page_size, nh * dh]`` (read with ``layer=0``: no
    arm's cost depends on how many layers the stack holds), made on the
    device (a real pool is GBs: no host round trip)."""
    import jax
    kk, kv = jax.random.split(jax.random.PRNGKey(0))
    shape = (1, num_pages, page_size, nh * dh)
    return (jax.random.normal(kk, shape, "float32").astype(dtype),
            jax.random.normal(kv, shape, "float32").astype(dtype))


def paged_winner(b, pages_per_slot, page_size, nh, dh, dtype, run_impl,
                 variant="", num_pages=None):
    """Pick (and cache) the fastest paged-attention decode impl for this
    signature — (backend, B, pages_per_slot, page_size, nh, dh, dtype[,
    variant]).

    run_impl(impl, q, k_pages, v_pages, page_table, pos, layer) must execute
    the named implementation over the stored pool (:func:`_synthetic_pools`)
    at ``layer`` and return [B, nh, dh]. ``dtype`` must be a REAL
    dtype (the synthetic test arrays are built with it); ``variant`` is a
    free-form key suffix for callers whose execution differs beyond the
    q dtype (e.g. "kv-int8": the dequant changes each candidate's
    arithmetic intensity, so it must not share the float pools' winner).
    ``num_pages`` is the caller's REAL pool size: the synthetic pool is
    built that large (and the winner keyed by it), so that an arm whose
    cost depends on the pool's capacity and not only on the pages it reads
    is timed as the step programs run it. None keeps the smallest pool that
    holds every slot.
    """
    backend = _backend_kind()
    key = ("paged", backend, int(b), int(pages_per_slot), int(page_size),
           int(nh), int(dh)) + _pool_key(num_pages) \
        + (str(dtype) + (f"/{variant}" if variant else ""),)
    cands = _paged_candidates(backend)
    state = {}

    def measure(impl):
        import jax
        import jax.numpy as jnp
        if "args" not in state:
            pool = max(1 + b * pages_per_slot, int(num_pages or 0))
            rng = np.random.RandomState(0)
            q = jnp.asarray(rng.randn(b, nh, dh).astype(np.float32)) \
                .astype(dtype)
            kp, vp = _synthetic_pools(pool, page_size, nh, dh, dtype)
            pt = jnp.asarray(1 + np.arange(b * pages_per_slot,
                                           dtype=np.int32)
                             .reshape(b, pages_per_slot))
            # ragged mix spanning 1..pages_per_slot pages — the serving
            # shape the pallas kernel's length-aware stop is built for
            pos = jnp.asarray(((np.arange(b) % pages_per_slot) + 1)
                              * page_size - 1, dtype=jnp.int32)
            state["args"] = (q, kp, vp)
            state["pt"], state["pos"] = pt, pos
        pt, pos = state["pt"], state["pos"]
        step = jax.jit(
            lambda q_, k_, v_, _i=impl: run_impl(_i, q_, k_, v_, pt, pos, 0))
        return _measure(step, state["args"])

    return registry.select("paged_attention", key, cands, measure,
                           verbose_tag="paged")


def prefill_winner(chunk, pages_per_slot, page_size, nh, dh, dtype,
                   run_impl, variant="", parity=True, num_pages=None):
    """Pick (and cache) the fastest ragged PREFILL attention impl for this
    signature — (backend, chunk, pages_per_slot, page_size, nh, dh,
    dtype[, variant]). Same candidate set and viability rules as the
    decode kernel; the measurement runs one mid-pool chunk (a page of
    prior context + a full chunk of fresh queries) so the length-aware
    stop is exercised. ``num_pages`` as in :func:`paged_winner`.

    ``parity=False`` is the dispatch-level viability gate threaded
    through (`registry._prefill_cands`): a call whose XLA arm does NOT
    read the page pool (one-shot prefill over a narrowing pool dtype)
    must never measure — let alone pick — the pool-reading pallas arm,
    and the winner is cached under a DISTINCT key so a parity-gated
    signature can't adopt an ungated one's pallas win.

    run_impl(impl, q, k_pages, v_pages, row, start, valid, layer) must
    execute the named implementation on a [1, chunk, nh, dh] query block
    over the stored pool at ``layer`` and return the same shape.
    """
    backend = _backend_kind()
    key = ("prefill", backend, int(chunk), int(pages_per_slot),
           int(page_size), int(nh), int(dh)) + _pool_key(num_pages) \
        + (str(dtype) + (f"/{variant}" if variant else "")
           + ("" if parity else "/no-parity"),)
    cands = _paged_candidates(backend)
    if not parity:
        cands = [c for c in cands if c != "pallas"]
    state = {}

    def measure(impl):
        import jax
        import jax.numpy as jnp
        if "args" not in state:
            pool = max(1 + pages_per_slot, int(num_pages or 0))
            rng = np.random.RandomState(0)
            q = jnp.asarray(rng.randn(1, chunk, nh, dh)
                            .astype(np.float32)).astype(dtype)
            kp, vp = _synthetic_pools(pool, page_size, nh, dh, dtype)
            row = jnp.asarray(1 + np.arange(pages_per_slot, dtype=np.int32))
            state["args"] = (q, kp, vp)
            state["row"] = row
        row = state["row"]
        # a page of prior context where the slot has room for it: the last
        # query position must stay inside the slot (start + chunk <=
        # capacity), past it the page walk leaves the page-table row
        start = jnp.int32(max(0, min(page_size,
                                     pages_per_slot * page_size - chunk)))
        valid = jnp.int32(chunk)
        step = jax.jit(
            lambda q_, k_, v_, _i=impl: run_impl(_i, q_, k_, v_, row,
                                                 start, valid, 0))
        return _measure(step, state["args"])

    return registry.select("prefill_attention", key, cands, measure,
                           verbose_tag="prefill")
