"""JAX's persistent compilation cache, placed from outside or at one fixed
path, and JAX's own account of every compile on the program's span ring.

Entry points call :func:`enable` once before their first compile
(`chip_smoke.py`, `bench.py`, `inference/serve.py::main`,
`serving/router.py::main`, the launch workers). It is not called at
``import paddle_tpu`` and not by the tests.

:func:`listen` hands ``jax.monitoring``'s compile events to the registry
(docs/OBSERVABILITY.md "Reading a start-up"). It is called by ``enable()``
and at the import of ``jit/static_function.py`` and ``inference/engine.py``,
so whoever captures a step or builds an engine has it, and it registers
once however often it is called. What JAX 0.9.0's events bracket (read in
``jax/_src/pjit.py``, ``interpreters/pxla.py``, ``compiler.py``):

- ``jaxpr_trace_duration``: one ``jit``'s Python trace to a jaxpr. A ``jit``
  traced inside another reports its own, INSIDE the outer one's interval,
  so these intervals nest: take their union, never their sum.
- ``jaxpr_to_mlir_module_duration``: the jaxpr's lowering to an MLIR module
  (Pallas kernels are lowered to Mosaic here).
- ``backend_compile_duration``: ``compile_or_get_cached`` whole: the
  cache's key and READ (a hit ends there: ``cache_retrieval_time_sec`` is
  inside it), or XLA's compile and the entry's write after a miss.
- ``cache_hits`` fires on a read that found the executable;
  ``cache_misses`` only when an entry is WRITTEN after a compile (JAX
  writes none for a compile shorter than
  ``jax_persistent_cache_min_compile_time_secs``), so a compile's ``cache``
  arg is taken from ``compile_requests_use_cache`` too (which fires
  whether or not a directory is set): ``off`` (no persistent cache),
  ``hit``, or ``miss`` (there is one, and this was compiled anyway).
"""
from __future__ import annotations

import os
import threading
import time
from pathlib import Path

from paddle_tpu.observability import metrics

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# the cache's path is part of every entry's key, so the default must not
# move between runs: the checkout that holds this package, nothing from
# tempfile, the pid or the clock
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# an eager op's first call makes a compile of a few hundred microseconds,
# and building a model makes thousands: those reach their counter only,
# because the span ring is bounded and a reader of set-up gets nothing
# from a ring that lost one span (benchmarks/harness/spans.py)
MIN_SPAN_SECONDS = 1e-3

# JAX's duration event -> (span name or None, seconds counter)
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("xla.trace", metrics.counter("xla.trace_seconds")),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("xla.lower", metrics.counter("xla.lower_seconds")),
    "/jax/core/compile/backend_compile_duration":
        ("xla.compile", metrics.counter("xla.compile_seconds")),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        (None, metrics.counter("xla.cache_retrieval_seconds")),
    "/jax/compilation_cache/compile_time_saved_sec":
        (None, metrics.counter("xla.compile_time_saved_seconds")),
}
# JAX's plain event -> (what the thread's next xla.compile says of the
# cache, counter)
_EVENTS = {
    "/jax/compilation_cache/cache_hits":
        ("hit", metrics.counter("xla.cache_hits")),
    "/jax/compilation_cache/cache_misses":
        ("miss", metrics.counter("xla.cache_misses")),
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_seen = threading.local()    # .cache: "hit" | "miss" since the last compile
_listening = False
_listen_lock = threading.Lock()


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it by itself and
    this sets nothing in code; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    listen()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def listen() -> None:
    """Register the two listeners below with ``jax.monitoring``, once a
    process."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listening = True


def _on_event(event, **_):
    if event == _CACHE_ASKED:
        # a miss until a hit says otherwise; JAX asks its cache also where
        # no directory holds one, and that compile's cache is `off`
        import jax
        if jax.config.jax_compilation_cache_dir is not None:
            _seen.cache = "miss"
        return
    known = _EVENTS.get(event)
    if known is not None:
        _seen.cache, counter = known
        counter.inc()


def _on_duration(event, duration, **kw):
    """One of JAX's compile phases just ended on this thread, ``duration``
    seconds long: its seconds go to the counter, and one of a millisecond
    or more becomes a span under whatever span the thread has open
    (``engine.compile:<program>``, ``jit.first_dispatch:<fn>``,
    ``kernel.select:<op>``, or none)."""
    known = _DURATIONS.get(event)
    if known is None:
        return
    name, counter = known
    # time saved is JAX's recorded compile time less this retrieval:
    # below zero where the cache was the slower way; a counter only rises
    counter.inc(max(0.0, duration))
    if name is None:
        return
    args = {}
    if name == "xla.compile":
        args["cache"] = getattr(_seen, "cache", None) or "off"
        _seen.cache = None
    if duration < MIN_SPAN_SECONDS:
        return
    if "fun_name" in kw:
        args["fun_name"] = str(kw["fun_name"])
    metrics.add_span(name, time.perf_counter() - duration, duration,
                     cat="compile", args=args, under=metrics.open_span())
