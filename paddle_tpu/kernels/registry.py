"""Kernel selection: the one place that decides a kernel's arm.

Three boxes, arrows one way: an op's MODULE (its arms, its
``candidates(ctx)``: the impls viable for one call, by name and in order of
preference; its synthetic workload) -> THIS module -> `observability.metrics`.
This module names no op and imports nothing from `paddle_tpu.kernels`.

:func:`dispatch` resolves one call site: a forced flag value wins, else the
candidates are computed ONCE and, where the call site gives a measurement,
:func:`select` decides among exactly those (memory -> single candidate ->
``PADDLE_AUTOTUNE_CACHE``'s version-1 file -> time each and keep the best;
keys are ``(op-tag, backend, shape-class..., dtype[, variant])``). Every
resolution counts ``kernel.dispatch.{op}.{impl}`` at TRACE time, every
selection is one ``kernel.select:<op>`` span. :func:`backend` and
:func:`measure` are the measurement's two probes; :func:`on_one_tpu` is
what an op that picks a Mosaic arm from what it can see asks first.
"""
from __future__ import annotations

import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Callable

from paddle_tpu.observability import metrics

_LOG = logging.getLogger("paddle_tpu.kernels.registry")

__all__ = ["KernelOp", "register_op", "ops", "dispatch", "count",
           "count_relayout", "count_paged_block", "select",
           "table", "clear", "backend", "on_one_tpu", "measure",
           "tpu_first"]


@dataclass
class KernelOp:
    """One named kernel op.

    ``impls`` is the full universe of impl names a forced flag may name;
    ``candidates(ctx)`` returns the subset VIABLE for this call (backend,
    shape, dtype parity — the ctx keys are op-specific), preference-ordered
    (index 0 is the no-measurement default). ``alias_counter`` keeps a
    pre-registry counter prefix alive alongside ``kernel.dispatch.*``."""
    name: str
    impls: tuple
    candidates: Callable[[dict], list] = field(repr=False, default=None)
    alias_counter: str | None = None


_OPS: dict[str, KernelOp] = {}

# measured winners {key: (winner, {impl: seconds | error})}
_TABLE: dict = {}

_DISK_VERSION = 1
_DISK_STATE: dict = {"path": None, "table": None}   # loaded-once per path


def register_op(name, impls, candidates=None, alias_counter=None):
    """Register (or re-register) one op. Idempotent by name so re-imports
    in tests never duplicate."""
    if candidates is None:
        all_impls = tuple(impls)
        candidates = lambda ctx: list(all_impls)  # noqa: E731
    _OPS[name] = KernelOp(name=name, impls=tuple(impls),
                          candidates=candidates,
                          alias_counter=alias_counter)
    return _OPS[name]


def ops() -> dict:
    return dict(_OPS)


def table() -> dict:
    """{signature: (winner, {impl: seconds, or the error string of a
    candidate that raised})} — measured decisions."""
    return dict(_TABLE)


def clear():
    _TABLE.clear()
    _DISK_STATE["path"] = _DISK_STATE["table"] = None


def count(op: str, impl: str):
    """The per-site trace-time dispatch counter: every resolution lands in
    ``kernel.dispatch.{op}.{impl}`` (and the op's legacy alias, if any).
    Selections run at trace time, so these count program BUILDS per call
    site, not executions."""
    metrics.counter(f"kernel.dispatch.{op}.{impl}").inc()
    o = _OPS.get(op)
    if o is not None and o.alias_counter:
        metrics.counter(f"{o.alias_counter}.{impl}").inc()


def count_relayout(op: str):
    """The trace-time twin of :func:`count` for the KV pool's layout: one
    per attention call whose pools arrive per layer and not as the stored
    stack (`kernels/paged_attention.py::stored_pools`), so a program that
    still copies a layer pool on its way into ``op`` reads non-zero in
    ``kernel.pool_relayout.{op}`` — without a chip."""
    metrics.counter(f"kernel.pool_relayout.{op}").inc()


def count_paged_block(pages: int, op: str | None = None):
    """Trace-time, once per build of a Pallas kernel that walks a paged
    pool: the pages a loop turn of it takes, which the kernel reads off the
    shapes — ``kernel.paged_block.{pages}`` for the paged-attention decode
    kernel (`kernels/pallas/paged_attention.py::block_pages`),
    ``kernel.paged_block.{op}.{pages}`` for another ``op``'s — says which
    shape a program runs, without a chip."""
    metrics.counter(
        f"kernel.paged_block.{op + '.' if op else ''}{pages}").inc()


def backend() -> str:
    """The backend name winners are keyed by and arms are offered for: the
    Pallas arms on ``"tpu"`` only, where they compile (interpret mode
    off-TPU is a parity tool, not a serving path)."""
    import jax
    return jax.default_backend()


def on_one_tpu() -> bool:
    """Whether a Mosaic kernel can be part of the program being traced: the
    backend is a TPU (in the interpreter: a test steers its name) and no
    multi-device mesh is installed, under which the trace becomes a program
    GSPMD partitions, which a Mosaic kernel cannot join."""
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    return backend() == "tpu" and (mesh is None or mesh.size <= 1)


def tpu_first(ctx) -> list:
    """The candidates of an op whose Pallas arm is preferred wherever it
    compiles and is not measured: ``pallas`` then ``xla`` on a TPU, ``xla``
    alone elsewhere (``ctx["backend"]`` stands in for :func:`backend`)."""
    return ["pallas", "xla"] if ctx.get("backend", backend()) == "tpu" \
        else ["xla"]


def measure(fn, args, warmup=1, reps=3, calls=1):
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


def dispatch(op: str, *, forced=None, ctx=None, key=None, measure=None,
             require_viable=False) -> str:
    """Resolve ONE call site's impl and count it.

    forced   : a flag value ("auto"/None defer to selection). Must name an
               impl in the op's universe — an unknown name is a loud
               config error, not a silent xla fallback. Forcing an impl
               outside the VIABLE set is allowed by default (interpret-
               mode parity testing forces pallas off-TPU on purpose)
               unless ``require_viable`` degrades it to the first viable
               candidate (the fused-CE "fused wanted but mp>1" rule).
    ctx      : op-specific viability context for ``candidates(ctx)``,
               which is called once.
    key, measure : the call site's measurement, the table key of this
               signature and ``measure(impl) -> seconds`` over a synthetic
               workload: :func:`select` decides among the candidates just
               computed (a single candidate is pinned, and still a
               recorded decision). Without one the first candidate wins.
    """
    o = _OPS.get(op)
    if o is None:
        raise KeyError(f"unknown kernel op {op!r}; registered: "
                       f"{sorted(_OPS)}")
    cands = o.candidates(ctx or {})
    if forced not in (None, "auto"):
        if forced not in o.impls:
            raise ValueError(
                f"kernel op {op!r} has no impl {forced!r}; known impls: "
                f"{list(o.impls)}")
        impl = forced if (forced in cands or not require_viable) \
            else cands[0]
    elif measure is not None:
        impl = select(op, key, cands, measure)
    else:
        impl = cands[0]
    count(op, impl)
    return impl


# ----------------------------------------------------------- winner table


def select(op: str, key: tuple, candidates: list, measure) -> str:
    """Measured-winner resolution for one (op, signature): in-memory table
    -> single-candidate pin -> persisted winner -> measure every candidate
    (``measure(impl) -> seconds``) and keep the best. A candidate that
    raises is not hidden: its error is logged at WARNING and stored in
    the table entry in place of its time, so whoever reads
    :func:`table` sees which arm the device refused; when every
    candidate raises, so does this. The winner is cached in memory and,
    when ``PADDLE_AUTOTUNE_CACHE`` names a table, persisted on disk.
    Each resolution is one ``kernel.select:<op>`` span whose args name
    the pick, where it came from and the candidates' timings: what the
    selection cost inside the program, beside what it chose."""
    with metrics.span(f"kernel.select:{op}", cat="kernel") as sp:
        winner, source, timings = _resolve(op, key, candidates, measure)
        sp.args.update(pick=winner, source=source, timings_ms={
            k: round(v * 1e3, 4) if isinstance(v, float) else v
            for k, v in timings.items()})
    return winner


def _resolve(op, key, candidates, measure):
    """``(winner, source, {impl: seconds | error})`` for :func:`select`;
    ``source`` says where the answer came from: ``memory`` (this process
    already decided), ``single`` (one viable candidate: pinned), ``disk``
    (the persisted table) or ``measured`` (every candidate timed now)."""
    hit = _TABLE.get(key)
    if hit is not None:
        return hit[0], "memory", hit[1]
    if len(candidates) == 1:
        _TABLE[key] = (candidates[0], {})
        return candidates[0], "single", {}
    disk = _disk_lookup(key, candidates)
    if disk is not None:
        _TABLE[key] = (disk, {})
        return disk, "disk", {}
    import jax
    timings, errors = {}, {}
    for impl in candidates:
        try:
            # selections happen while a step program is being TRACED, where
            # every jnp op (and a nested jit) would only be staged into
            # that trace and the clock would time tracing: step out of
            # the trace and run the candidate for real
            with jax.core.eval_context():
                timings[impl] = measure(impl)
        except Exception as e:  # noqa: BLE001 — recorded, never swallowed
            errors[impl] = f"{type(e).__name__}: {e}"
            _LOG.warning("registry: %s candidate %r failed for %s: %s",
                         op, impl, key, errors[impl])
    if not timings:
        raise RuntimeError(
            f"registry: every candidate of {op} failed for {key}: {errors}")
    winner = min(timings, key=timings.get)
    _TABLE[key] = (winner, {**timings, **errors})
    _disk_store(key, winner)
    return winner, "measured", _TABLE[key][1]


# ------------------------------------------------------------ persistence


def _disk_path():
    return os.environ.get("PADDLE_AUTOTUNE_CACHE") or None


def _load_disk_table(path) -> dict:
    """Read the persisted winner table, ``{"version": 1, "winners":
    {repr(key): impl}}``; ANY failure (missing, corrupt, another version
    or schema) degrades to an empty table — never fatal."""
    try:
        with open(path) as f:
            data = json.load(f)
    except Exception as e:  # noqa: BLE001 — a bad cache file is advisory
        if not isinstance(e, FileNotFoundError):
            _LOG.info("registry: ignoring unreadable cache %s: %s", path, e)
        return {}
    if not isinstance(data, dict) or data.get("version") != _DISK_VERSION:
        return {}
    winners = data.get("winners")
    return winners if isinstance(winners, dict) else {}


def _disk_lookup(key, viable):
    """Persisted winner for ``key``, or None. Winners outside the backend's
    ``viable`` candidate list are stale (table copied across backends or an
    impl renamed) and are ignored."""
    path = _disk_path()
    if path is None:
        return None
    if _DISK_STATE["path"] != path or _DISK_STATE["table"] is None:
        _DISK_STATE["path"] = path
        _DISK_STATE["table"] = _load_disk_table(path)
    win = _DISK_STATE["table"].get(repr(key))
    if isinstance(win, str) and win in viable:
        metrics.counter("autotune.disk_hits").inc()
        return win
    return None


def _disk_store(key, winner):
    """Merge one measured winner into the on-disk table (atomic replace;
    re-reads first so concurrent processes lose at most their own entry).
    Failures are logged and swallowed — persistence is an optimization."""
    path = _disk_path()
    if path is None:
        return
    try:
        tab = _load_disk_table(path)
        tab[repr(key)] = winner
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": _DISK_VERSION, "winners": tab}, f,
                      sort_keys=True)
        os.replace(tmp, path)
        _DISK_STATE["path"], _DISK_STATE["table"] = path, tab
    except Exception as e:  # noqa: BLE001
        _LOG.info("registry: cache write to %s failed: %s", path, e)
