"""ONE kernel registry: named op -> candidate impls -> viability predicate
-> measured winner (ROADMAP item 4).

Before this module, every switchable kernel carried its own dispatch glue:
flash attention had `autotune.flash_winner` + a flag switch, paged decode
attention had `autotune.paged_winner` + its own flag + its own counter,
ring/Ulysses had a dict lookup in `nn/functional/attention.py`, and the
fused CE / fused layernorm sites hand-rolled their gating inline. Each new
kernel (the ragged prefill kernel, the fused sampler) would have added a
fifth and sixth copy. This module is the single replacement:

- **Ops** are registered by NAME with (a) the full impl universe and (b) a
  viability predicate (`candidates(ctx)`) that returns the impls actually
  runnable on this backend for this call — backend viability decided by
  NAME, never by executing an op (`kernels/autotune.py`).
- **Dispatch** (`dispatch()`) resolves one call site's impl: a forced flag
  value wins (validated against the op's universe), a single viable
  candidate pins itself, and multiple candidates defer to the op's
  measured-winner hook (the synthetic-workload measurement lives with the
  op's adapter in `kernels/autotune.py`, which calls back into
  :func:`select` below). Every resolution counts
  ``kernel.dispatch.{op}.{impl}`` — a TRACE-TIME counter (once per program
  build per call site), plus any legacy alias counter the op declares
  (``paged_attention.impl.{impl}`` predates the registry and stays pinned
  by tests).
- **The winner table** (`select()`) is the PR 7 measured-selection policy
  generalized: in-memory cache -> single-candidate short circuit ->
  persisted winner -> measure every viable candidate and keep the best.
  Keys are ``(op-tag, backend, shape-class..., dtype[, variant])`` tuples.
- **Persistence** folds the PR 7 on-disk table in
  (``PADDLE_AUTOTUNE_CACHE``): same version-1 ``{"winners": {repr(key):
  impl}}`` schema, so every legacy file written by `flash_winner` /
  `paged_winner` loads as-is — and a PRE-version bare ``{key: winner}``
  mapping (the oldest format) is migrated on first load. Corrupt or stale
  files are ignored, never fatal; a persisted winner outside the current
  viable set is discarded (a table copied from a TPU host cannot poison a
  CPU one).

`kernels/autotune.py` keeps the measurement probes (`_measure`,
`_backend_kind`, the candidate lists) and the back-compat wrappers
(`flash_winner`/`paged_winner`) — those are the op ADAPTERS; the registry
is the one dispatch + persistence + observability layer under them.
"""
from __future__ import annotations

import ast
import json
import logging
import os
from dataclasses import dataclass, field
from typing import Callable

from paddle_tpu.observability import metrics

_LOG = logging.getLogger("paddle_tpu.kernels.registry")

__all__ = ["KernelOp", "register_op", "ops", "dispatch", "count",
           "count_relayout", "count_paged_block", "select",
           "table", "clear"]


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
    flag: str | None = None
    alias_counter: str | None = None


_OPS: dict[str, KernelOp] = {}

# measured winners {key: (winner, {impl: seconds | error})} —
# `kernels/autotune.py` aliases this object as its `_CACHE` (tests
# introspect it there), so it is mutated IN PLACE only, never rebound.
_TABLE: dict = {}

_DISK_VERSION = 1
_DISK_STATE: dict = {"path": None, "table": None}   # loaded-once per path


def register_op(name, impls, candidates=None, flag=None, alias_counter=None):
    """Register (or re-register) one op. Idempotent by name so re-imports
    in tests never duplicate."""
    if candidates is None:
        all_impls = tuple(impls)
        candidates = lambda ctx: list(all_impls)  # noqa: E731
    _OPS[name] = KernelOp(name=name, impls=tuple(impls),
                          candidates=candidates, flag=flag,
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


def count_paged_block(pages: int):
    """Trace-time, once per build of the Pallas paged-attention decode
    kernel: the pages a loop turn of it takes, which the kernel reads off
    the pool's shape (`kernels/pallas/paged_attention.py::block_pages`) —
    ``kernel.paged_block.{pages}`` says which shape a program runs,
    without a chip."""
    metrics.counter(f"kernel.paged_block.{pages}").inc()


def dispatch(op: str, *, forced=None, ctx=None, winner=None,
             require_viable=False) -> str:
    """Resolve ONE call site's impl and count it.

    forced   : a flag value ("auto"/None defer to selection). Must name an
               impl in the op's universe — an unknown name is a loud
               config error, not a silent xla fallback. Forcing an impl
               outside the VIABLE set is allowed by default (interpret-
               mode parity testing forces pallas off-TPU on purpose)
               unless ``require_viable`` degrades it to the first viable
               candidate (the fused-CE "fused wanted but mp>1" rule).
    ctx      : op-specific viability context for ``candidates(ctx)``.
    winner   : zero-arg measured-selection hook (the op adapter in
               kernels/autotune.py, which calls :func:`select`); consulted
               only when >1 candidate is viable. Without one the first
               viable candidate wins.
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
    elif winner is not None:
        # the adapter owns the winner-table entry even for a single
        # candidate (a pinned impl is still a recorded decision)
        impl = winner()
        if impl not in cands:
            # defense in depth: an adapter whose candidate list drifted
            # from the dispatch-level viability ctx must not smuggle a
            # non-viable impl past the gate — degrade to the first
            # viable candidate and say so
            _LOG.warning(
                "registry: %s winner %r outside the viable set %s — "
                "using %r", op, impl, cands, cands[0])
            impl = cands[0]
    else:
        impl = cands[0]
    count(op, impl)
    return impl


# ----------------------------------------------------------- winner table


def select(op: str, key: tuple, candidates: list, measure,
           verbose_tag: str | None = None) -> str:
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
    if source == "measured":
        try:
            from paddle_tpu.framework.flags import flag_value
            verbose = flag_value("autotune_verbose")
        except Exception:  # noqa: BLE001 — flags registry unavailable
            verbose = False
        if verbose:
            _LOG.warning("autotune %s %s -> %s (%s)", verbose_tag or op,
                         key, winner,
                         {k: f"{v * 1e3:.2f}ms" for k, v in timings.items()
                          if isinstance(v, float)})
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


def _parse_disk(data, count_migrated=True) -> dict:
    """Accept every table generation ever written:

    - version-1 ``{"version": 1, "winners": {repr(key): impl}}`` (the PR 7
      format `flash_winner`/`paged_winner` wrote — loads as-is, the
      registry keys those two ops identically);
    - the PRE-version bare ``{repr(key): impl}`` mapping — migrated in
      (counted on ``autotune.disk_migrated``) so a fleet's oldest cache
      files keep their winners;
    - anything else (future version stamp, wrong shapes) -> empty table.
    """
    if not isinstance(data, dict):
        return {}
    if "version" in data or "winners" in data:
        if data.get("version") != _DISK_VERSION:
            return {}
        winners = data.get("winners")
        return winners if isinstance(winners, dict) else {}
    # legacy pre-version file: a bare {key: winner} mapping. Only migrate
    # entries that look like our repr'd tuple keys with string winners.
    migrated = {k: v for k, v in data.items()
                if isinstance(k, str) and k.startswith("(")
                and isinstance(v, str)}
    if migrated and count_migrated:
        metrics.counter("autotune.disk_migrated").inc(len(migrated))
    return migrated


def _load_disk_table(path, count_migrated=True) -> dict:
    """Read the persisted winner table; ANY failure (missing, corrupt,
    wrong schema) degrades to an empty table — never fatal.
    ``count_migrated=False`` is the store-path re-read: only the
    lookup-time load counts legacy entries, so `autotune.disk_migrated`
    reports each migrated entry ONCE."""
    try:
        with open(path) as f:
            data = json.load(f)
        return _parse_disk(data, count_migrated=count_migrated)
    except Exception as e:  # noqa: BLE001 — a bad cache file is advisory
        if not isinstance(e, FileNotFoundError):
            _LOG.info("registry: ignoring unreadable cache %s: %s", path, e)
        return {}


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
        tab = _load_disk_table(path, count_migrated=False)
        tab[repr(key)] = winner
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": _DISK_VERSION, "winners": tab}, f,
                      sort_keys=True)
        os.replace(tmp, path)
        _DISK_STATE["path"], _DISK_STATE["table"] = path, tab
    except Exception as e:  # noqa: BLE001
        _LOG.info("registry: cache write to %s failed: %s", path, e)


def parse_key(repr_key: str):
    """Best-effort parse of a persisted key back into its tuple (registry
    introspection / tests); None when unparseable."""
    try:
        return ast.literal_eval(repr_key)
    except Exception:  # noqa: BLE001
        return None


# ------------------------------------------------------- built-in op set
#
# Candidate providers import lazily: viability consults the autotune
# backend name (`_backend_kind`) at CALL time, so a test that steers it
# takes effect without re-registration.


def _flash_cands(ctx):
    from paddle_tpu.kernels import autotune
    return autotune._flash_candidates(
        ctx.get("backend", autotune._backend_kind()),
        ctx.get("tileable", False),
        ctx.get("shape_q", (1, 1, 1, 1)), ctx.get("shape_k", (1, 1, 1, 1)),
        ctx.get("partitioned", False))


def _paged_cands(ctx):
    from paddle_tpu.kernels import autotune
    cands = autotune._paged_candidates(
        ctx.get("backend", autotune._backend_kind()))
    if ctx.get("grouped"):
        # grouped queries are not measured (`kernels/paged_attention.py`):
        # where the kernel is viable it is preferred, as it read ten times
        # faster at 32 heads over 8 (PERF.md section 6, PR 31)
        cands = cands[::-1]
    return cands


def _prefill_cands(ctx):
    from paddle_tpu.kernels import autotune
    cands = autotune._paged_candidates(
        ctx.get("backend", autotune._backend_kind()))
    if not ctx.get("parity", True):
        # the pallas arm reads the PAGE POOL; when the pool dtype narrows
        # the compute dtype (bf16 pages under f32 weights, non-quant), the
        # one-shot XLA arm attends the raw full-precision K/V — offering
        # pallas there would silently change numerics, so it is not viable
        cands = [c for c in cands if c != "pallas"]
    if ctx.get("grouped"):
        # the Pallas prefill arm takes one K/V head a query head
        cands = [c for c in cands if c != "pallas"]
    return cands


def _sp_cands(ctx):
    cands = ["ring"]
    if ctx.get("heads", 1) % max(ctx.get("sp", 1), 1) == 0:
        cands.append("ulysses")
    return cands


def _fused_ce_cands(ctx):
    # the fused chunked-vocab CE assumes the full [V, H] head on every
    # rank; under mp the vocab is sharded and only the dense parallel CE
    # is correct
    return ["fused", "dense"] if ctx.get("mp", 1) == 1 else ["dense"]


register_op("flash_attention",
            impls=("xla", "dense", "splash", "mosaic", "authored"),
            candidates=_flash_cands, flag="tpu_flash_impl")
register_op("paged_attention", impls=("xla", "pallas"),
            candidates=_paged_cands, flag="tpu_paged_impl",
            alias_counter="paged_attention.impl")
register_op("prefill_attention", impls=("xla", "pallas"),
            candidates=_prefill_cands, flag="tpu_prefill_impl")
register_op("fused_sampling", impls=("xla",))
register_op("sp_attention", impls=("ring", "ulysses"),
            candidates=_sp_cands)
register_op("fused_ce", impls=("fused", "dense"),
            candidates=_fused_ce_cands)
register_op("fused_layernorm", impls=("pallas",))
register_op("fused_rope", impls=("pallas",))
