"""SPMD pipeline-parallel engine over the 'pp' mesh axis.

Counterpart of the reference's pipeline runtime — 1F1B `forward_backward_pipeline`
(`python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:119`), stage
layers (`parallel_layers/pp_layers.py:209`) and the p2p layer
(`pp_utils/p2p_communication.py:74`) — redesigned for XLA's single-program model:

- every pp rank holds ONE stage's weights (per-stage param trees stacked on a
  leading [pp] axis, sharded over the 'pp' mesh axis);
- a shard_map body runs the GPipe schedule: `n_micro + pp - 1` unrolled steps,
  each computing the local stage on the current micro-batch and handing the
  activation to the next stage with `jax.lax.ppermute` (the send/recv pair the
  reference implements as batched isend/irecv);
- the BACKWARD pipeline falls out of jax.vjp: the transpose of `ppermute` is the
  reversed ring, so the reverse schedule with its p2p traffic is derived, not
  hand-written.

Loss semantics match the reference's accumulate-then-step contract (GPipe ==
1F1B numerically; 1F1B only changes peak memory, which XLA already schedules).
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.observability import metrics


def note_pipeline_dispatch(engine, n_stages, n_micro, n_ticks, t0, dt):
    """Per-call pipeline schedule accounting, shared by both engines.

    The GPipe schedule lives inside ONE XLA program, so per-tick host timers
    cannot exist; what the host observes is the dispatch of the whole
    `n_micro + s_total - 1`-tick schedule. `tick_seconds` divides that wall
    time evenly over the ticks — the per-(stage, microbatch) figure the
    reference reads off its per-micro p2p timeline. Dispatch is async under
    jax: on a first call the figure includes compile; steady-state calls that
    are not immediately consumed may under-report device time (p50 vs max in
    the histogram separates the two regimes)."""
    metrics.counter("pipeline.calls", engine=engine).inc()
    metrics.counter("pipeline.microbatches", engine=engine).inc(n_micro)
    metrics.gauge("pipeline.stages", engine=engine).set(n_stages)
    metrics.histogram("pipeline.dispatch_seconds", engine=engine).observe(dt)
    metrics.histogram("pipeline.tick_seconds", engine=engine).observe(
        dt / max(n_ticks, 1))
    metrics.add_span(f"pipeline.dispatch:{engine}", t0, dt, cat="pipeline")


class _GuardGenerator:
    """Swapped in as the default RNG generator while template layers execute
    inside a raw jax trace (pipeline stage_fn / MoE expert_fn): stateful RNG
    there would write a leaked tracer into the global generator and bake a
    constant mask. Raising turns silent corruption into a clear error."""

    def __init__(self, what):
        self._what = what

    def __getattr__(self, name):
        raise RuntimeError(
            f"stateful RNG (e.g. Dropout) is not supported inside {self._what}"
            " — the template body is traced outside the to_static RNG-threading"
            " machinery. Set dropout to 0 in these blocks (or move the dropout"
            " outside the pipelined/expert region).")


@contextlib.contextmanager
def template_rng_guard(what):
    from paddle_tpu.ops import random as rnd
    prev = rnd._default_generator
    rnd._default_generator = _GuardGenerator(what)
    try:
        yield
    finally:
        rnd._default_generator = prev


@contextlib.contextmanager
def functional_rng(key):
    """Install a functional generator (ops/random.FunctionalGenerator) so
    nn.Dropout works inside pipeline stage / expert bodies: draws fold a
    deterministic per-call counter into ``key`` instead of mutating global
    state (the TPU answer to the reference's RNGStatesTracker,
    `fleet/layers/mpu/random.py:34` — placement-independent by construction)."""
    from paddle_tpu.ops import random as rnd
    prev = rnd._default_generator
    rnd._default_generator = rnd.FunctionalGenerator(key)
    try:
        yield
    finally:
        rnd._default_generator = prev


def stage_rng_key(base_key, logical_stage, micro):
    """The per-(logical stage, microbatch) dropout key. ONE derivation shared
    by the SPMD engine and the serial oracle, so RNG is a function of model
    position — not of how the pipeline is partitioned."""
    import jax.random as jrandom
    return jrandom.fold_in(jrandom.fold_in(base_key, logical_stage), micro)


def spmd_pipeline(stage_fn, n_stages, n_micro, stacked_params, x, mesh,
                  rng_key=None):
    """Pure-jax GPipe over the 'pp' axis — the single-chunk case of
    :func:`spmd_pipeline_interleaved`.

    stage_fn(local_param_arrays, x_micro) -> y_micro  (shape-preserving);
    with ``rng_key`` it is called as stage_fn(params, x_micro, key).
    stacked_params: list of arrays [n_stages, ...] (leading axis = stage id)
    x: [B, ...] full batch; B must divide into n_micro micro-batches.
    Returns [B, ...] outputs of the LAST stage, replicated over 'pp'.
    """
    return spmd_pipeline_interleaved(stage_fn, n_stages, 1, n_micro,
                                     stacked_params, x, mesh,
                                     rng_key=rng_key)


def pipeline_serial_reference(stage_fn, s_total, n_micro, logical_params, x,
                              rng_key=None):
    """Single-device oracle computing EXACTLY the function the SPMD engine
    computes (same microbatching, same `stage_rng_key` derivation) — the
    parity reference for tests and the multichip dryrun.

    logical_params: arrays with leading axis s_total in LOGICAL stage order
    (the engine instead wants rank-major, see spmd_pipeline_interleaved).
    """
    B = x.shape[0]
    mb = B // n_micro
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    outs = []
    for m in range(n_micro):
        h = xm[m]
        for s in range(s_total):
            local = [p[s] for p in logical_params]
            if rng_key is None:
                h = stage_fn(local, h)
            else:
                h = stage_fn(local, h, stage_rng_key(rng_key, s, m))
        outs.append(h)
    return jnp.concatenate(outs, axis=0)


def stack_stage_params(per_stage_param_trees, mesh):
    """[stage][i] -> list of stacked arrays [n_stages, ...] placed on 'pp'.

    per_stage_param_trees: list (one per stage) of equal-length lists of
    jax arrays in matching order/shapes. A source param already carrying a
    NamedSharding (e.g. the mpu layers' 'mp' placements) keeps its spec with
    'pp' prepended, so pipeline and tensor parallelism compose in one mesh.
    """
    n = len(per_stage_param_trees)
    ref0 = per_stage_param_trees[0]
    for s, tree in enumerate(per_stage_param_trees[1:], 1):
        if len(tree) != len(ref0) or any(
                a.shape != b.shape or a.dtype != b.dtype
                for a, b in zip(tree, ref0)):
            raise ValueError(
                f"pipeline stage {s} param tree differs from stage 0 — "
                "SPMD pipelining needs structurally identical stages")
    stacked = []
    for i in range(len(ref0)):
        arr = jnp.stack([per_stage_param_trees[s][i] for s in range(n)])
        src_sh = getattr(ref0[i], "sharding", None)
        if isinstance(src_sh, NamedSharding) and any(
                ax is not None for ax in src_sh.spec):
            spec = P("pp", *src_sh.spec)
        else:
            spec = P("pp", *([None] * (arr.ndim - 1)))
        stacked.append(jax.device_put(arr, NamedSharding(mesh, spec)))
    return stacked


def spmd_pipeline_interleaved(stage_fn, n_stages, n_chunks, n_micro,
                              stacked_params, x, mesh, rng_key=None):
    """Interleaved (virtual-stage) GPipe over the 'pp' axis — the SPMD analog
    of the reference's `PipelineParallelWithInterleave`
    (`meta_parallel/pipeline_parallel.py:463`): each rank owns ``n_chunks``
    non-adjacent model chunks, so the pipeline bubble shrinks by ~1/n_chunks.

    stage_fn(chunk_param_arrays, x_micro) -> y_micro  (shape-preserving);
    with ``rng_key`` it is called as stage_fn(params, x_micro, key) where key
    is `stage_rng_key(rng_key, logical_stage, micro)` — dropout inside stage
    bodies is then deterministic in model position, so the serial oracle
    (:func:`pipeline_serial_reference`) reproduces it bit-for-bit.
    stacked_params: arrays with leading axis n_stages * n_chunks in RANK-MAJOR
    order — index r * n_chunks + c holds the params of LOGICAL stage
    c * n_stages + r (shard_map splits the leading axis contiguously per rank,
    so each rank's local block is its n_chunks chunks in order). Build it as
    ``stacked_logical[[c * n_stages + r for r in range(S) for c in range(V)]]``.
    Returns the final chunk's outputs [B, ...], replicated over 'pp'.
    """
    B = x.shape[0]
    assert B % n_micro == 0, f"batch {B} not divisible into {n_micro} micro"
    mb = B // n_micro
    s_total = n_stages * n_chunks
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    for p in stacked_params:
        assert p.shape[0] == s_total, (
            f"stacked param leading axis {p.shape[0]} != "
            f"n_stages*n_chunks={s_total}")

    def per_rank(params, xs, *key_data):
        # shard_map's contiguous P('pp') split gives each rank its local
        # [n_chunks, ...] block (rank-major layout, see docstring)
        local = list(params)
        r = jax.lax.axis_index("pp")
        is_first = (r == 0)
        is_last = (r == n_stages - 1)
        base_key = (jax.random.wrap_key_data(key_data[0])
                    if key_data else None)
        carry = jnp.zeros((n_chunks, mb) + xs.shape[2:], xs.dtype)
        ys_hist = []
        total_ticks = n_micro + s_total - 1
        for t in range(total_ticks):
            feed = xs[min(t, n_micro - 1)]
            x0 = jnp.where(is_first, feed, carry[0]) \
                if t < n_micro else carry[0]
            # concatenate, not carry.at[0].set: an in-place update on the
            # big carried buffer creates a full un-aliasable buffer version
            # per unrolled tick in the compiled vjp (measured: ~1 MB/tick
            # fixed temp overhead that erased the pipeline's memory win)
            x_in = (jnp.concatenate([x0[None], carry[1:]], axis=0)
                    if n_chunks > 1 else x0[None])
            # all chunks advance one tick in parallel (independent microbatches)
            if base_key is not None:
                # chunk ci runs LOGICAL stage s = ci*n_stages + r, which at
                # tick t holds microbatch m = t - s (clipped: out-of-range
                # ticks compute garbage that never reaches the output)
                s_ids = jnp.arange(n_chunks) * n_stages + r
                m_ids = jnp.clip(t - s_ids, 0, n_micro - 1)
                keys = jax.vmap(
                    lambda s, m: stage_rng_key(base_key, s, m))(s_ids, m_ids)
                y = _vmap_chunks(stage_fn, local, x_in, keys)
            else:
                y = _vmap_chunks(stage_fn, local, x_in)
            # microbatch m leaves the last chunk of the last rank at
            # t = m + s_total - 1; stash this tick's output instead of
            # updating an [n_micro, ...] buffer in place (aliasing, above)
            ys_hist.append(y)
            if t < total_ticks - 1:
                moved = jax.lax.ppermute(y, "pp", perm)
                # the wrap-around from the last rank enters the NEXT chunk on
                # rank 0; other ranks keep chunk alignment
                rolled = jnp.roll(moved, 1, axis=0)
                carry = jnp.where(is_first, rolled, moved)
        outs = jnp.stack([ys_hist[m + s_total - 1][-1]
                          for m in range(n_micro)])
        return jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)), "pp")

    def _vmap_chunks(fn, local, x_in, keys=None):
        # vmap over the chunk axis of the local params and carries
        if keys is None:
            return jax.vmap(lambda *args: fn(list(args[:-1]), args[-1]))(
                *local, x_in)
        return jax.vmap(
            lambda *args: fn(list(args[:-2]), args[-2], args[-1]))(
            *local, x_in, keys)

    extra = ()
    extra_specs = ()
    if rng_key is not None:
        # raw uint32 key data crosses the shard_map boundary (replicated);
        # typed keys are rewrapped inside per_rank
        extra = (jax.random.key_data(rng_key),)
        extra_specs = (P(),)
    f = jax.shard_map(
        per_rank, mesh=mesh,
        in_specs=(tuple(P("pp") for _ in stacked_params), P()) + extra_specs,
        out_specs=P(), axis_names={"pp"},
        # check_vma must stay off here: the stage bodies run
        # with_sharding_constraint on AUTO axes (dp/mp/sp), and jax's
        # vma checker rejects auto-typed axes inside a manual region
        # (ValueError: axes in vma should be Manual). The ring/ulysses
        # shard_maps, which constrain nothing, run with check_vma=True.
        check_vma=False)
    t0 = time.perf_counter()
    outs = f(tuple(stacked_params), xm, *extra)
    note_pipeline_dispatch("spmd", n_stages, n_micro,
                           n_micro + s_total - 1, t0,
                           time.perf_counter() - t0)
    return outs.reshape((B,) + outs.shape[2:])
