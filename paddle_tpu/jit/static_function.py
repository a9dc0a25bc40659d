"""StaticFunction: whole-program capture of imperative code into one jitted XLA
computation (see package docstring; ref `program_translator.py:283,399,904,1040`).

Capture protocol:
1. cold call: run the function once with read/write hooks installed on Tensor.
   Every Tensor whose concrete array is *read* becomes a state input; every Tensor
   *written* becomes a state output. RNG state and BN running stats participate
   automatically because they are themselves Tensors.
2. build ``pure(state_arrays, arg_arrays) -> (out_arrays, new_state_arrays)`` that
   replays the python under jax.jit (donating state buffers), keyed by input
   shapes/dtypes like ProgramCache (`program_translator.py:1040`).
3. steady state: call the compiled executable, write state back into the same
   Tensor objects.
"""
from __future__ import annotations

import contextlib
import functools
import time
import weakref
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.core import tensor as tensor_mod
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import compile_cache
from paddle_tpu.framework.flags import flag_value
from paddle_tpu.observability import metrics

# JAX's trace, lower and compile events land on the span ring from here
# on, under whatever span is open (`jit.capture`, `jit.first_dispatch`)
compile_cache.listen()

# ProgramCache telemetry (docs/OBSERVABILITY.md): a hit is a signature that
# resolved to an existing compiled variant; a miss triggers _capture
_M_CACHE_HIT = metrics.counter("jit.cache_hit")
_M_CACHE_MISS = metrics.counter("jit.cache_miss")
_M_COMPILES = metrics.counter("jit.compile_count")
_M_COMPILE_S = metrics.histogram("jit.compile_seconds")
_M_DONATED = metrics.counter("jit.donated_bytes")
_M_DISPATCH_S = metrics.histogram("jit.dispatch_seconds")
_NO_SPAN = contextlib.nullcontext()   # every call of a signature but its first


def _array_nbytes(arrays) -> int:
    n = 0
    for a in arrays:
        nb = getattr(a, "nbytes", None)
        if nb is not None:
            n += int(nb)
    return n

_IGNORED_MODULES: set = set()


def ignore_module(modules):
    _IGNORED_MODULES.update(modules)


def not_to_static(fn=None):
    if fn is None:
        return lambda f: f
    fn._not_to_static = True
    return fn


class _CaptureSet:
    """Read/write sets observed during a capture run. Only tensors that existed
    BEFORE the probe started are state — temporaries created inside the probe are
    recomputed by the traced program (and under remat may hold inner tracers)."""

    def __init__(self, start_stamp: int):
        self.start_stamp = start_stamp
        self.reads: dict[int, Tensor] = {}
        self.writes: dict[int, Tensor] = {}
        self.old_values: dict[int, Any] = {}
        self.order: list[int] = []
        # pre-probe .grad of every state tensor: the probe's backward mutates
        # grads, and grads are themselves step state (grad accumulation across
        # compiled calls), so they are snapshotted, rolled back, and threaded
        self.old_grads: dict[int, Any] = {}

    def _note(self, t: Tensor, key: int):
        if key not in self.old_grads:
            self.old_grads[key] = t._grad

    def on_read(self, t: Tensor):
        if t._stamp > self.start_stamp and not t.persistable:
            return
        key = id(t)
        self._note(t, key)
        if key not in self.reads:
            self.reads[key] = t
            self.order.append(key)

    def on_write(self, t: Tensor):
        if t._stamp > self.start_stamp and not t.persistable:
            return
        key = id(t)
        self._note(t, key)
        if key not in self.writes:
            # hook fires pre-rebind: snapshot so the probe can be rolled back
            # (the compiled first call must BE step one, not step two)
            self.old_values[key] = t._data
        self.writes[key] = t
        if key not in self.reads:
            # written-then-read later in the fn: treat as state too so the final
            # value escapes
            self.reads.setdefault(key, t)
            self.order.append(key)

    def rollback(self):
        for key, t in self.writes.items():
            if key in self.old_values:
                t._data = self.old_values[key]
        for key, t in self.reads.items():
            if key in self.old_grads:
                t._grad = self.old_grads[key]


def _tree_flatten_tensors(obj):
    """Flatten nested python structures, extracting Tensors; returns
    (arrays, treedef-rebuilder)."""
    tensors = []

    def rec(o):
        if isinstance(o, Tensor):
            tensors.append(o)
            return ("__T__", len(tensors) - 1)
        if isinstance(o, dict):
            return {k: rec(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            items = [rec(v) for v in o]
            return ("__L__", type(o).__name__, items)
        return ("__C__", o)

    spec = rec(obj)

    def rebuild(spec, values, wrap):
        if isinstance(spec, tuple) and spec and spec[0] == "__T__":
            return wrap(values[spec[1]])
        if isinstance(spec, tuple) and spec and spec[0] == "__C__":
            return spec[1]
        if isinstance(spec, tuple) and spec and spec[0] == "__L__":
            seq = [rebuild(s, values, wrap) for s in spec[2]]
            return tuple(seq) if spec[1] == "tuple" else seq
        if isinstance(spec, dict):
            return {k: rebuild(v, values, wrap) for k, v in spec.items()}
        return spec

    return tensors, spec, rebuild


def _sig_of(args, kwargs):
    parts = []

    def rec(o):
        if isinstance(o, Tensor):
            parts.append(("T", tuple(o._data.shape), str(o.dtype),
                          o.stop_gradient))
        elif isinstance(o, (list, tuple)):
            parts.append(("L", len(o)))
            for v in o:
                rec(v)
        elif isinstance(o, dict):
            parts.append(("D", tuple(sorted(o))))
            for k in sorted(o):
                rec(o[k])
        else:
            parts.append(("C", repr(o)))

    rec(args)
    rec(kwargs)
    # flags that change what a trace COMPUTES must key the program cache, or
    # toggling them after first compile is silently ignored
    from paddle_tpu.framework.flags import flag_value
    parts.append(("F", flag_value("use_bfloat16_matmul")))
    parts.append(("F", flag_value("moe_dispatch")))
    parts.append(("F", flag_value("tpu_flash_impl")))
    return tuple(parts)


class _Compiled:
    __slots__ = ("jitted", "state_tensors", "out_spec", "out_rebuild",
                 "n_out_tensors", "out_stop_grads", "grad_mask", "pure")

    def __init__(self, jitted, state_tensors, out_spec, out_rebuild,
                 n_out_tensors, out_stop_grads, grad_mask, pure=None):
        self.jitted = jitted
        self.pure = pure
        self.state_tensors = state_tensors
        self.out_spec = out_spec
        self.out_rebuild = out_rebuild
        self.n_out_tensors = n_out_tensors
        self.out_stop_grads = out_stop_grads
        # which state tensors carried a .grad when this variant was captured;
        # a different pattern at call time (e.g. first vs subsequent micro-step
        # of a grad-accumulation loop) selects/captures a different variant
        self.grad_mask = grad_mask

    def mask_matches(self):
        return self.grad_mask == tuple(
            t._grad is not None for t in self.state_tensors)


class StaticFunction:
    def __init__(self, function, input_spec=None, build_strategy=None,
                 backend=None, donate_state=None, **kwargs):
        self._fn = function
        self._cache: dict[Any, _Compiled] = {}
        self._input_spec = input_spec
        self._donate = flag_value("tpu_donate_buffers") if donate_state is None \
            else donate_state
        functools.update_wrapper(self, function)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = functools.partial(self.__call__, instance)
        bound.__wrapped__ = self._fn
        return bound

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)

    def concrete_program(self, *args, **kwargs):
        key = _sig_of(args, kwargs)
        variants = self._cache.get(key)
        return variants[-1] if variants else None

    def __call__(self, *args, **kwargs):
        key = _sig_of(args, kwargs)
        compiled = None
        for cand in self._cache.get(key, ()):
            if cand.mask_matches():
                compiled = cand
                break
        first = compiled is None
        if first:
            _M_CACHE_MISS.inc()
            compiled = self._capture(key, args, kwargs)
        else:
            _M_CACHE_HIT.inc()
        arg_tensors, _, _ = _tree_flatten_tensors((args, kwargs))
        # host-offloaded state (distributed/sharding.offload_optimizer_states):
        # fetch to device memory for the step, push the new value home after —
        # HBM holds these arrays only while the step runs
        state_in = []
        for t in compiled.state_tensors:
            d = t._data
            if getattr(d.sharding, "memory_kind", None) == "pinned_host" \
                    and hasattr(t, "_offload_device"):
                d = jax.device_put(d, t._offload_device)
            state_in.append(d)
        grad_in = [t._grad._data for t, m in zip(compiled.state_tensors,
                                                 compiled.grad_mask) if m]
        arg_in = [t._data for t in arg_tensors]
        if self._donate:
            _M_DONATED.inc(_array_nbytes(state_in) + _array_nbytes(grad_in))
        _t0 = time.perf_counter()
        with self._first_dispatch_span() if first else _NO_SPAN:
            outs = compiled.jitted(state_in, grad_in, arg_in)
        _M_DISPATCH_S.observe(time.perf_counter() - _t0)
        out_arrays, new_state, new_grads = outs
        for t, arr in zip(compiled.state_tensors, new_state):
            if hasattr(t, "_offload_host"):
                arr = jax.device_put(arr, t._offload_host)
            t._data = arr  # direct rebind; hooks not needed outside capture
        for t, g in zip(compiled.state_tensors, new_grads):
            t._grad = None if g is None else Tensor(g, stop_gradient=True,
                                                    _internal=True)
        values = list(out_arrays)

        def wrap(i_arr):
            idx, arr = i_arr
            t = Tensor(arr, stop_gradient=compiled.out_stop_grads[idx],
                       _internal=True)
            return t

        wrapped = [wrap((i, a)) for i, a in enumerate(values)]
        return compiled.out_rebuild(compiled.out_spec, wrapped, lambda t: t)

    # ------------------------------------------------------------------ capture

    def _span_name(self, phase: str) -> str:
        return f"jit.{phase}:{getattr(self._fn, '__name__', '?')}"

    def _first_dispatch_span(self):
        """The span around the FIRST call of a captured signature's
        program, `jit.capture:<fn>`'s sibling: jax traces the pure
        function, lowers it and compiles it (or loads it from the
        persistent cache) inside that call, and JAX's own `xla.trace`,
        `xla.lower` and `xla.compile` spans land under it
        (framework/compile_cache.py). No later call has a span."""
        return metrics.span(self._span_name("first_dispatch"), cat="compile")

    def _capture(self, key, args, kwargs):
        """Probe, trace and register one signature's program, as one
        `jit.capture:<fn>` span. Its wall time covers the abstract probe +
        pure-fn construction; XLA's own compile lands inside the first
        dispatch, under `jit.first_dispatch:<fn>`, whose `xla.compile`
        child says how long it took and whether the persistent cache had
        it."""
        with metrics.span(self._span_name("capture"), cat="compile") as sp:
            compiled = self._probe_and_trace(key, args, kwargs)
        _M_COMPILES.inc()
        _M_COMPILE_S.observe(sp.dur)
        return compiled

    def _probe_and_trace(self, key, args, kwargs, _converted=False):
        if not _converted and getattr(self, "_fn_dy2static", None) is not None:
            # a previous signature already needed conversion — start from
            # the converted fn instead of re-probing the original
            _converted = True
        fn = self._fn if not _converted else self._fn_dy2static
        cap = _CaptureSet(tensor_mod.current_stamp())
        arg_tensors, _, _ = _tree_flatten_tensors((args, kwargs))
        arg_ids = {id(t) for t in arg_tensors}

        # phase 1: ABSTRACT probe — replay fn under jax.eval_shape with the arg
        # arrays as tracers, recording read/write sets through the hooks. State
        # tensors enter the trace as constants (no copies, no FLOPs, and none of
        # the O(model) vjp-residual memory an eager probe would pin in HBM —
        # an un-remat'd GPT-2-small probe at 8x1024 OOMs a 16 GB chip eagerly).
        # Nothing may depend on concrete probe values anyway: phase 2 re-traces
        # the same fn under jit, where every value is abstract.
        result_box = []

        def probe(arg_arrays):
            saved = [(t._data, t._grad_node, t._out_slot, t._grad)
                     for t in arg_tensors]
            for t, a in zip(arg_tensors, arg_arrays):
                t._data = a
                t._grad_node = None
            prev = tensor_mod.set_capture_hooks(
                lambda t: (id(t) not in arg_ids) and cap.on_read(t),
                lambda t: (id(t) not in arg_ids) and cap.on_write(t))
            prev_active = tensor_mod.set_capture_active(True)
            try:
                result_box.append(fn(*args, **kwargs))
                return ()
            finally:
                tensor_mod.set_capture_hooks(*prev)
                tensor_mod.set_capture_active(prev_active)
                for t, (a, n, s, g) in zip(arg_tensors, saved):
                    t._data = a
                    t._grad_node = n
                    t._out_slot = s
                    t._grad = g

        retry_dy2static = False
        try:
            jax.eval_shape(probe, [t._data for t in arg_tensors])
        except Exception as e:
            from paddle_tpu.jit.dy2static import (
                DataDependentControlFlowError)
            if _converted or not isinstance(
                    e, DataDependentControlFlowError):
                raise
            retry_dy2static = True
        finally:
            # roll the probe's state mutations back (tracer writes must not
            # escape; the first compiled call must observe pre-call state)
            cap.rollback()
        if retry_dy2static:
            # data-dependent Python control flow: retry with the AST-
            # converted function (ref ProgramTranslator's transparent
            # dy2static conversion, `program_translator.py:283`)
            from paddle_tpu.jit.dy2static import convert_to_static
            self._fn_dy2static = convert_to_static(self._fn)
            return self._probe_and_trace(key, args, kwargs, _converted=True)
        result = result_box[0]

        state_tensors = [cap.reads[k] for k in cap.order]
        for t in state_tensors:
            if isinstance(t._data, jax.core.Tracer):
                raise RuntimeError(
                    "to_static capture: a persistable tensor created during "
                    "the capture probe holds a tracer (shape "
                    f"{t._data.shape}). Lazily-initialized step state must be "
                    "created under jax.ensure_compile_time_eval() so its "
                    "initial value is concrete (see Optimizer._accumulator).")
        out_tensors, out_spec, out_rebuild = _tree_flatten_tensors(result)
        out_stop_grads = [t.stop_gradient for t in out_tensors]
        # pre-probe grad presence (the probe's own grads were rolled back above)
        grad_mask = tuple(cap.old_grads.get(id(t)) is not None
                          for t in state_tensors)

        # phase 2: build the pure function and jit it
        def pure(state_arrays, grad_arrays, arg_arrays):
            saved_state = [t._data for t in state_tensors]
            saved_args = [t._data for t in arg_tensors]
            saved_nodes = [(t._grad_node, t._out_slot, t._grad)
                           for t in state_tensors + arg_tensors]
            gi = iter(grad_arrays)
            for t, a, m in zip(state_tensors, state_arrays, grad_mask):
                t._data = a
                t._grad_node = None
                t._grad = Tensor(next(gi), stop_gradient=True,
                                 _internal=True) if m else None
            for t, a in zip(arg_tensors, arg_arrays):
                t._data = a
                t._grad_node = None
            prev_active = tensor_mod.set_capture_active(True)
            try:
                res = fn(*args, **kwargs)
                res_tensors, _, _ = _tree_flatten_tensors(res)
                out_arrays = [t._data for t in res_tensors]
                new_state = [t._data for t in state_tensors]
                # grads escape as state too: accumulation across compiled calls
                # and post-call `.grad` inspection both see live values
                new_grads = [None if t._grad is None else t._grad._data
                             for t in state_tensors]
                return out_arrays, new_state, new_grads
            finally:
                tensor_mod.set_capture_active(prev_active)
                for t, a in zip(state_tensors, saved_state):
                    t._data = a
                for t, a in zip(arg_tensors, saved_args):
                    t._data = a
                for t, (n, s, g) in zip(state_tensors + arg_tensors, saved_nodes):
                    t._grad_node = n
                    t._out_slot = s
                    t._grad = g

        # donate threaded grads too: a grad-accumulation micro-step otherwise
        # keeps old+new full-model grad sets live and copies O(model) per call
        donate = (0, 1) if self._donate else ()
        jitted = jax.jit(pure, donate_argnums=donate)
        compiled = _Compiled(jitted, state_tensors, out_spec, out_rebuild,
                             len(out_tensors), out_stop_grads, grad_mask,
                             pure=pure)
        self._cache.setdefault(key, []).append(compiled)
        return compiled

    def multi_steps(self, k: int) -> "MultiStepFunction":
        """k steps per dispatch: `lax.scan` over the captured step.

        Amortizes the fixed per-dispatch cost (measured 5-10 ms/call through
        the TPU runtime, PERF.md) across k steps: the returned callable
        takes the SAME arguments as the step function but with an extra
        leading axis of size k (one slice per step), runs all k steps inside
        ONE compiled, donated XLA program, and returns outputs stacked along
        a leading k axis (so losses can be logged sparsely without breaking
        the async chain).

        This is the step-granularity completion of what the reference's
        one-op `run_program` capture does at op granularity
        (ref `python/paddle/jit/dy2static/program_translator.py:399`):
        there, per-op dispatch is amortized into one program; here, the
        per-program dispatch is amortized into one k-step program.

        Constraint: the step must leave `.grad` presence the way it found it
        (e.g. a full train step ending in `clear_grad()`). A step that turns
        absent grads into present ones (bare grad-accumulation micro-step)
        changes the scan carry structure and raises at trace time.

        Scheduler granularity: host-side Python that runs BETWEEN steps
        (``lr_scheduler.step()``, logging, callbacks) now runs between
        k-step CALLS — the learning rate is constant within one call and
        updates take effect on the next (state tensors, incl. the lr
        tensor, are re-read per call). Pick k well below the scheduler's
        time scale (e.g. k=32 under a 1000-step warmup).
        """
        return MultiStepFunction(self, k)


class MultiStepFunction:
    """See StaticFunction.multi_steps. Shares the per-step capture cache with
    the parent StaticFunction; holds its own cache of k-step executables."""

    def __init__(self, static_fn: StaticFunction, k: int):
        if int(k) < 1:
            raise ValueError(f"multi_steps k must be >= 1, got {k}")
        self._sf = static_fn
        self._k = int(k)
        self._cache: dict[Any, Any] = {}
        functools.update_wrapper(self, static_fn._fn)

    @property
    def steps_per_call(self):
        return self._k

    def __call__(self, *args, **kwargs):
        k = self._k
        arg_tensors, arg_spec, rebuild = _tree_flatten_tensors((args, kwargs))
        for t in arg_tensors:
            if not t._data.shape or t._data.shape[0] != k:
                raise ValueError(
                    f"multi_steps({k}): every tensor argument needs a leading "
                    f"axis of size {k} (one slice per step); got shape "
                    f"{tuple(t._data.shape)}")
        # per-step probe tensors: slice step 0 (shape/dtype carrier only)
        step_tensors = [Tensor(t._data[0], stop_gradient=t.stop_gradient,
                               _internal=True) for t in arg_tensors]
        step_args, step_kwargs = rebuild(arg_spec, step_tensors, lambda t: t)
        sig = _sig_of(step_args, step_kwargs)

        compiled, jitted_k = None, None
        for cand, jk in self._cache.get(sig, ()):
            if cand.mask_matches():
                compiled, jitted_k = cand, jk
                break
        first = compiled is None
        if first:
            _M_CACHE_MISS.inc()
            compiled, jitted_k = self._build(sig, step_args, step_kwargs)
        else:
            _M_CACHE_HIT.inc()

        state_in = []
        for t in compiled.state_tensors:
            d = t._data
            if getattr(d.sharding, "memory_kind", None) == "pinned_host" \
                    and hasattr(t, "_offload_device"):
                d = jax.device_put(d, t._offload_device)
            state_in.append(d)
        grads_full = [t._grad._data if m else None
                      for t, m in zip(compiled.state_tensors,
                                      compiled.grad_mask)]
        stacked = [t._data for t in arg_tensors]
        if self._sf._donate:
            _M_DONATED.inc(_array_nbytes(state_in) +
                           _array_nbytes(g for g in grads_full
                                         if g is not None))
        _t0 = time.perf_counter()
        with self._sf._first_dispatch_span() if first else _NO_SPAN:
            outs_stacked, new_state, new_grads = jitted_k(
                state_in, grads_full, stacked)
        _M_DISPATCH_S.observe(time.perf_counter() - _t0)
        for t, arr in zip(compiled.state_tensors, new_state):
            if hasattr(t, "_offload_host"):
                arr = jax.device_put(arr, t._offload_host)
            t._data = arr
        for t, g in zip(compiled.state_tensors, new_grads):
            t._grad = None if g is None else Tensor(g, stop_gradient=True,
                                                    _internal=True)
        wrapped = [Tensor(a, stop_gradient=compiled.out_stop_grads[i],
                          _internal=True)
                   for i, a in enumerate(outs_stacked)]
        return compiled.out_rebuild(compiled.out_spec, wrapped, lambda t: t)

    def _build(self, sig, step_args, step_kwargs):
        sf = self._sf
        compiled = None
        for cand in sf._cache.get(sig, ()):
            if cand.mask_matches() and cand.pure is not None:
                compiled = cand
                break
        if compiled is None:
            compiled = sf._capture(sig, step_args, step_kwargs)
        pure, mask = compiled.pure, compiled.grad_mask

        def pure_k(state_arrays, grads_full, stacked_args):
            def body(carry, args_t):
                state, gfull = carry
                gin = [g for g, m in zip(gfull, mask) if m]
                outs, new_state, new_grads = pure(state, gin, list(args_t))
                return (new_state, new_grads), outs

            try:
                (state, gfull), outs = jax.lax.scan(
                    body, (state_arrays, grads_full), stacked_args)
            except (TypeError, ValueError) as e:
                raise TypeError(
                    "multi_steps: the step changes which tensors carry a "
                    ".grad between entry and exit (scan carry structure "
                    "mismatch). Use multi_steps only on full train steps "
                    "that end in clear_grad(); run grad-accumulation "
                    "micro-steps through the plain to_static path. "
                    f"Underlying error: {e}") from e
            return outs, state, gfull

        donate = (0, 1) if sf._donate else ()
        jitted_k = jax.jit(pure_k, donate_argnums=donate)
        self._cache.setdefault(sig, []).append((compiled, jitted_k))
        return compiled, jitted_k


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              **kwargs):
    """Decorator/wrapper turning imperative code into one compiled XLA program."""
    def decorate(fn):
        if isinstance(fn, StaticFunction):
            return fn
        from paddle_tpu.nn.layer import Layer
        if isinstance(fn, Layer):
            layer = fn
            layer.forward = StaticFunction(layer.forward.__func__).__get__(
                layer, type(layer))
            return layer
        return StaticFunction(fn, input_spec=input_spec,
                              build_strategy=build_strategy, backend=backend,
                              **kwargs)

    if function is not None:
        return decorate(function)
    return decorate
