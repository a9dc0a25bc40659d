"""Imperative autograd on a functional substrate.

The reference implements dygraph autograd as generated per-op GradNode classes plus a
ready-queue backward engine (`paddle/fluid/eager/grad_node_info.h:168`,
`paddle/fluid/eager/backward.cc:105`). Here the same user-facing contract
(``Tensor.backward()`` accumulating ``.grad`` on leaves, hooks, ``retain_graph``,
``no_grad``) is built as a *tape of jax.vjp closures*:

- every op executed through :func:`apply` calls ``jax.vjp`` when gradients are required,
  storing the vjp closure in a :class:`GradNode`;
- ``backward()`` walks reachable nodes in reverse creation order (creation order is a
  valid topological order, so all consumers of a tensor are processed before its
  producing node — the same invariant the reference's in-degree map establishes at
  `backward.cc:22`);
- because ``jax.vjp`` works on tracers, this exact machinery also runs *inside*
  ``jax.jit``: tracing a train step that calls ``loss.backward()`` dissolves the tape
  into one XLA computation (the TPU-native analog of the reference's ``run_program`` op,
  `paddle/fluid/operators/run_program_op.cc`).
"""
from __future__ import annotations

import contextlib
import functools
import itertools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

_node_counter = itertools.count()

# ---------------------------------------------------------------------------- grad mode

_grad_enabled: bool = True


def is_grad_enabled() -> bool:
    return _grad_enabled


@contextlib.contextmanager
def set_grad_enabled(mode: bool):
    global _grad_enabled
    prev = _grad_enabled
    _grad_enabled = bool(mode)
    try:
        yield
    finally:
        _grad_enabled = prev


class no_grad(contextlib.ContextDecorator):
    """Context manager / decorator disabling gradient recording (ref: paddle.no_grad)."""

    def __enter__(self):
        global _grad_enabled
        self._prev = _grad_enabled
        _grad_enabled = False
        return self

    def __exit__(self, *exc):
        global _grad_enabled
        _grad_enabled = self._prev
        return False


class enable_grad(contextlib.ContextDecorator):
    def __enter__(self):
        global _grad_enabled
        self._prev = _grad_enabled
        _grad_enabled = True
        return self

    def __exit__(self, *exc):
        global _grad_enabled
        _grad_enabled = self._prev
        return False


# ---------------------------------------------------------------------------- GradNode


class GradNode:
    """One recorded op application: holds the vjp closure and graph edges.

    Mirrors ``egr::GradNodeBase`` + ``Edge`` (`eager/grad_node_info.h:168,50`), except the
    backward computation is the jax.vjp closure rather than a generated kernel call.
    """

    __slots__ = (
        "vjp_fn", "prim", "inputs", "out_avals", "out_refs", "index", "name",
        "released", "multi", "__weakref__",
    )

    def __init__(self, vjp_fn, inputs, out_avals, name="", prim=None, multi=False):
        self.vjp_fn = vjp_fn
        self.prim = prim                # primal fn (kwargs bound) for create_graph
        self.multi = multi              # primal returned a tuple (vjp wants tuple ct)
        self.inputs = inputs            # list[Tensor] — strong refs (like TensorWrapper)
        self.out_avals = out_avals      # list[(shape, dtype)]
        self.out_refs = []              # list[weakref to output Tensors] for hooks
        self.index = next(_node_counter)
        self.name = name
        self.released = False

    def release(self):
        self.vjp_fn = None
        self.prim = None
        self.inputs = ()
        self.out_refs = ()
        self.released = True

    def __repr__(self):
        return f"<GradNode {self.name}#{self.index}{' released' if self.released else ''}>"


def _tensor_mod():
    from paddle_tpu.core import tensor as T
    return T


def _needs_grad(t) -> bool:
    return (not t.stop_gradient) and jnp.issubdtype(t.dtype, jnp.inexact)


def x64_off_scope():
    """Scoped x64-off dtype promotion — the one spelling in the repo.
    paddle_tpu turns ``jax_enable_x64`` on for the whole process (int64 and
    float64 are real paddle dtypes); Pallas kernels and the SPMD-partitioned
    scan step must trace with int32 Python scalars instead (Mosaic has no
    i64 index type, and XLA's partitioner rejects s64/s32 compares)."""
    return jax.enable_x64(False)


def apply(prim: Callable, *inputs, op_name: str = "", n_outputs: int | None = None,
          x64_off: bool = False, **static_kwargs):
    """Execute ``prim(*arrays, **static_kwargs)`` with autograd recording.

    ``prim`` must be a pure jax function of the positional arrays. Returns Tensor or
    tuple of Tensors. The single dispatch point — the analog of the generated
    ``*_ad_func`` forwards (`eager/auto_code_generator/generator/eager_gen.py`).

    ``x64_off``: trace this op's forward AND backward under x64-disabled dtype
    promotion — required by Pallas kernels (flash attention) that mix
    int32 iota with weak python ints, which breaks under paddle's global
    jax_enable_x64. The backward scope matters because vjp_fn traces the
    custom-vjp bwd rule at backward time, long after the forward scope exits.
    """
    T = _tensor_mod()
    arrays = [t._read() for t in inputs]
    record = _grad_enabled and any(_needs_grad(t) for t in inputs)
    fn = functools.partial(prim, **static_kwargs) if static_kwargs else prim
    if x64_off:
        inner = fn

        def fn(*a):
            with x64_off_scope():
                return inner(*a)

    if not record:
        out = fn(*arrays)
        if _DEBUG_CHECKS:
            _debug_check_outputs(
                op_name or getattr(prim, "__name__", "op"),
                list(out) if isinstance(out, (tuple, list)) else [out])
        return _wrap_outputs(out, node=None, stop_gradient=True)

    out, raw_vjp_fn = jax.vjp(fn, *arrays)
    if x64_off:
        def vjp_fn(cts, _raw=raw_vjp_fn):
            with x64_off_scope():
                return _raw(cts)
    else:
        vjp_fn = raw_vjp_fn
    multi = isinstance(out, (tuple, list))
    outs = list(out) if multi else [out]
    node = GradNode(
        vjp_fn, list(inputs), [(o.shape, o.dtype) for o in outs],
        name=op_name or getattr(prim, "__name__", "op"), prim=fn, multi=multi,
    )
    if _DEBUG_CHECKS:
        _debug_check_outputs(node.name, outs)
    result = _wrap_outputs(out, node=node, stop_gradient=False)
    return result


_DEBUG_CHECKS = False     # flipped by flags.set_flags (check_nan_inf/benchmark)


def _debug_check_outputs(op_name, outs):
    """FLAGS_check_nan_inf / FLAGS_benchmark hooks at the dispatch point (ref
    per-op nan/inf detection `eager/nan_inf_utils.cc`, gated the same way).
    Eager-only: inside a trace, jax_debug_nans (also wired to the flag) covers
    the compiled path."""
    from paddle_tpu.framework.flags import flag_value
    check = flag_value("check_nan_inf")
    bench = flag_value("benchmark")
    if not (check or bench):
        return
    for o in outs:
        if isinstance(o, jax.core.Tracer):
            return
        if bench:
            jax.block_until_ready(o)
        if check and jnp.issubdtype(o.dtype, jnp.inexact):
            bad = ~jnp.isfinite(o)
            if bool(jnp.any(bad)):
                raise FloatingPointError(
                    f"FLAGS_check_nan_inf: op '{op_name}' produced "
                    f"{int(jnp.sum(bad))} non-finite value(s) in an output of "
                    f"shape {tuple(o.shape)}")


def _wrap_outputs(out, node, stop_gradient):
    import weakref
    T = _tensor_mod()
    multi = isinstance(out, (tuple, list))
    outs = list(out) if multi else [out]
    wrapped = []
    for i, o in enumerate(outs):
        t = T.Tensor(o, stop_gradient=stop_gradient, _internal=True)
        if node is not None:
            t._grad_node = node
            t._out_slot = i
            node.out_refs.append(weakref.ref(t))
        wrapped.append(t)
    if multi:
        return tuple(wrapped)
    return wrapped[0]


# ---------------------------------------------------------------------------- backward


def _collect_subgraph(roots: Sequence[GradNode]):
    """DFS the node graph reachable from roots; returns nodes sorted by index desc."""
    seen = {}
    stack = list(roots)
    while stack:
        n = stack.pop()
        if n is None or n.index in seen:
            continue
        if n.released:
            raise RuntimeError(
                f"GradNode {n.name} has been released; set retain_graph=True to "
                "backward through the same graph twice.")
        seen[n.index] = n
        for t in n.inputs:
            if t._grad_node is not None:
                stack.append(t._grad_node)
    return sorted(seen.values(), key=lambda n: -n.index)


def backward(tensors, grad_tensors=None, retain_graph=False):
    """Run backward from ``tensors``, accumulating ``.grad`` on leaf tensors.

    Ref: ``egr::Backward`` (`eager/backward.cc:393`). Leaf accumulation mirrors
    ``GradNodeAccumulation`` (`eager/accumulation/accumulation_node.cc`).
    """
    T = _tensor_mod()
    if not isinstance(tensors, (list, tuple)):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    # node -> {slot: cotangent array}
    pending: dict[int, dict[int, Any]] = {}
    nodes_by_id: dict[int, GradNode] = {}
    roots = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            # reference semantics (varbase_patch_methods.py:234): implicit initial
            # gradient is ones for ANY shape, not just scalars
            g_arr = jnp.ones(t.shape, t.dtype)
        else:
            g_arr = g._data if isinstance(g, T.Tensor) else jnp.asarray(g, t.dtype)
        node = t._grad_node
        if node is None:
            if not t.stop_gradient:
                _accumulate_leaf(t, g_arr)
            continue
        roots.append(node)
        slot_map = pending.setdefault(node.index, {})
        prev = slot_map.get(t._out_slot)
        slot_map[t._out_slot] = g_arr if prev is None else prev + g_arr
        nodes_by_id[node.index] = node

    order = _collect_subgraph(roots)
    for node in order:
        slot_map = pending.pop(node.index, {})
        cotangents = []
        for i, (shape, dtype) in enumerate(node.out_avals):
            g = slot_map.get(i)
            if g is None:
                g = jnp.zeros(shape, dtype)
            else:
                g = jnp.asarray(g, dtype)
            cotangents.append(g)
        # fire output-tensor hooks now that cotangents are final
        for ref in node.out_refs:
            t = ref()
            if t is not None and t._hooks:
                g = cotangents[t._out_slot]
                for hook in t._hooks.values():
                    new_g = hook(T.Tensor(g, stop_gradient=True, _internal=True))
                    if new_g is not None:
                        g = new_g._data if isinstance(new_g, T.Tensor) else jnp.asarray(new_g)
                cotangents[t._out_slot] = g
        in_grads = node.vjp_fn(tuple(cotangents) if node.multi
                               else cotangents[0])
        for t, g in zip(node.inputs, in_grads):
            if g is None or g.dtype == jax.dtypes.float0:
                continue
            if t.stop_gradient:
                continue
            child = t._grad_node
            if child is None:
                _accumulate_leaf(t, g)
            else:
                m = pending.setdefault(child.index, {})
                prev = m.get(t._out_slot)
                m[t._out_slot] = g if prev is None else prev + g
        if not retain_graph:
            node.release()


def _accumulate_leaf(t, g_arr):
    T = _tensor_mod()
    g_arr = jnp.asarray(g_arr, t.dtype)
    if t._hooks:
        for hook in t._hooks.values():
            new_g = hook(T.Tensor(g_arr, stop_gradient=True, _internal=True))
            if new_g is not None:
                g_arr = new_g._data if isinstance(new_g, T.Tensor) else jnp.asarray(new_g)
    if t._grad is not None and not isinstance(t._grad, T.Tensor):
        # existing grad is a SelectedRows (sparse embedding + tied dense use):
        # densify so both contributions survive
        t._grad = T.Tensor(t._grad.to_dense().astype(t.dtype),
                           stop_gradient=True, _internal=True)
    if t._grad is None:
        t._grad = T.Tensor(g_arr, stop_gradient=True, _internal=True)
    else:
        t._grad = T.Tensor(t._grad._data + g_arr, stop_gradient=True, _internal=True)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False,
         only_inputs=True, allow_unused=False, no_grad_vars=None):
    """Functional gradient API (ref: ``paddle.grad``, `eager/general_grad.h`).

    Computes gradients of ``outputs`` w.r.t. ``inputs`` without touching ``.grad``.
    ``create_graph`` re-records backward ops on the tape for higher-order grads.
    """
    T = _tensor_mod()
    single_in = not isinstance(inputs, (list, tuple))
    if single_in:
        inputs = [inputs]
    if not isinstance(outputs, (list, tuple)):
        outputs = [outputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif not isinstance(grad_outputs, (list, tuple)):
        grad_outputs = [grad_outputs]
    if retain_graph is None:
        retain_graph = create_graph

    no_grad_ids = {id(v) for v in (no_grad_vars or [])}
    input_ids = {id(t): i for i, t in enumerate(inputs)}
    results: list = [None] * len(inputs)

    # Cotangent values flow through the walk either as raw arrays (create_graph=False)
    # or as tape-connected Tensors (create_graph=True) so grad-of-grad stays wired.
    if create_graph:
        def _lift(arr):
            return T.Tensor(arr, stop_gradient=True, _internal=True)

        def _vadd(a, b):
            return a + b  # Tensor arithmetic — records on the tape

        def _vdata(v):
            return v._data
    else:
        def _lift(arr):
            return arr

        def _vadd(a, b):
            return a + b

        def _vdata(v):
            return v

    pending: dict[int, dict[int, Any]] = {}
    roots = []
    for t, g in zip(outputs, grad_outputs):
        if g is None:
            gv = _lift(jnp.ones(t.shape, t.dtype))
        elif isinstance(g, T.Tensor):
            gv = g if create_graph else g._data
        else:
            gv = _lift(jnp.asarray(g, t.dtype))
        if id(t) in input_ids:
            i = input_ids[id(t)]
            results[i] = gv if results[i] is None else _vadd(results[i], gv)
        node = t._grad_node
        if node is None:
            continue
        roots.append(node)
        m = pending.setdefault(node.index, {})
        prev = m.get(t._out_slot)
        m[t._out_slot] = gv if prev is None else _vadd(prev, gv)

    order = _collect_subgraph(roots)
    for node in order:
        slot_map = pending.pop(node.index, None)
        if slot_map is None:
            continue  # not on a path from outputs
        cotangents = []
        for i, (shape, dtype) in enumerate(node.out_avals):
            g = slot_map.get(i)
            cotangents.append(_lift(jnp.zeros(shape, dtype)) if g is None else g)
        # float0 cotangents appear exactly for non-inexact primal inputs, so the
        # keep-mask is static and keeps the filtered vjp outputs aligned.
        keeps = [jnp.issubdtype(t.dtype, jnp.inexact) for t in node.inputs]
        if create_graph:
            # Re-derive the vjp from the primal fn applied to the tape Tensors so the
            # grad-of-grad graph connects to the primal inputs (jax.vjp residuals in
            # node.vjp_fn are baked constants and would not be differentiated).
            n_in = len(node.inputs)
            n_out = len(node.out_avals)

            def grad_op(*args, _fn=node.prim, _n_in=n_in, _multi=node.multi,
                        _keeps=tuple(keeps)):
                primals, cts = args[:_n_in], args[_n_in:]
                _, vjp_fn = jax.vjp(_fn, *primals)
                gs = vjp_fn(tuple(cts) if _multi else cts[0])
                return tuple(g for g, k in zip(gs, _keeps) if k)

            grads = apply(grad_op, *node.inputs, *cotangents,
                          op_name=f"{node.name}_grad")
            if not isinstance(grads, tuple):
                grads = (grads,)
            kept = iter(grads)
            in_grads = [next(kept) if k else None for k in keeps]
        else:
            out = node.vjp_fn(tuple(cotangents) if node.multi
                              else cotangents[0])
            in_grads = [g if k else None for g, k in zip(out, keeps)]
        for t, g in zip(node.inputs, in_grads):
            if g is None:
                continue
            if id(t) in no_grad_ids or t.stop_gradient:
                continue
            if id(t) in input_ids:
                i = input_ids[id(t)]
                results[i] = g if results[i] is None else _vadd(results[i], g)
            child = t._grad_node
            if child is not None:
                m = pending.setdefault(child.index, {})
                prev = m.get(t._out_slot)
                m[t._out_slot] = g if prev is None else _vadd(prev, g)
        if not retain_graph and not create_graph:
            node.release()

    out_tensors = []
    for i, (t, r) in enumerate(zip(inputs, results)):
        if r is None:
            if not allow_unused:
                raise RuntimeError(
                    f"input {i} is unreachable from outputs; pass allow_unused=True "
                    "to get None for such inputs")
            out_tensors.append(None)
        elif isinstance(r, T.Tensor):
            out_tensors.append(r)
        else:
            out_tensors.append(T.Tensor(jnp.asarray(r), stop_gradient=True,
                                        _internal=True))
    # ALWAYS a list, matching the reference ("a list of Tensors, whose
    # length is the same as the Tensor number inside inputs") — unwrapping
    # for a single bare-Tensor input made the common `paddle.grad(y, x)[0]`
    # idiom silently index ELEMENT 0 of the gradient instead
    return out_tensors
