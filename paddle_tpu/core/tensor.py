"""The imperative Tensor: a Paddle-flavored wrapper over an immutable ``jax.Array``.

Reference analog: ``phi::DenseTensor`` (`paddle/phi/core/dense_tensor.h:38`) plus the
eager-mode Python Tensor (`paddle/fluid/pybind/eager.cc`, `eager_method.cc`). Because
``jax.Array`` is immutable, "in-place" ops rebind ``_data``; previously recorded vjp
closures keep referencing the old value, so the tape stays consistent without the
reference's inplace-version checks (`paddle/fluid/eager/tensor_wrapper.h`).

The same Tensor object can hold either a concrete device array (eager mode) or a JAX
tracer (inside ``to_static``/``jax.jit`` capture) — this is what collapses the
reference's dygraph/static duality into one code path.
"""
from __future__ import annotations

import itertools

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.core import autograd
from paddle_tpu.core import dtype as dtype_mod

# tensor-creation clock: lets jit capture distinguish pre-existing state tensors
# (params, buffers, RNG/optimizer state) from temporaries created during a probe run
_creation_clock = 0


def current_stamp() -> int:
    return _creation_clock


_ops_cache = None


def _ops():
    global _ops_cache
    if _ops_cache is None:
        import paddle_tpu.ops as ops
        _ops_cache = ops
    return _ops_cache


# Read/write hooks for static capture (set by paddle_tpu.jit). Each is either None or
# a callable taking the Tensor.
_read_hook = None
_write_hook = None
# True during BOTH capture phases (probe run and traced replay); lets stateful code
# (e.g. optimizer lr sync) skip out-of-graph writes that would bake constants.
_capture_active = False


def set_capture_hooks(read_hook, write_hook):
    global _read_hook, _write_hook
    prev = (_read_hook, _write_hook)
    _read_hook, _write_hook = read_hook, write_hook
    return prev


def set_capture_active(v: bool) -> bool:
    global _capture_active
    prev = _capture_active
    _capture_active = bool(v)
    return prev


def in_capture() -> bool:
    return _capture_active


def _is_scalar(x) -> bool:
    return isinstance(x, (int, float, bool, complex)) and not isinstance(x, Tensor)


class Tensor:
    __slots__ = ("_data", "stop_gradient", "_grad", "_grad_node", "_out_slot",
                 "_hooks", "_hook_counter", "name", "persistable", "_stamp",
                 "__weakref__", "__dict__")

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 _internal=False):
        global _creation_clock
        if _internal:
            self._data = data
        else:
            if isinstance(data, Tensor):
                arr = data._data
                if dtype is not None:
                    arr = arr.astype(dtype_mod.convert_dtype(dtype))
                self._data = arr
            else:
                self._data = _to_array(data, dtype)
        self.stop_gradient = stop_gradient
        self._grad = None
        self._grad_node = None
        self._out_slot = 0
        self._hooks = {}
        self._hook_counter = 0
        self.name = ""
        self.persistable = False
        _creation_clock += 1
        self._stamp = _creation_clock

    # ----------------------------------------------------------------- data access

    def _read(self):
        if _read_hook is not None:
            _read_hook(self)
        return self._data

    def _write(self, new_array):
        """Rebind the payload (in-place op / optimizer update / set_value).
        The hook fires BEFORE the rebind so capture can snapshot the old value
        (probe runs are rolled back to keep exactly-once step semantics)."""
        if _write_hook is not None:
            _write_hook(self)
        self._data = new_array

    @property
    def data(self):
        return self

    @data.setter
    def data(self, value):
        v = value._data if isinstance(value, Tensor) else _to_array(value, None)
        self._write(v)

    def set_value(self, value):
        v = value._data if isinstance(value, Tensor) else _to_array(value, self.dtype)
        if tuple(v.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {v.shape} vs {self._data.shape}")
        self._write(jnp.asarray(v, self.dtype))

    # ----------------------------------------------------------------- properties

    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dim(self):
        return self._data.ndim

    def rank(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def T(self):
        return _ops().t(self)

    @property
    def mT(self):
        return _ops().matrix_transpose(self)

    @property
    def place(self):
        from paddle_tpu.device import _place_of
        return _place_of(self._data)

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    @property
    def is_leaf(self):
        return self._grad_node is None

    def get_tensor(self):
        return self

    # ----------------------------------------------------------------- conversion

    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def astype(self, dtype):
        return _ops().cast(self, dtype)

    def cast(self, dtype):
        return _ops().cast(self, dtype)

    def clone(self):
        out = autograd.apply(lambda a: a + 0, self, op_name="clone")
        return out

    def detach(self):
        t = Tensor(self._data, stop_gradient=True, _internal=True)
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def cpu(self):
        return self

    def cuda(self, *a, **k):
        return self

    def to(self, *args, **kwargs):
        # to(dtype) / to(device) / to(device, dtype)
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and (a in dtype_mod._NAME_TO_DTYPE
                                       or a in dtype_mod._ALIASES):
                out = out.astype(a)
            elif isinstance(a, (np.dtype, type)):
                try:
                    out = out.astype(a)
                except TypeError:
                    pass
        return out

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    # ----------------------------------------------------------------- autograd

    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def register_hook(self, hook):
        self._hook_counter += 1
        hid = self._hook_counter
        self._hooks[hid] = hook

        class RemovableHandle:
            def __init__(h, tensor, hid):
                h._t, h._id = tensor, hid

            def remove(h):
                h._t._hooks.pop(h._id, None)

        return RemovableHandle(self, hid)

    def clear_grad(self, set_to_zero=False):
        if (set_to_zero and self._grad is not None
                and isinstance(self._grad, Tensor)):
            self._grad = Tensor(jnp.zeros_like(self._grad._data), _internal=True)
        else:
            # None, or a SelectedRows grad (no dense buffer to zero)
            self._grad = None

    clear_gradient = clear_grad

    def zero_(self):
        self._write(jnp.zeros_like(self._data))
        return self

    def fill_(self, value):
        self._write(jnp.full_like(self._data, value))
        return self

    # ----------------------------------------------------------------- dunders

    def __repr__(self):
        sg = self.stop_gradient
        try:
            body = repr(np.asarray(self._data))
            body = body[body.find("(") + 1: body.rfind(")")] if body.startswith(
                "array") else body
        except Exception:
            body = f"<traced {self._data}>"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"stop_gradient={sg},\n       {body})")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if isinstance(self._data, jax.core.Tracer):
            from paddle_tpu.jit.dy2static import (
                DataDependentControlFlowError, _HINT)
            raise DataDependentControlFlowError(_HINT)
        return bool(self._data)

    def __int__(self):
        return int(self._data)

    def __float__(self):
        return float(self._data)

    def __index__(self):
        if isinstance(self._data, jax.core.Tracer):
            # `range(t)` / `x[t]` on a traced scalar: signal the dy2static
            # retry (the converter lowers for-over-range to a carried while)
            # instead of surfacing jax's ConcretizationTypeError. The raise
            # ALSO inherits TypeError — the index protocol's contract —
            # so numpy/stdlib fallbacks that probe __index__ inside
            # `except TypeError` keep degrading gracefully
            from paddle_tpu.jit.dy2static import (
                DataDependentIndexError, _HINT)
            raise DataDependentIndexError(_HINT)
        return int(self._data)

    def __hash__(self):
        return id(self)

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return object.__format__(self, spec)

    # arithmetic — implemented in paddle_tpu.ops and bound here lazily
    def __add__(self, o):
        return _ops().add(self, o)

    def __radd__(self, o):
        return _ops().add(self, o)

    def __sub__(self, o):
        return _ops().subtract(self, o)

    def __rsub__(self, o):
        return _ops().subtract(o, self)

    def __mul__(self, o):
        return _ops().multiply(self, o)

    def __rmul__(self, o):
        return _ops().multiply(self, o)

    def __truediv__(self, o):
        return _ops().divide(self, o)

    def __rtruediv__(self, o):
        return _ops().divide(o, self)

    def __floordiv__(self, o):
        return _ops().floor_divide(self, o)

    def __rfloordiv__(self, o):
        return _ops().floor_divide(o, self)

    def __mod__(self, o):
        return _ops().remainder(self, o)

    def __rmod__(self, o):
        return _ops().remainder(o, self)

    def __pow__(self, o):
        return _ops().pow(self, o)

    def __rpow__(self, o):
        return _ops().pow(o, self)

    def __matmul__(self, o):
        return _ops().matmul(self, o)

    def __rmatmul__(self, o):
        return _ops().matmul(o, self)

    def __neg__(self):
        return _ops().neg(self)

    def __abs__(self):
        return _ops().abs(self)

    def __invert__(self):
        return _ops().logical_not(self)

    def __and__(self, o):
        return _ops().bitwise_and(self, o)

    def __or__(self, o):
        return _ops().bitwise_or(self, o)

    def __xor__(self, o):
        return _ops().bitwise_xor(self, o)

    def __eq__(self, o):
        return _ops().equal(self, o)

    def __ne__(self, o):
        return _ops().not_equal(self, o)

    def __lt__(self, o):
        return _ops().less_than(self, o)

    def __le__(self, o):
        return _ops().less_equal(self, o)

    def __gt__(self, o):
        return _ops().greater_than(self, o)

    def __ge__(self, o):
        return _ops().greater_equal(self, o)

    # ----------------------------------------------------------------- indexing

    def __getitem__(self, idx):
        return _ops().getitem(self, idx)

    def __setitem__(self, idx, value):
        return _ops().setitem(self, idx, value)

    # in-place arithmetic sugar
    def __iadd__(self, o):
        return _ops().add_(self, o)

    def __isub__(self, o):
        return _ops().subtract_(self, o)

    def __imul__(self, o):
        return _ops().multiply_(self, o)

    def __itruediv__(self, o):
        return _ops().divide_(self, o)


def _to_array(data, dtype):
    """Convert arbitrary host data to a jax array with Paddle's dtype defaults
    (python floats / float64 numpy default to the framework default dtype)."""
    want = dtype_mod.convert_dtype(dtype) if dtype is not None else None
    if isinstance(data, jax.Array) or isinstance(data, jax.core.Tracer):
        return data.astype(want) if want is not None and data.dtype != want else data
    if isinstance(data, (bool, int, float, complex)):
        if want is None:
            if isinstance(data, bool):
                want = dtype_mod.bool_
            elif isinstance(data, int):
                want = dtype_mod.int64
            elif isinstance(data, float):
                want = dtype_mod.get_default_dtype()
            else:
                want = dtype_mod.complex64
        return jnp.asarray(data, want)
    explicit_np = isinstance(data, np.ndarray) or np.isscalar(data)
    arr = np.asarray(data)
    if want is None and arr.dtype == np.float64 and not explicit_np:
        # match paddle.to_tensor: python float lists come in as f64 -> default dtype
        want = dtype_mod.get_default_dtype()
    return jnp.asarray(arr, want) if want is not None else jnp.asarray(arr)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """Create a Tensor from python data / numpy / Tensor (ref: ``paddle.to_tensor``,
    `python/paddle/tensor/creation.py`)."""
    if isinstance(data, Tensor):
        arr = data._data
        if dtype is not None:
            arr = arr.astype(dtype_mod.convert_dtype(dtype))
        return Tensor(arr, stop_gradient=stop_gradient, _internal=True)
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


class Parameter(Tensor):
    """A Tensor that is trainable by default (ref: ``paddle.fluid.framework.Parameter``)."""

    def __init__(self, data, dtype=None, stop_gradient=False, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable
                         if trainable is not None else stop_gradient)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v
