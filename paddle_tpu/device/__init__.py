"""Device management (ref: `python/paddle/device/__init__.py`, `phi/common/place.h`).

On TPU there is one device kind per process topology; places map onto jax devices.
"""
from __future__ import annotations

import jax


class Place:
    def __init__(self, kind: str, device_id: int = 0):
        self._kind = kind
        self._id = device_id

    def __repr__(self):
        return f"Place({self._kind}:{self._id})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self._kind, self._id) == \
            (other._kind, other._id)

    def __hash__(self):
        return hash((self._kind, self._id))

    def is_tpu_place(self):
        return self._kind == "tpu"

    def is_cpu_place(self):
        return self._kind == "cpu"

    def is_gpu_place(self):
        return False


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id=0):
    return Place("tpu", device_id)


CUDAPlace = TPUPlace  # scripts written for the reference keep working on TPU
CUDAPinnedPlace = CPUPlace
XPUPlace = TPUPlace

_current_device = None


def set_device(device):
    """ref: ``paddle.device.set_device`` — accepts 'cpu', 'tpu', 'tpu:0', and for
    script compatibility 'gpu'/'gpu:0' (routed to the TPU backend)."""
    global _current_device
    dev = str(device)
    _current_device = dev
    return get_device()


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    kind = jax.default_backend()
    return f"{kind}:0" if kind != "cpu" else "cpu"


def get_all_custom_device_type():
    return ["tpu"] if jax.default_backend() == "tpu" else []


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_custom_device(device_type="tpu"):
    return device_type == "tpu"


def is_compiled_with_distribute():
    return True


def device_count():
    return jax.device_count()


def _place_of(arr) -> Place:
    try:
        devs = arr.devices()
        d = next(iter(devs))
        return Place(d.platform, d.id)
    except Exception:
        return Place(jax.default_backend(), 0)


def synchronize(device=None):
    """Block until all queued device work finishes (ref: paddle.device.synchronize)."""
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


class Stream:
    """XLA has no user-visible streams; kept for API parity (no-op)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, stream):
        pass

    def wait_event(self, event):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def stream_guard(stream):
    import contextlib
    return contextlib.nullcontext()


# ------------------------------------------------------- memory introspection
# (ref `paddle.device.cuda.max_memory_allocated` etc., `memory/stats.cc`;
# on TPU the numbers come from the PJRT device's memory_stats)


def _mem_stats(device=None):
    import jax
    d = jax.local_devices()[0] if device is None else device
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


def max_memory_allocated(device=None):
    """Peak bytes in use on the device (ref device/cuda:max_memory_allocated)."""
    return int(_mem_stats(device).get("peak_bytes_in_use", 0))


def memory_allocated(device=None):
    """Current bytes in use (ref device/cuda:memory_allocated)."""
    return int(_mem_stats(device).get("bytes_in_use", 0))


def max_memory_reserved(device=None):
    """Peak bytes reserved by the allocator pool (ref max_memory_reserved)."""
    s = _mem_stats(device)
    return int(s.get("peak_pool_bytes", s.get("peak_bytes_in_use", 0)))


def memory_reserved(device=None):
    s = _mem_stats(device)
    return int(s.get("pool_bytes", s.get("bytes_in_use", 0)))


class cuda:
    """Namespace shim: `paddle.device.cuda.*` memory queries report the
    accelerator (TPU) allocator stats so profiling code ports unchanged."""

    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_reserved = staticmethod(memory_reserved)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)

    @staticmethod
    def empty_cache():
        import gc
        gc.collect()
