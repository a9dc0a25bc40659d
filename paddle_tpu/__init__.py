"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's capabilities.

Built from scratch on JAX/XLA/Pallas/pjit (NOT a port): eager mode is a tape of
jax.vjp closures over immutable device arrays; ``to_static`` captures whole train
steps into single donated XLA programs; parallelism is a device mesh with compiled
collectives instead of NCCL process groups. Blueprint: SURVEY.md at the repo root.
"""
from __future__ import annotations

import sys as _sys
import time as _time

# the registry first (stdlib only): its epoch is the stamp `package.import`
# starts from, and whether jax was loaded before us says which import this
# was (well under a second after jax, several seconds with it)
_JAX_PRELOADED = "jax" in _sys.modules
from paddle_tpu.observability import _EPOCH as _T_IMPORT  # noqa: E402

import jax as _jax  # noqa: E402

# float64/int64 must exist as real dtypes (the reference supports them; grad checks
# need f64 on CPU). Defaults remain float32 — see core/dtype.py.
_jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

from paddle_tpu.core import dtype as _dtype_mod
from paddle_tpu.core.dtype import (  # noqa: F401
    bool_ as bool, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, set_default_dtype, get_default_dtype, finfo,
    iinfo,
)
from paddle_tpu.core.tensor import Tensor, to_tensor, Parameter  # noqa: F401
from paddle_tpu.core.autograd import (  # noqa: F401
    no_grad, enable_grad, set_grad_enabled, is_grad_enabled, grad,
)
from paddle_tpu.ops import *  # noqa: F401,F403
from paddle_tpu.ops.random import seed, get_rng_state, set_rng_state  # noqa: F401

from paddle_tpu import device  # noqa: F401
from paddle_tpu.device import (  # noqa: F401
    CPUPlace, CUDAPlace, TPUPlace, CUDAPinnedPlace, set_device, get_device,
    is_compiled_with_cuda, is_compiled_with_rocm, is_compiled_with_xpu,
)

from paddle_tpu import nn  # noqa: F401
from paddle_tpu import optimizer  # noqa: F401
from paddle_tpu import amp  # noqa: F401
from paddle_tpu import io  # noqa: F401
from paddle_tpu import autograd  # noqa: F401
from paddle_tpu import jit  # noqa: F401
from paddle_tpu import framework  # noqa: F401
from paddle_tpu.framework.io import save, load  # noqa: F401
from paddle_tpu.framework.flags import get_flags, set_flags  # noqa: F401
from paddle_tpu.autograd import PyLayer  # noqa: F401
from paddle_tpu import vision  # noqa: F401
from paddle_tpu import metric  # noqa: F401
from paddle_tpu import distributed  # noqa: F401
from paddle_tpu import distribution  # noqa: F401
from paddle_tpu import observability  # noqa: F401
from paddle_tpu import profiler  # noqa: F401
from paddle_tpu import incubate  # noqa: F401
from paddle_tpu.hapi.model import Model  # noqa: F401
from paddle_tpu.distributed.parallel_wrappers import DataParallel  # noqa: F401
from paddle_tpu.hapi import summary  # noqa: F401
from paddle_tpu import sparse  # noqa: F401
from paddle_tpu import inference  # noqa: F401
from paddle_tpu import audio  # noqa: F401
from paddle_tpu import quantization  # noqa: F401
from paddle_tpu import utils  # noqa: F401
from paddle_tpu import fft  # noqa: F401
from paddle_tpu import signal  # noqa: F401
from paddle_tpu import geometric  # noqa: F401
from paddle_tpu import text  # noqa: F401
from paddle_tpu import strings  # noqa: F401
from paddle_tpu import onnx  # noqa: F401
from paddle_tpu import regularizer  # noqa: F401
from paddle_tpu import hub  # noqa: F401
from paddle_tpu import static  # noqa: F401
from paddle_tpu.hapi import callbacks  # noqa: F401
from paddle_tpu import version  # noqa: F401
from paddle_tpu import sysconfig  # noqa: F401
from paddle_tpu import tensor  # noqa: F401

from paddle_tpu.nn.functional.common import linear  # noqa: F401  (paddle exposes it)

observability.metrics.add_span(
    "package.import", _T_IMPORT, _time.perf_counter() - _T_IMPORT,
    cat="startup", args={"jax_preloaded": _JAX_PRELOADED})


def disable_static(place=None):
    """Dygraph is the only mode; kept for API parity (ref: paddle.disable_static)."""


def enable_static():
    raise NotImplementedError(
        "paddle_tpu has no ProgramDesc static graph; use paddle_tpu.jit.to_static "
        "to capture a function into one compiled XLA program instead")


def in_dynamic_mode():
    return True


# paddle exposes creation/math at top level already via ops import; a few extras:
def is_grad_enabled_():  # pragma: no cover - alias safety
    return is_grad_enabled()


def batch(reader, batch_size, drop_last=False):
    """Batch a sample generator (ref `python/paddle/batch.py`)."""
    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched
