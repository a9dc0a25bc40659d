"""Runtime flag registry (ref: gflags system `paddle/fluid/platform/flags.cc` with
`ExportedFlagInfoMap`, python `get_flags/set_flags` at
`python/paddle/fluid/framework.py:7611,7636`).

Flags are read from env ``FLAGS_*`` at import and mutable at runtime.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable


@dataclass
class FlagInfo:
    name: str
    default: Any
    doc: str
    parser: Callable[[str], Any]
    value: Any = None
    on_change: Callable[[Any], None] | None = None


_REGISTRY: dict[str, FlagInfo] = {}


def _parse_bool(s):
    return str(s).lower() in ("1", "true", "yes", "on")


def define_flag(name, default, doc="", parser=None, on_change=None):
    if parser is None:
        if isinstance(default, bool):
            parser = _parse_bool
        elif isinstance(default, int):
            parser = int
        elif isinstance(default, float):
            parser = float
        else:
            parser = str
    info = FlagInfo(name, default, doc, parser, default, on_change)
    env = os.environ.get(f"FLAGS_{name}")
    _REGISTRY[name] = info
    if env is not None:
        info.value = parser(env)
        if on_change:
            # env-set flags must fire their wiring too (FLAGS_check_nan_inf=1
            # python train.py is the canonical gflags usage)
            on_change(info.value)
    return info


def get_flags(flags):
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        n = n.removeprefix("FLAGS_")
        if n not in _REGISTRY:
            raise ValueError(f"unknown flag {n}")
        out[f"FLAGS_{n}"] = _REGISTRY[n].value
    return out


def set_flags(flags: dict):
    for k, v in flags.items():
        n = k.removeprefix("FLAGS_")
        if n not in _REGISTRY:
            raise ValueError(f"unknown flag {n}")
        info = _REGISTRY[n]
        info.value = info.parser(v) if isinstance(v, str) else v
        if info.on_change:
            info.on_change(info.value)


def flag_value(name):
    return _REGISTRY[name].value


# ---- core flags (TPU-meaningful subset of the reference's 77) -------------------
def _sync_debug_hooks(_value=None):
    """check_nan_inf / benchmark wiring: a cheap module-level switch on the
    autograd dispatch path (eager per-op checks) + jax_debug_nans for code
    under jit (the compiled-path analog of the reference's per-op detector,
    `eager/nan_inf_utils.cc` / `nan_inf_utils_detail.cc`)."""
    from paddle_tpu.core import autograd
    autograd._DEBUG_CHECKS = bool(
        _REGISTRY["check_nan_inf"].value or _REGISTRY["benchmark"].value)
    import jax
    jax.config.update("jax_debug_nans", bool(_REGISTRY["check_nan_inf"].value))


define_flag("check_nan_inf", False,
            "check outputs of every op for nan/inf (ref FLAGS_check_nan_inf)",
            on_change=_sync_debug_hooks)
define_flag("benchmark", False, "sync after each op for timing",
            on_change=_sync_debug_hooks)
define_flag("paddle_num_threads", 1, "host compute threads")
define_flag("use_bfloat16_matmul", False,
            "run fp32 matmuls in bf16 on the MXU (TPU-specific speed knob)")
define_flag("seed", 0, "global random seed (0 = nondeterministic)")
define_flag("log_level", "INFO", "framework log level")
define_flag("allocator_strategy", "xla",
            "kept for compat; XLA/PJRT owns device memory on TPU")
define_flag("eager_delete_tensor_gb", 0.0, "kept for compat; XLA GC is automatic")
define_flag("tpu_donate_buffers", True,
            "donate param/opt-state buffers in captured train steps")
define_flag("tpu_fused_optimizer", True,
            "multi-tensor optimizer path: one fused update over concatenated "
            "flat param/state buffers per dtype group (ref fused adam kernels)")
define_flag("moe_dispatch", "auto",
            "MoE token dispatch path: auto | scatter (index scatter/gather, "
            "O(N*K*D) movement — the global_scatter analog) | einsum "
            "(one-hot [N,E,C] einsum, O(N*E*C*D) FLOPs; fine at tiny scale)")
define_flag("dataloader_auto_fallback", True,
            "drop multi-worker DataLoader to the in-process path on "
            "single-core hosts, where workers and the training loop share "
            "one core (not measured on the current chip). Set False only "
            "to force workers for measurement, or on multi-core hosts "
            "where decode parallelism is real")
define_flag("dataloader_mp_method", "spawn",
            "multiprocessing start method for DataLoader workers: spawn "
            "(default — fork is unsafe under the multithreaded JAX runtime) "
            "| forkserver | fork (requires a single-threaded parent; kept "
            "for unpicklable datasets at the caller's risk)")
define_flag("tpu_flash_impl", "auto",
            "flash-attention backend: auto (measured per-shape selection, "
            "kernels/registry.py — ref phi's AutoTuneCache) | authored "
            "(in-repo Pallas fwd+bwd kernels, "
            "kernels/pallas/flash_attention.py) | xla (pure-XLA flash-style "
            "custom vjp)")
define_flag("tpu_paged_impl", "auto",
            "paged-attention decode backend (serving engine hot kernel): "
            "auto (measured per-signature selection on real TPU, xla "
            "elsewhere — kernels/registry.py) | xla (gather + masked f32 "
            "softmax reference, traffic scales with pool capacity) | pallas "
            "(authored ragged paged-attention kernel, kernels/pallas/"
            "paged_attention.py — page loop bounded by each sequence's true "
            "length; interpret mode off-TPU, parity tests only)")
define_flag("tpu_prefill_impl", "auto",
            "ragged prefill-attention backend (chunked prefill + prefix "
            "tails + the PTKS1 prefill-worker stream): auto (measured "
            "per-signature selection via the kernel registry, "
            "kernels/registry.py) | xla (paged gather + absolute-position "
            "masked softmax, traffic scales with pool capacity) | pallas "
            "(authored ragged prefill kernel, kernels/pallas/"
            "prefill_attention.py — page loop bounded by each request's "
            "true context; interpret mode off-TPU, parity tests only)")
define_flag("dy2static_max_trip_count", 0,
            "when > 0, TRACED loops produced by dy2static conversion "
            "(data-dependent while / for-over-range) lower to a bounded "
            "lax.scan of this many steps with an active mask — making them "
            "REVERSE-DIFFERENTIABLE (the TPU analog of the reference's "
            "WhileGradOp forward replay, operators/controlflow/"
            "while_op.cc:348) at the cost of always running the bound "
            "(a traced loop whose true trip count exceeds it is TRUNCATED — "
            "choose a real upper bound; concrete loops are never capped). "
            "0 = unbounded lax.while, forward-only (loud error under grad)")
