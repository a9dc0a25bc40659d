"""ZeRO-style sharding (ref: `python/paddle/distributed/sharding/group_sharded.py:54`
group_sharded_parallel + GroupSharded stages 2/3 under meta_parallel/sharding/).

TPU-native: stage 1/2 = optimizer-state (and grad) arrays laid out sharded over the
'dp'/'sdp' mesh axis; stage 3 = parameters themselves sharded, with XLA's SPMD
partitioner materializing the all-gathers the reference hand-codes as forward hooks
(`group_sharded_stage3.py:185`). Under a captured train step this is pure sharding
annotation — ~50 lines vs the reference's ~2.5k.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.mesh import get_mesh, auto_mesh


def _shard_spec_for(shape, mesh, axis):
    """Shard the largest dim divisible by the axis size; replicate otherwise."""
    size = mesh.shape[axis]
    dims = sorted(range(len(shape)), key=lambda i: -shape[i])
    for d in dims:
        if shape[d] % size == 0 and shape[d] >= size:
            spec = [None] * len(shape)
            spec[d] = axis
            return PartitionSpec(*spec)
    return PartitionSpec()


def _place(t: Tensor, sharding):
    if not isinstance(t._data, jax.core.Tracer):
        t._write(jax.device_put(t._data, sharding))


def zero1_partition_spec(shape, mesh, axis="dp", base_spec=None):
    """ZeRO-1 placement for ONE optimizer-state leaf (arxiv 2004.13336:
    shard the weight-update/optimizer-state over the data-parallel axis).

    Picks the LARGEST dim that the param's own sharding (``base_spec`` —
    its mp/sp placement, which moments must mirror) leaves unsharded and
    that divides by the axis size, and assigns ``axis`` to it, so an
    mp-sharded weight gets dp x mp - sharded moments. Returns None when no
    dim qualifies or the axis has size 1 (replicate: nothing to win)."""
    size = mesh.shape.get(axis, 1) if mesh is not None else 1
    if size <= 1 or not shape:
        return None
    base = list(base_spec) if base_spec is not None else []
    base = base + [None] * (len(shape) - len(base))
    cands = [d for d in range(len(shape))
             if base[d] is None and shape[d] % size == 0 and shape[d] >= size]
    if not cands:
        return None
    d = max(cands, key=lambda i: shape[i])
    base[d] = axis
    return PartitionSpec(*base)


def shard_optimizer_states(optimizer, mesh=None, axis="dp"):
    """Stage-1/2: lay optimizer accumulators out sharded over the data axis."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return optimizer
    orig_accumulator = optimizer._accumulator

    def sharded_accumulator(name, p, init=None, dtype=None):
        t = orig_accumulator(name, p, init=init, dtype=dtype)
        spec = _shard_spec_for(tuple(t._data.shape), mesh, axis)
        _place(t, NamedSharding(mesh, spec))
        return t

    optimizer._accumulator = sharded_accumulator
    return optimizer


def shard_parameters(model, mesh=None, axis="dp"):
    """Stage-3: shard the parameter arrays themselves. Parameters that
    already carry a named mesh sharding (a pipeline's 'pp'-stacked stage
    params, an mpu layer's 'mp' shard) are left in place — stage3 composes
    with model parallelism by sharding the REMAINING (replicated) params
    over the data axis, not by fighting placements the model chose."""
    mesh = mesh or get_mesh()
    if mesh is None:
        return model
    for p in model.parameters():
        sh = getattr(p._data, "sharding", None)
        if isinstance(sh, NamedSharding) and any(
                s is not None for s in sh.spec):
            continue
        spec = _shard_spec_for(tuple(p._data.shape), mesh, axis)
        _place(p, NamedSharding(mesh, spec))
    return model


def host_memory_kind(devices):
    """``"pinned_host"`` where the devices have a distinct host memory tier
    (TPU), else None: a CPU device reports its ONLY memory as
    ``unpinned_host``, so there is nothing to offload to and state stays in
    default memory (numerics unchanged)."""
    kinds = {m.kind for d in devices for m in d.addressable_memories()}
    return "pinned_host" if "pinned_host" in kinds else None


def _host_device_shardings(shape, mesh, axis):
    """(host, device) sharding pair for one state array. Without a host
    tier (:func:`host_memory_kind` is None) the host sharding IS the device
    sharding, so the CPU tests still check stage-3 numerics."""
    if mesh is not None:
        kind = host_memory_kind(mesh.devices.flat)
        spec = _shard_spec_for(shape, mesh, axis)
        host = (NamedSharding(mesh, spec, memory_kind=kind) if kind
                else NamedSharding(mesh, spec))
        return host, NamedSharding(mesh, spec)
    dev = jax.devices()[0]
    kind = host_memory_kind([dev])
    host = (jax.sharding.SingleDeviceSharding(dev, memory_kind=kind) if kind
            else jax.sharding.SingleDeviceSharding(dev))
    return host, jax.sharding.SingleDeviceSharding(dev)


def _flag_offload(t, mesh, axis):
    host, devsh = _host_device_shardings(tuple(t._data.shape), mesh, axis)
    t._offload_host = host
    t._offload_device = devsh
    return t


def offload_optimizer_states(optimizer, mesh=None, axis="dp"):
    """Stage-3 host offload (ref `group_sharded_stage3.py:61` offload=True,
    `_param2buffer` :133): optimizer accumulators, fused flat state buffers
    and fp32 master weights RESIDE in host memory (``pinned_host``) between
    steps. The step runner fetches them to device memory for the update and
    pushes the new values home afterwards — donate+fetch, so HBM holds
    optimizer state only transiently during the step. The compiled program
    itself stays memory-kind-free (portable across backends; the transfers
    happen at the call boundary, see jit/static_function.py)."""
    mesh = mesh or get_mesh()
    if getattr(optimizer, "_offloaded_states", None) is not None:
        return optimizer
    optimizer._offloaded_states = []

    def collect():
        """The CURRENT state tensors — recomputed every step so that
        set_state_dict (which rebinds whole accumulator dicts) and fused
        freeze/unfreeze rebuilds self-heal instead of leaving stale entries
        shuttling dead arrays (round-3 review finding)."""
        out = []
        for store in optimizer._accumulators.values():
            out.extend(store.values())
        out.extend(optimizer._master_weights.values())
        for meta in getattr(optimizer, "_fused_parts", {}).values():
            out.extend(meta["states"])
        for t in out:
            if not hasattr(t, "_offload_host"):
                _flag_offload(t, mesh, axis)
        optimizer._offloaded_states = out
        return out

    orig_step = optimizer.step

    def step():
        # eager fetch: concrete host-resident state moves to device before
        # the update math touches it (inside a capture probe the arrays are
        # concrete at entry too, so the probe never sees host avals)
        for t in collect():
            d = t._data
            if not isinstance(d, jax.core.Tracer) and \
                    getattr(d.sharding, "memory_kind", None) == "pinned_host":
                t._data = jax.device_put(d, t._offload_device)
        out = orig_step()
        # eager push-back over the post-step state set (lazy creation happens
        # inside the step); during capture the new values are tracers and the
        # compiled-step runner does the push-back instead
        for t in collect():
            d = t._data
            if not isinstance(d, jax.core.Tracer) and \
                    getattr(d.sharding, "memory_kind", None) != "pinned_host":
                t._data = jax.device_put(d, t._offload_host)
        return out

    optimizer.step = step
    return optimizer


def group_sharded_parallel(model, optimizer, level, scaler=None, group=None,
                           offload=False, sync_buffers=False, buffer_max_size=2**23,
                           segment_size=2**20, sync_comm=False,
                           dp_group=None, exclude_layer=None):
    """ref signature: `distributed/sharding/group_sharded.py:54`.
    level: 'os' (stage1), 'os_g' (stage2), 'p_g_os' (stage3).
    ``offload=True`` additionally homes optimizer state in host memory
    (works on a single device too, like the reference's CPU offload)."""
    mesh = get_mesh()
    if mesh is None and len(jax.devices()) > 1:
        mesh = auto_mesh(dp=len(jax.devices()))
    if mesh is not None:
        if level in ("os", "os_g", "p_g_os"):
            shard_optimizer_states(optimizer, mesh)
        if level == "p_g_os":
            shard_parameters(model, mesh)
    if offload:
        offload_optimizer_states(optimizer, mesh)
    return model, optimizer, scaler


def save_group_sharded_model(model, output, optimizer=None):
    """ref: `group_sharded.py:222` — gather shards and save one logical ckpt.
    Global arrays already hold the full logical value, so plain save works."""
    import os
    from paddle_tpu.framework import io as fio
    os.makedirs(output, exist_ok=True) if not output.endswith(".pdparams") else None
    base = output if not os.path.isdir(output) else os.path.join(output, "model")
    fio.save(model.state_dict(), base + ".pdparams")
    if optimizer is not None:
        fio.save(optimizer.state_dict(), base + ".pdopt")
