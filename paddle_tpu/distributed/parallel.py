"""Process/env bringup (ref: `python/paddle/distributed/parallel.py:100`
init_parallel_env — TCPStore + ProcessGroupNCCL + global Group + barrier).

TPU-native: `jax.distributed.initialize` joins the multi-controller JAX cluster
(its coordination service plays the TCPStore role); afterwards every process sees
the global device set and collectives compile into programs. Single-process
multi-device needs no init at all.
"""
from __future__ import annotations

import os

import jax
import numpy as np


_initialized = False


class ParallelEnv:
    """ref: `python/paddle/fluid/dygraph/parallel.py` ParallelEnv."""

    def __init__(self):
        self._rank = int(os.environ.get("PADDLE_TRAINER_ID",
                                        os.environ.get("RANK", "0")))
        self._world_size = int(os.environ.get(
            "PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE", "1")))
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        self._endpoints = eps.split(",") if eps else []
        self._current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def rank(self):
        return self._rank

    @property
    def local_rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def nranks(self):
        return self._world_size

    @property
    def dev_id(self):
        return int(os.environ.get("FLAGS_selected_tpus", "0"))

    @property
    def trainer_endpoints(self):
        return self._endpoints

    @property
    def current_endpoint(self):
        return self._current_endpoint


def init_parallel_env():
    """Join the cluster. Multi-process: jax.distributed.initialize using the
    launch env (coordinator = PADDLE_MASTER / first endpoint). Single-process:
    no-op — all local devices are already visible."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    env = ParallelEnv()
    if "PADDLE_TRAINER_ID" in os.environ:
        # a worker started by a launcher: ranks and restarts share one
        # persistent compilation cache
        from paddle_tpu.framework import compile_cache
        compile_cache.enable()
    # NOTE: jax.process_count() would initialise the XLA backend, after which
    # jax.distributed.initialize refuses to run — consult the distributed
    # client state instead
    already_joined = jax.distributed.is_initialized()
    if env.world_size > 1 and not already_joined:
        coordinator = os.environ.get("PADDLE_MASTER") or (
            env.trainer_endpoints[0] if env.trainer_endpoints else None)
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=env.world_size,
            process_id=env.rank,
        )
    hb = os.environ.get("PADDLE_HEARTBEAT_FILE")
    if hb:
        from paddle_tpu.distributed.fleet.elastic import start_heartbeat
        start_heartbeat(hb)
    _initialized = True
    return env


def is_initialized():
    return _initialized or jax.process_count() > 1


def get_rank(group=None):
    if group is not None:
        return group.get_group_rank(jax.process_index())
    return int(os.environ.get("PADDLE_TRAINER_ID", jax.process_index()))


def get_world_size(group=None):
    if group is not None:
        return group.world_size
    ws = os.environ.get("PADDLE_TRAINERS_NUM")
    if ws is not None:
        return int(ws)
    return jax.process_count()


def barrier(group=None):
    """Block until all processes arrive (compiled psum over one scalar)."""
    if jax.process_count() == 1:
        import jax.numpy as jnp
        jnp.zeros(()).block_until_ready()
        return
    from paddle_tpu.distributed import liveness
    if liveness.current() is not None:
        # liveness-guarded fleet (elastic training): the polling barrier
        # converts a dead peer into typed PeerLost instead of wedging in
        # wait_at_barrier (whose expiry this jaxlib cannot survive)
        from paddle_tpu.distributed.collective import _kv_client
        _barrier_seq[0] += 1
        client = _kv_client()
        liveness.kv_barrier(client, f"pbar/{_barrier_seq[0]}",
                            rank=get_rank(), world=jax.process_count(),
                            timeout_ms=60_000)
        if get_rank() == 0 and _barrier_seq[0] >= 3:
            # two-generations-back sweep (same deferral contract as the
            # allgather barriers): seq N completing proves everyone is
            # fully past seq N-2's listing loop
            liveness.kv_barrier_cleanup(client,
                                        f"pbar/{_barrier_seq[0] - 2}")
        return
    from jax.experimental import multihost_utils
    try:
        multihost_utils.sync_global_devices("paddle_tpu_barrier")
    except Exception:  # noqa: BLE001 — backend can't run multiprocess XLA
        # coordination-service barrier: same rendezvous, no compiled program
        # (0.4.x CPU jaxlib cannot compile cross-process computations); the
        # id advances in lockstep because every rank calls barrier() in the
        # same program order
        from paddle_tpu.distributed.collective import _kv_client
        _barrier_seq[0] += 1
        _kv_client().wait_at_barrier(f"ptpu_barrier/{_barrier_seq[0]}",
                                     60_000)


_barrier_seq = [0]
