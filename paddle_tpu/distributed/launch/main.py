"""Launch CLI: multi-process training bringup.

Counterpart of `python -m paddle.distributed.launch`
(`python/paddle/distributed/launch/main.py:18`): the CollectiveController
(`launch/controllers/collective.py:21`) builds a Pod of per-rank Container
subprocesses with `PADDLE_TRAINER_*` env and per-rank log files, a rendezvous
master address, and a watch loop that tears the pod down on failure.

TPU-native differences: one process per HOST. A chip belongs to one process
at a time and nothing here limits a child to its own chip, so the way to use
the four chips of one host is ONE process that owns them all through one jax
runtime (a mesh over ``jax.devices()`` — what ``chip_smoke.py --four-chips``
does). ``--nproc_per_node`` therefore defaults to 1 and is refused above 1
unless the workers run on the CPU platform (``--backend cpu`` or
``JAX_PLATFORMS=cpu``), where it simulates several hosts on one. The
launcher itself never initialises a jax backend: a parent that held the
chip would starve its children. The rendezvous "store" is the JAX
coordination service that ``init_parallel_env`` joins via
``jax.distributed.initialize`` (coordinator = ``PADDLE_MASTER``).

Usage:
    python -m paddle_tpu.distributed.launch \
        [--nnodes N] [--node_rank R] [--nproc_per_node P] \
        [--master host:port] [--log_dir dir] [--max_restarts K] \
        script.py [script args...]
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


class Container:
    """One rank's subprocess (ref `launch/job/container.py`)."""

    def __init__(self, rank, cmd, env, log_path):
        self.rank = rank
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc = None
        self.log_file = None

    def start(self):
        self.log_file = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            self.cmd, env=self.env, stdout=self.log_file,
            stderr=subprocess.STDOUT)

    def poll(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if self.log_file:
            self.log_file.close()
            self.log_file = None


class Pod:
    """Per-node process group + watch loop (ref Controller at
    `launch/controllers/controller.py:161`; PodWatcher restart semantics)."""

    def __init__(self, containers, max_restarts=0, poll_interval=0.5,
                 elastic=None):
        self.containers = containers
        self.max_restarts = max_restarts
        self.poll_interval = poll_interval
        self.restarts = 0
        self.elastic = elastic

    def run(self):
        for c in self.containers:
            c.start()
        try:
            while True:
                codes = [c.poll() for c in self.containers]
                if all(code == 0 for code in codes):
                    return 0
                bad = [(c, code) for c, code in zip(self.containers, codes)
                       if code not in (None, 0)]
                if not bad and self.elastic is not None:
                    # heartbeat staleness counts as death (hung worker) —
                    # ref ElasticManager liveness watch
                    dead = self.elastic.dead_workers()
                    live_ranks = [c.rank for c, code in
                                  zip(self.containers, codes) if code is None]
                    dead = [r for r in dead if r in live_ranks]
                    if dead:
                        sys.stderr.write(
                            f"[launch] rank(s) {dead} heartbeat stale — "
                            "treating as failed\n")
                        # 124: conventional timeout exit code (a numeric code
                        # must flow to sys.exit / supervisor scripting)
                        bad = [(next(c for c in self.containers
                                     if c.rank == dead[0]), 124)]
                if bad:
                    c0, code = bad[0]
                    sys.stderr.write(
                        f"[launch] rank {c0.rank} exited with {code} "
                        f"(log: {c0.log_path})\n")
                    if self.restarts < self.max_restarts:
                        self.restarts += 1
                        sys.stderr.write(
                            f"[launch] restarting pod "
                            f"({self.restarts}/{self.max_restarts})\n")
                        for c in self.containers:
                            c.terminate()
                        if self.elastic is not None:
                            self.elastic.reset()
                        for c in self.containers:
                            c.start()
                        continue
                    for c in self.containers:
                        c.terminate()
                    return code
                time.sleep(self.poll_interval)
        finally:
            for c in self.containers:
                c.terminate()

    def stop(self, *_):
        for c in self.containers:
            c.terminate()
        sys.exit(143)


def build_pod(args, extra):
    nnodes = args.nnodes
    nproc = args.nproc_per_node
    world = nnodes * nproc
    master = args.master
    if master is None:
        master = f"127.0.0.1:{_free_port()}"
    host = master.split(":")[0] if nnodes == 1 else socket.gethostname()
    base_port = _free_port()
    all_eps = []
    for node in range(nnodes):
        for p in range(nproc):
            # endpoints are informational on TPU (the coordination service is
            # the real rendezvous); keep the reference's env contract anyway
            all_eps.append(f"{host}:{base_port + node * nproc + p}")
    os.makedirs(args.log_dir, exist_ok=True)
    containers = []
    for p in range(nproc):
        rank = args.node_rank * nproc + p
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(all_eps),
            "PADDLE_CURRENT_ENDPOINT": all_eps[rank],
            "PADDLE_MASTER": master,
            "PADDLE_LOCAL_RANK": str(p),
            "PADDLE_NNODES": str(nnodes),
            "FLAGS_selected_tpus": str(p),
        })
        if args.backend:
            env["JAX_PLATFORMS"] = args.backend
        if args.elastic_timeout:
            env["PADDLE_HEARTBEAT_FILE"] = os.path.join(
                args.log_dir, f"heartbeat.{rank}")
        cmd = [sys.executable, "-u"] + extra
        log = os.path.join(args.log_dir, f"workerlog.{rank}")
        containers.append(Container(rank, cmd, env, log))
    elastic = None
    if args.elastic_timeout:
        from paddle_tpu.distributed.fleet.elastic import ElasticManager
        elastic = ElasticManager(args.log_dir, world,
                                 timeout=args.elastic_timeout)
    return Pod(containers, max_restarts=args.max_restarts, elastic=elastic)


def launch(argv=None):
    parser = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="multi-process training launcher (ref launch/main.py)")
    parser.add_argument("--nnodes", type=int,
                        default=int(os.environ.get("PADDLE_NNODES", 1)))
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    parser.add_argument("--nproc_per_node", type=int, default=1)
    parser.add_argument("--master", default=os.environ.get("PADDLE_MASTER"))
    parser.add_argument("--log_dir", default="log")
    parser.add_argument("--max_restarts", type=int, default=0)
    parser.add_argument("--backend", default=None,
                        help="force JAX_PLATFORMS for workers (e.g. cpu for "
                             "multi-process simulation on one host)")
    parser.add_argument("--elastic_timeout", type=float, default=0,
                        help="heartbeat staleness (seconds) after which a "
                             "hung worker counts as failed (0 = off); "
                             "restarts follow --max_restarts")
    # split at the first non-flag token (the script): everything after belongs
    # to the training script — parse_known_args would otherwise steal flags
    # like `--backend` the user meant for their script
    argv = list(sys.argv[1:] if argv is None else argv)
    split = next((i for i, a in enumerate(argv)
                  if not a.startswith("-") and (
                      i == 0 or argv[i - 1] not in (
                          "--nnodes", "--node_rank", "--nproc_per_node",
                          "--master", "--log_dir", "--max_restarts",
                          "--backend", "--elastic_timeout"))), len(argv))
    args = parser.parse_args(argv[:split])
    extra = argv[split:]
    if not extra:
        parser.error("no training script given")
    worker_platform = args.backend or os.environ.get("JAX_PLATFORMS", "")
    if args.nproc_per_node > 1 and worker_platform != "cpu":
        parser.error(
            "--nproc_per_node > 1 needs CPU workers (--backend cpu): on an "
            "accelerator the first child takes every local chip and the "
            "others fail or hang; one process drives all chips of a host")
    if args.nnodes > 1 and args.master is None:
        parser.error("--master host:port is required when nnodes > 1 "
                     "(every node must rendezvous at the same coordinator)")
    pod = build_pod(args, extra)
    signal.signal(signal.SIGTERM, pod.stop)
    signal.signal(signal.SIGINT, pod.stop)
    rc = pod.run()
    sys.exit(rc)
