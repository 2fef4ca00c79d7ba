"""``python -m paddle_tpu.distributed.launch`` — multi-process job launcher.

Reference: python/paddle/distributed/launch/main.py:18 (Context → controller
→ Job/Pod/Container spawn + watch), fleet/elastic/manager.py:131 (restart
policy, exit-code-101 restart signal), launch/controllers/watcher.py.

TPU-native shape: the reference spawns ONE process per GPU; under jax one
process drives all local chips, so the natural unit is one process per
host (``--nproc_per_node`` stays available for CPU-mesh testing and
multi-plane hosts). The launcher wires the PADDLE_* env that
``env.init_parallel_env`` already reads — PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM / PADDLE_MASTER — so the rendezvous is jax's PjRt
coordination service instead of a TCPStore. Elastic policy: a child that
exits with code 101 (the reference's restart signal) or any non-zero code
triggers a full local respawn up to ``--max_restarts`` times; rank logs
stream to ``--log_dir``.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["main", "launch_local"]

# the reference's elastic manager treats 101 as "please restart me"
# (fleet/elastic/manager.py ELASTIC_AUTO_PARALLEL_EXIT_CODE area)
RESTART_EXIT_CODE = 101


from ..elastic import _free_port  # shared bind-port-0 helper  # noqa: E402


def check_one_process_per_chip(nprocs: int, platforms: Optional[str]) -> None:
    """Refuse ``nprocs > 1`` local processes that would all open the TPU.

    A chip belongs to one process at a time, and nothing here maps a
    rank to a visible chip (``FLAGS_selected_tpus`` is read by
    ``ParallelEnv`` only): on a TPU host every child would claim ALL
    local chips and all but the first would fail or hang at backend
    start-up. The supported shape is one process driving all local chips
    (the module docstring). ``platforms`` is the ``JAX_PLATFORMS`` value
    the children get: an explicit list decides; with none, jax picks the
    TPU exactly when libtpu and a chip's device node are present."""
    if nprocs <= 1:
        return
    if platforms:
        on_tpu = "tpu" in platforms.split(",")
    else:
        import glob
        import importlib.util
        on_tpu = importlib.util.find_spec("libtpu") is not None and bool(
            glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))
    if on_tpu:
        raise RuntimeError(
            f"{nprocs} processes per host on a TPU backend: every process "
            f"would claim all local chips and all but one would fail or "
            f"hang. Drive the local chips from ONE process "
            f"(nprocs / --nproc_per_node 1 with a device mesh), or run "
            f"the extra processes on the CPU backend")


def _parse(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch a multi-process distributed job "
                    "(reference: paddle.distributed.launch)")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of hosts in the job")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")),
                   help="this host's index")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1 = jax-native: one process "
                        "drives all local chips)")
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER"),
                   help="coordinator ip:port (default: auto on "
                        "single-host)")
    p.add_argument("--log_dir", type=str, default=None,
                   help="write per-rank logs here instead of inheriting "
                        "stdio")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic: respawn the local pod up to N times on "
                        "child failure")
    p.add_argument("--elastic_master", type=str, default=None,
                   help="elastic membership master host:port "
                        "(node_rank 0 hosts it); enables heartbeat "
                        "membership + rebuild-on-node-change")
    p.add_argument("--elastic_ttl", type=float, default=6.0,
                   help="seconds without heartbeats before a node is "
                        "declared dead")
    p.add_argument("--backend", type=str, default=None,
                   help="override JAX_PLATFORMS for children (e.g. cpu "
                        "for mesh tests)")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


class _Pod:
    """The local process group (reference launch/job/pod.py Container
    set). ``membership`` (elastic) overrides nnodes/node_rank/master with
    the current alive-node view."""

    def __init__(self, args, membership=None):
        self.args = args
        self.membership = membership
        self.procs: List[subprocess.Popen] = []
        self.logs = []

    def spawn(self):
        a = self.args
        nnodes, node_rank, master = a.nnodes, a.node_rank, a.master
        if self.membership is not None:
            nnodes, node_rank, master = self.membership
        world = nnodes * a.nproc_per_node
        check_one_process_per_chip(
            a.nproc_per_node,
            a.backend or os.environ.get("JAX_PLATFORMS"))
        if master is None:
            if nnodes > 1:
                raise SystemExit(
                    "--master ip:port is required for multi-host jobs")
            master = f"127.0.0.1:{_free_port()}"
        for local in range(a.nproc_per_node):
            rank = node_rank * a.nproc_per_node + local
            env = dict(os.environ)
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_MASTER": master,
                "PADDLE_LOCAL_RANK": str(local),
                "PADDLE_NNODES": str(nnodes),
            })
            if a.backend:
                env["JAX_PLATFORMS"] = a.backend
            cmd = [sys.executable, a.training_script,
                   *a.training_script_args]
            if a.log_dir:
                os.makedirs(a.log_dir, exist_ok=True)
                logf = open(os.path.join(
                    a.log_dir, f"workerlog.{rank}"), "ab")
                self.logs.append(logf)
                proc = subprocess.Popen(cmd, env=env, stdout=logf,
                                        stderr=subprocess.STDOUT)
            else:
                proc = subprocess.Popen(cmd, env=env)
            self.procs.append(proc)

    def poll(self):
        """Returns None while running, else the pod's exit code (first
        failure wins; 0 when all exited cleanly)."""
        codes = [p.poll() for p in self.procs]
        for c in codes:
            if c is not None and c != 0:
                return c
        if all(c == 0 for c in codes):
            return 0
        return None

    def terminate(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        for f in self.logs:
            f.close()
        self.procs, self.logs = [], []


def launch_local(argv: Optional[List[str]] = None) -> int:
    """Spawn + watch + elastic-restart loop. Returns the job exit code."""
    args = _parse(argv)
    if args.elastic_master:
        return _launch_elastic(args)
    restarts = 0
    while True:
        pod = _Pod(args)
        pod.spawn()
        try:
            while True:
                code = pod.poll()
                if code is not None:
                    break
                time.sleep(0.2)
        except KeyboardInterrupt:
            pod.terminate()
            return 130
        # terminate() also closes/flushes the workerlog handles, so run
        # it on EVERY exit path (clean exit included)
        pod.terminate()
        if code == 0:
            return 0
        if restarts < args.max_restarts:
            restarts += 1
            print(f"[launch] child failed with code {code}; elastic "
                  f"restart {restarts}/{args.max_restarts}",
                  file=sys.stderr, flush=True)
            continue
        return int(code)


def _launch_elastic(args) -> int:
    """Membership-driven watch loop (reference:
    fleet/elastic/manager.py): register with the master, heartbeat,
    rebuild the pod whenever the alive-node set changes — ranks and world
    size rewritten from the sorted node list, a fresh PjRt port per
    membership version. Node_rank 0 hosts the master in-process (the
    documented single-master trade-off vs the reference's external ETCD).
    """
    from ..elastic import ElasticAgent, ElasticMaster, sort_nodes

    host, port = args.elastic_master.rsplit(":", 1)
    master = None
    if args.node_rank == 0:
        master = ElasticMaster(int(port), ttl=args.elastic_ttl)
    node_id = f"{host if args.node_rank == 0 else socket.gethostname()}" \
              f"#{args.node_rank}"
    agent = ElasticAgent(args.elastic_master, node_id)
    agent.register()
    agent.start_heartbeat()
    restarts = 0
    code = 1
    # a peer whose master is gone for this long gives up instead of
    # spinning forever (the single-master fate-sharing boundary)
    master_lost_after = max(3 * args.elastic_ttl, 30.0)
    try:
        while True:
            try:
                st = agent.status()
            except (OSError, ValueError):
                # transient blip at rebuild time: retry via register's
                # backoff rather than crashing the launcher
                st = agent.register()
            if agent.node_id not in st["nodes"]:
                st = agent.register()  # expired while rebuilding
            version = st["version"]
            # node_rank-suffix order, NOT lexicographic: the node hosting
            # the master (node_rank 0) must map to global rank 0 so the
            # PjRt coordinator binds on its own host
            nodes = sort_nodes(st["nodes"])
            membership = (len(nodes), nodes.index(agent.node_id),
                          f"{host}:{st['pjrt_port']}")
            print(f"[launch] elastic v{version}: {len(nodes)} node(s), "
                  f"this={membership[1]}", file=sys.stderr, flush=True)
            pod = _Pod(args, membership=membership)
            pod.spawn()
            rebuild = False
            master_lost_since = None
            try:
                while True:
                    code = pod.poll()
                    if code is not None:
                        break
                    try:
                        cur = agent.status()
                        master_lost_since = None
                    except (OSError, ValueError):
                        cur = None  # master briefly unreachable: keep on
                        now = time.time()
                        if master_lost_since is None:
                            master_lost_since = now
                        elif now - master_lost_since > master_lost_after:
                            print("[launch] elastic master unreachable "
                                  f"for {master_lost_after:.0f}s; "
                                  "terminating", file=sys.stderr,
                                  flush=True)
                            pod.terminate()
                            return 1
                    if cur is not None and cur["version"] != version:
                        # a node died (TTL lapse) or joined: rebuild with
                        # rewritten world size/endpoints
                        print("[launch] membership changed "
                              f"(v{version} -> v{cur['version']}); "
                              "rebuilding", file=sys.stderr, flush=True)
                        rebuild = True
                        break
                    time.sleep(0.3)
            except KeyboardInterrupt:
                pod.terminate()
                return 130
            pod.terminate()
            if rebuild:
                continue
            if code == 0:
                return 0
            if restarts < args.max_restarts:
                restarts += 1
                print(f"[launch] child failed with code {code}; elastic "
                      f"restart {restarts}/{args.max_restarts}",
                      file=sys.stderr, flush=True)
                continue
            return int(code)
    finally:
        agent.stop_heartbeat()
        if code == 0:
            # clean exit leaves the membership explicitly; a FAILED node
            # just stops heartbeating, so peers detect it through the TTL
            # sweep — the actual dead-rank path (reference: ETCD lease
            # expiry, manager.py:131)
            agent.leave()
        if master is not None and code == 0:
            # clean job end: wait briefly so peers can observe the leave
            time.sleep(0.5)
        if master is not None:
            master.shutdown()


def main():
    raise SystemExit(launch_local())
