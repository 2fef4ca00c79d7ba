"""``paddle.distributed.spawn`` (reference: python/paddle/distributed/
spawn.py) — in-code multi-process launch as an alternative to
``python -m paddle_tpu.distributed.launch``.

Spawns ``nprocs`` fresh python processes (spawn context: fork is unsafe
after jax initializes its thread pools), wiring the same PADDLE_* /
coordination-service env the launcher sets, and runs ``func(*args)`` in
each. ``func`` must be importable (module-level) for pickling.
"""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Optional

from .launch import _free_port, check_one_process_per_chip

__all__ = ["spawn", "ParallelEnv"]


class ParallelEnv:
    """Reference: fluid/dygraph/parallel.py ParallelEnv — the per-process
    view of the distributed environment (rank, world size, endpoints)."""

    def __init__(self):
        self._rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        dev = os.environ.get("FLAGS_selected_tpus",
                             os.environ.get("FLAGS_selected_gpus", "0"))
        # reference ParallelEnv: a comma list selects this process's first
        self._device_id = int(str(dev).split(",")[0])
        self._current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT",
                                                "")
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        self._trainer_endpoints = eps.split(",") if eps else []

    @property
    def rank(self):
        return self._rank

    local_rank = rank

    @property
    def world_size(self):
        return self._world_size

    nranks = world_size

    @property
    def device_id(self):
        return self._device_id

    dev_id = device_id

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._trainer_endpoints


def _worker(func, args):
    func(*args)


def spawn(func, args=(), nprocs: Optional[int] = None, join: bool = True,
          daemon: bool = False, backend: Optional[str] = None, **options):
    """Launch ``func(*args)`` in ``nprocs`` fresh processes with PADDLE_*
    env wired; returns the context (list of processes) when ``join=False``.
    """
    if nprocs is None:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    on_cpu = backend == "cpu" or os.environ.get("PADDLE_SPAWN_CPU") == "1"
    check_one_process_per_chip(
        nprocs, "cpu" if on_cpu else os.environ.get("JAX_PLATFORMS"))
    coordinator = options.get(
        "master", f"127.0.0.1:{_free_port()}")
    endpoints = ",".join(
        f"127.0.0.1:{_free_port()}" for _ in range(nprocs))
    ctx = mp.get_context("spawn")
    procs = []
    # env is set in the PARENT around each start(): spawn children inherit
    # it before unpickling, so modules that initialize jax at import time
    # (the normal `import paddle_tpu` pattern) see the right platform and
    # rank — setting env inside the worker would be too late
    saved = {k: os.environ.get(k) for k in
             ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM", "PADDLE_MASTER",
              "PADDLE_CURRENT_ENDPOINT", "PADDLE_TRAINER_ENDPOINTS",
              "FLAGS_selected_tpus", "JAX_PLATFORMS")}
    try:
        for rank in range(nprocs):
            os.environ["PADDLE_TRAINER_ID"] = str(rank)
            os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
            os.environ["PADDLE_MASTER"] = coordinator
            os.environ["PADDLE_CURRENT_ENDPOINT"] = \
                endpoints.split(",")[rank]
            os.environ["PADDLE_TRAINER_ENDPOINTS"] = endpoints
            os.environ["FLAGS_selected_tpus"] = str(rank)
            if on_cpu:
                os.environ["JAX_PLATFORMS"] = "cpu"
            p = ctx.Process(target=_worker, args=(func, args),
                            daemon=daemon)
            p.start()
            procs.append(p)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not join:
        return procs
    failed = []
    for rank, p in enumerate(procs):
        p.join()
        if p.exitcode != 0:
            failed.append((rank, p.exitcode))
    if failed:
        raise RuntimeError(f"spawn: ranks failed: {failed}")
    return procs
