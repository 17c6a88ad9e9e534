"""Distributed environment (reference: python/paddle/distributed/parallel.py
init_parallel_env — TCPStore + env vars PADDLE_TRAINER_*).

TPU-native: multi-controller JAX.  `init_parallel_env` maps onto
jax.distributed.initialize (coordinator rendezvous — the TCPStore analog);
rank/world are process-level (one process per host, all local TPU chips
addressable).  Single-process = trivially initialized.
"""
from __future__ import annotations

import os

import jax

_initialized = False


def init_parallel_env(coordinator_address=None, num_processes=None,
                      process_id=None):
    global _initialized
    if _initialized:
        return
    coord = coordinator_address or os.environ.get("PADDLE_MASTER") or \
        os.environ.get("COORDINATOR_ADDRESS")
    nproc = num_processes or int(os.environ.get(
        "PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE", "1")))
    pid = process_id if process_id is not None else int(os.environ.get(
        "PADDLE_TRAINER_ID", os.environ.get("RANK", "0")))
    if coord and nproc > 1:
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=nproc, process_id=pid)
        except RuntimeError as e:
            # tolerate an earlier direct jax.distributed.initialize (it must
            # run before any backend touch, so callers may do it themselves)
            # — but ONLY when the distributed client really exists; a
            # too-late init with no client is a genuine failure.
            if not jax.distributed.is_initialized():
                raise RuntimeError(
                    "jax.distributed.initialize failed and no distributed "
                    "client exists — init_parallel_env must run before any "
                    "JAX backend use (build tensors only after it)") from e
    _initialized = True


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    return jax.process_count()


def device_count():
    return jax.device_count()


def local_device_count():
    return jax.local_device_count()


def is_initialized():
    return _initialized


class ParallelEnv:
    """reference: paddle.distributed.ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def dev_id(self):
        return 0

    @property
    def nranks(self):
        return get_world_size()

    @property
    def local_rank(self):
        return get_rank()
