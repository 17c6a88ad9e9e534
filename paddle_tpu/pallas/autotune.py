"""Kernel autotune: block-size selection for Pallas kernels.

Reference capability: runtime algorithm-selection cache
(paddle/phi/kernels/autotune/cache.h, switch_autotune.h — conv algo and
transpose tuning cached per shape key).  TPU-native realization: a
per-(kernel, shape-key) table of Pallas block sizes for this process,
filled by an explicit timed sweep (`sweep()`).  Nothing is read from or
written to a file outside the checkout: a kernel parameter comes from
the kernel's own heuristics (``flash_attention._pick_blocks``), from a
sweep this process ran, or from a table git tracks — so two runs of one
commit compile the same programs.
"""
from __future__ import annotations

import time

_CACHE: dict[str, dict[str, tuple]] = {}


def _key(shape_key):
    return ",".join(str(int(x)) for x in shape_key)


def lookup(op, shape_key):
    """Recorded config for (op, shape_key), or None."""
    return _CACHE.get(op, {}).get(_key(shape_key))


def record(op, shape_key, config):
    _CACHE.setdefault(op, {})[_key(shape_key)] = tuple(config)


def clear():
    _CACHE.clear()


def sweep(op, shape_key, candidates, run, *, warmup=1, iters=3):
    """Time `run(config)` for each candidate, record and return the winner.

    `run` must block until the device work is done (e.g. via
    jax.block_until_ready).  A candidate that fails to compile or run
    raises: a block shape the chip refuses is a finding, not a slow
    candidate.
    """
    cached = lookup(op, shape_key)
    if cached is not None:
        return cached
    best, best_t = None, float("inf")
    for cfg in candidates:
        for _ in range(warmup):
            run(cfg)
        t0 = time.perf_counter()
        for _ in range(iters):
            run(cfg)
        dt = (time.perf_counter() - t0) / iters
        if dt < best_t:
            best, best_t = cfg, dt
    if best is None:
        raise ValueError(f"autotune sweep for {op}{shape_key}: no candidates")
    record(op, shape_key, best)
    return best
