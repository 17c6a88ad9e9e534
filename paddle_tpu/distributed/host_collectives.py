"""Host-mediated collectives: the CPU/gloo fallback lane.

Reference capability: ``ProcessGroupGloo`` — the reference serves CPU
processes a real collective backend when NCCL has no device to drive.
TPU-native realization: eager collectives normally compile INTO the XLA
program (`collective._multiproc_collective`), but some backends cannot
execute cross-process programs at all (jaxlib's CPU client raises
``Multiprocess computations aren't implemented``).  This module supplies
the same semantics at host level: every rank posts its contribution into
a shared KV store under ``{job}/hc/g{gid}/s{seq}/r{rank}``, polls for
its peers' contributions, stacks them, and derives the op result locally
(all_reduce = reduce over the stacked axis, all_to_all = transpose — the
same math `_multiproc_collective`'s XLA programs encode).

Two properties matter here beyond correctness:

- the poll loop is a *Python-level* blocking point, so the collective
  watchdog (`distributed/watchdog.py`) can abort a gather stuck on a
  dead peer with an async-raised `CollectiveTimeoutError`/
  `PeerFailureError` — unlike a C-blocked XLA transfer, which needs the
  watchdog's hard-abort escalation;
- the store is pluggable and defaults to whatever the job already has:
  the launch controllers' guardian store (``PADDLE_GUARDIAN_STORE`` /
  ``PADDLE_GUARDIAN_DIR``), falling back to the jax coordination
  service's KV (`CoordKVStore`) that every multi-controller job carries
  — which is per-incarnation by construction, so a relaunched job never
  reads a dead incarnation's stale contributions.

Selection: ``FLAGS_collective_backend`` = ``auto`` (XLA first, fall back
on the specific "multiprocess not implemented" failure) | ``xla`` |
``host``.
"""
from __future__ import annotations

import io
import os
import threading
import time

import numpy as np


class CoordKVStore:
    """TCPStore-shaped KV (set/get/list_prefix/delete_key) over the jax
    coordination-service client — the rendezvous channel
    ``jax.distributed.initialize`` already established, so host
    collectives and the error trap need no extra infrastructure."""

    def __init__(self, client):
        self._client = client

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        self._client.key_value_set_bytes(key, bytes(value),
                                         allow_overwrite=True)

    def get(self, key, default=None):
        try:
            return self._client.blocking_key_value_get_bytes(key, 1)
        except Exception:
            return default

    def list_prefix(self, prefix):
        try:
            pairs = self._client.key_value_dir_get_bytes(
                prefix.rstrip("/"))
        except Exception:
            return {}
        return {k: v for k, v in pairs if k.startswith(prefix)}

    def delete_key(self, key):
        try:
            self._client.key_value_delete(key)
        except Exception:
            pass

    def close(self):
        pass


def coord_kv_store():
    """The coordination-service KV, or None outside a multi-controller
    job."""
    # jax 0.9.0 has no public handle on the coordination-service client
    from jax._src import distributed as _jd
    client = _jd.global_state.client
    return CoordKVStore(client) if client is not None else None


def guardian_store():
    """The store the launch controller exported for the guardian, if
    any (shared with the error trap — one substrate, two protocols)."""
    endpoint = os.environ.get("PADDLE_GUARDIAN_STORE")
    root = os.environ.get("PADDLE_GUARDIAN_DIR")
    try:
        if endpoint:
            from .store import TCPStore
            host, port = endpoint.rsplit(":", 1)
            return TCPStore(host, int(port), timeout=20.0)
        if root:
            from .store import FileKVStore
            return FileKVStore(root)
    except Exception:
        return None
    return None


class HostCollectives:
    """One gather primitive; every collective derives from it."""

    def __init__(self, store, job="default"):
        self.store = store
        self.job = str(job)
        self._seq: dict[int, int] = {}
        self._lock = threading.Lock()

    def _key(self, gid, seq, rank):
        return f"{self.job}/hc/g{gid}/s{seq}/r{rank}"

    def gather(self, group, local, poll_s=0.005, rank=None):
        """Post this rank's array, block until every group member's
        contribution for the same per-group sequence number arrives,
        return them stacked ``[nranks, ...]`` in group order.

        ``rank`` overrides the ambient process index — launched workers
        that never initialize jax.distributed (the pickle/CPU lane, e.g.
        the elastic resize drill) pass their PADDLE_TRAINER_ID here.

        The wait polls in small sleeps — deliberately interpreter-level,
        so the collective watchdog can abort it when a peer is dead."""
        from . import env as _env
        gid = getattr(group, "id", 0)
        with self._lock:
            seq = self._seq.get(gid, 0)
            self._seq[gid] = seq + 1
        local = np.asarray(local)
        me = _env.get_rank() if rank is None else int(rank)
        buf = io.BytesIO()
        np.save(buf, local, allow_pickle=False)
        self.store.set(self._key(gid, seq, me), buf.getvalue())
        if seq >= 2:
            # a peer inside seq-1 has, by construction, consumed every
            # seq-2 contribution — reclaim ours (bounded store growth)
            self.store.delete_key(self._key(gid, seq - 2, me))
        parts: dict[int, np.ndarray] = {}
        while True:
            for idx, rank in enumerate(group.ranks):
                if idx in parts:
                    continue
                val = self.store.get(self._key(gid, seq, rank))
                if val is not None:
                    parts[idx] = np.load(io.BytesIO(val),
                                         allow_pickle=False)
            if len(parts) == group.nranks:
                return np.stack([parts[i]
                                 for i in range(group.nranks)])
            time.sleep(poll_s)


_HC = None
_HC_LOCK = threading.Lock()


def bootstrap():
    """Process-wide HostCollectives over the best available store, or
    None when the process has no shared substrate (single-process)."""
    global _HC
    with _HC_LOCK:
        if _HC is None:
            store = guardian_store() or coord_kv_store()
            if store is None:
                _HC = False
            else:
                _HC = HostCollectives(
                    store, job=os.environ.get("PADDLE_JOB_ID", "default"))
        return _HC or None


def reset():
    global _HC
    with _HC_LOCK:
        _HC = None
