"""Device mesh abstraction.

Reference capability: `ProcessMesh` (reference:
paddle/phi/core/distributed/auto_parallel/process_mesh.h:31 and
python/paddle/distributed/auto_parallel/process_mesh.py) — an N-D cartesian
arrangement of ranks with named axes, the substrate every parallelism
strategy shards over.

TPU-native realization: a thin, pickle-friendly wrapper over
`jax.sharding.Mesh`.  Axis layout matters on TPU: the *last* mesh axis is
laid out over the fastest-varying (adjacent-on-ICI) device order, so model
axes that carry heavy collectives ("mp"/"sp") should come last — the JAX
convention — while slow axes ("pp", then "dp") come first and may ride DCN
across slices.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh


class ProcessMesh:
    """N-D named device mesh (reference: process_mesh.h:31).

    `mesh` — array of device ids (or jax devices) shaped like the topology.
    `dim_names` — one name per mesh axis, e.g. ["dp", "mp"].
    """

    def __init__(self, mesh, dim_names=None, process_ids=None):
        arr = np.asarray(mesh)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        if len(dim_names) != arr.ndim:
            raise ValueError(
                f"dim_names {dim_names} does not match mesh ndim {arr.ndim}")
        self._shape = tuple(arr.shape)
        self._dim_names = tuple(dim_names)
        if arr.dtype == object:  # already jax devices
            devices = arr
            self._process_ids = np.array(
                [d.id for d in arr.flat]).reshape(arr.shape)
        else:
            all_devices = {d.id: d for d in jax.devices()}
            self._process_ids = arr.astype(np.int64)
            devices = np.empty(arr.shape, dtype=object)
            for idx, did in np.ndenumerate(arr):
                devices[idx] = all_devices[int(did)]
        self._jax_mesh = Mesh(devices, axis_names=self._dim_names)

    # ---- reference-parity surface ----
    @property
    def shape(self):
        return list(self._shape)

    @property
    def ndim(self):
        return len(self._shape)

    @property
    def dim_names(self):
        return list(self._dim_names)

    @property
    def process_ids(self):
        return [int(x) for x in self._process_ids.flat]

    @property
    def mesh(self):
        return self._process_ids

    def get_dim_size(self, name):
        return self._shape[self._dim_names.index(name)]

    # ---- jax interop ----
    @property
    def jax_mesh(self) -> Mesh:
        return self._jax_mesh

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh)
                and self._dim_names == other._dim_names
                and np.array_equal(self._process_ids, other._process_ids))

    def __hash__(self):
        return hash((self._dim_names, self._process_ids.tobytes()))

    def __repr__(self):
        return (f"ProcessMesh(shape={list(self._shape)}, "
                f"dim_names={list(self._dim_names)})")

    def __enter__(self):
        _MESH_STACK.append(self)
        return self

    def __exit__(self, *exc):
        _MESH_STACK.pop()


_MESH_STACK: list[ProcessMesh] = []


def get_mesh() -> ProcessMesh | None:
    """Innermost `with mesh:` scope, else the globally-set default."""
    if _MESH_STACK:
        return _MESH_STACK[-1]
    return _DEFAULT[0]


_DEFAULT: list = [None]


def set_mesh(mesh: ProcessMesh):
    _DEFAULT[0] = mesh


def inside_manual_region() -> bool:
    """True while tracing the body of a ``shard_map``: some mesh axis is
    Manual there, the arrays are per-shard, and another manual
    ``shard_map`` over the same axes cannot be nested."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def gspmd_mesh() -> ProcessMesh | None:
    """The active mesh when the code being traced is (part of) ONE
    program that GSPMD partitions over more than one device; None with
    no mesh, a one-device mesh, or inside a ``shard_map`` body.

    Mosaic (Pallas TPU) kernels cannot be partitioned automatically, so
    every kernel gate asks this: under such a mesh a kernel either runs
    inside its own ``shard_map`` (flash attention) or yields to its XLA
    form (fused Adam, RMS norm, rope, paged decode)."""
    mesh = get_mesh()
    if mesh is None or mesh.jax_mesh.size == 1 or inside_manual_region():
        return None
    return mesh


from contextlib import contextmanager


@contextmanager
def suspended():
    """Temporarily deactivate the scoped AND default mesh.

    Used by ragged-batch eager fallbacks (framework/train_step.py): a
    batch that does not divide the dp axis cannot satisfy the model's
    activation ``shard_constraint``s in ANY lane, but with the mesh
    scope lifted those constraints become no-ops while committed
    (sharded) parameters still compute the same values through GSPMD
    eager propagation."""
    saved_stack = _MESH_STACK[:]
    saved_default = _DEFAULT[0]
    del _MESH_STACK[:]
    _DEFAULT[0] = None
    try:
        yield
    finally:
        _MESH_STACK[:] = saved_stack
        _DEFAULT[0] = saved_default


def init_mesh(shape, dim_names, devices=None) -> ProcessMesh:
    """Build a mesh over the first prod(shape) available devices.

    On real hardware prefer `jax.experimental.mesh_utils` contiguity; here we
    keep device order (jax.devices() is already ICI-contiguous on TPU).
    """
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"have {len(devices)}")
    try:
        from jax.experimental import mesh_utils
        dev_arr = mesh_utils.create_device_mesh(
            tuple(shape), devices=devices[:n])
    except Exception:
        dev_arr = np.array(devices[:n], dtype=object).reshape(shape)
    mesh = ProcessMesh(np.array(dev_arr, dtype=object), dim_names)
    return mesh
