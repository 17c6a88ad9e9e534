"""Device management (reference: python/paddle/device/).

TPU-native: devices are JAX devices; `set_device` selects the default
placement.  There is no per-op stream management — XLA owns scheduling.
"""
from __future__ import annotations

import jax


_current = None


def set_device(device: str):
    """Accepts 'tpu', 'cpu', 'tpu:0' etc."""
    global _current
    _current = device
    return device


def get_device() -> str:
    if _current is not None:
        return _current
    d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'id', 0)}"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def synchronize(device=None):
    """Block until all queued device work is done (reference:
    paddle.device.synchronize / cudaDeviceSynchronize).  JAX arrays are
    async; effectively a fence via block_until_ready on a trivial op."""
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


class Stream:
    """API-parity stub: XLA manages streams internally on TPU."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, other):
        pass


class Event:
    def __init__(self, enable_timing=False):
        self._t = None

    def record(self, stream=None):
        import time
        synchronize()
        self._t = time.perf_counter()

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end):
        return (end._t - self._t) * 1000.0


def cuda_stream_guard(*a, **k):
    import contextlib

    @contextlib.contextmanager
    def _g():
        yield
    return _g()


class XPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(xpu:{self.device_id})"


class IPUPlace:
    def __repr__(self):
        return "Place(ipu)"


def get_cudnn_version():
    """No cuDNN in an XLA/TPU runtime (reference returns the linked
    version on CUDA builds)."""
    return None


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_type):
    return device_type == "tpu"


def get_all_device_type():
    import jax
    try:
        return sorted({d.platform for d in jax.devices()} | {"cpu"})
    except Exception:
        return ["cpu"]


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if not d.startswith(("cpu", "gpu"))]


def current_stream(device=None):
    """XLA orders execution per device; the Stream object is the
    compatibility handle (reference: device/cuda streams)."""
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    """Context placing ops on a stream (reference: device/__init__.py
    stream_guard) — XLA orders per-device execution, so this scopes the
    compatibility handle only."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        yield stream
    return ctx()
