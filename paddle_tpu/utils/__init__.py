from . import flags  # noqa: F401
from .flags import set_flags, get_flags  # noqa: F401


def cache_stats():
    """Hit/miss/evict/bytes counters for the tiered executable cache
    (core/op_cache.py): ``tier1`` is the jitted eager-op dispatch LRU,
    ``tier2`` the persistent XLA compilation cache.  See
    docs/CACHING.md."""
    from ..core import op_cache
    return op_cache.cache_stats()


def try_import(name):
    import importlib
    try:
        return importlib.import_module(name)
    except ImportError:
        return None


def deprecated(update_to="", since="", reason="", level=0):
    """Decorator marking an API deprecated (reference:
    utils/deprecated.py) — warns once per call site."""
    import functools
    import warnings

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            msg = f"API {fn.__name__} is deprecated since {since or '?'}"
            if update_to:
                msg += f", use {update_to} instead"
            if reason:
                msg += f" ({reason})"
            warnings.warn(msg, DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)
        return wrapper
    return deco


def require_version(min_version, max_version=None):
    """Check the installed framework version (reference:
    utils/install_check.py require_version)."""
    from .. import __version__ as ver

    def parse(v):
        return tuple(int(x) for x in str(v).split(".")[:3] if x.isdigit())

    if parse(ver) < parse(min_version):
        raise RuntimeError(f"requires version >= {min_version}, got {ver}")
    if max_version is not None and parse(ver) > parse(max_version):
        raise RuntimeError(f"requires version <= {max_version}, got {ver}")
    return True


def run_check():
    """Sanity-check the install: run a small matmul + backward on the
    default device (reference: utils/install_check.py run_check)."""
    import numpy as np
    import paddle_tpu as paddle

    x = paddle.to_tensor(np.ones((4, 4), np.float32), stop_gradient=False)
    w = paddle.to_tensor(np.eye(4, dtype=np.float32), stop_gradient=False)
    y = (x @ w).sum()
    y.backward()
    assert np.allclose(x.grad.numpy(), np.ones((4, 4)))
    import jax
    print(f"paddle_tpu is installed successfully! device: "
          f"{jax.devices()[0].platform}")
from . import download  # noqa: F401,E402
