"""Training step telemetry: step-time histograms, throughput, MFU, and
device-memory watermarks.

The ROADMAP's "fast as the hardware allows" north star is judged by
exactly three numbers — step wall time, tokens/examples per second, and
achieved-vs-peak FLOPs (MFU) — plus the memory headroom that bounds
batch size.  ``StepMetrics`` publishes all of them into the metrics
registry so they ride the same Prometheus/JSON exposition as every
other counter:

- ``<prefix>step_time_ms``       histogram (p50/p99 via exposition)
- ``<prefix>examples_total`` / ``<prefix>tokens_total``  counters
- ``<prefix>examples_per_sec`` / ``<prefix>tokens_per_sec``  gauges
  (last completed step)
- ``<prefix>mfu``                gauge, analytic step FLOPs (from
  ``ops/flops.py``'s dispatch-funnel counter) / step time / peak
  (``FLAGS_peak_flops``, else the device's row of the one peak table;
  a CPU has no peak, so no MFU is published there)
- ``device.memory.peak_bytes{device=i}`` high-watermark gauges sampled
  from ``jax.local_devices()[i].memory_stats()``; on backends that
  expose none (CPU) the fallback is the process RSS high-watermark in
  ``host.peak_rss_bytes``.

Wired into ``hapi.Model.fit`` (one instance per fit, FLOPs measured
once from the first batch) and usable standalone around any training
loop::

    sm = StepMetrics(tokens_per_example=seq_len)
    sm.set_flops_per_step(fc.train_step_flops)
    for batch in loader:
        with sm.step(examples=batch_size):
            train_step(batch)
    sm.snapshot()   # {"step_time_ms": {...}, "tokens_per_sec": ..., ...}
"""
from __future__ import annotations

import time

from ..utils.flags import flag as _flag
from . import registry as _registry


class StepMetrics:
    def __init__(self, prefix="train.", registry=None, peak_flops=None,
                 tokens_per_example=None, memory_every=16):
        reg = registry or _registry.REGISTRY
        self.registry = reg
        self.prefix = prefix
        self.tokens_per_example = tokens_per_example
        self.memory_every = max(int(memory_every), 1)
        self.flops_per_step = None
        self._peak = peak_flops
        self._t0 = None
        self._steps_seen = 0
        self.step_time_ms = reg.histogram(
            prefix + "step_time_ms", "training step wall time (ms)")
        self.examples_total = reg.counter(
            prefix + "examples_total", "examples consumed")
        self.tokens_total = reg.counter(
            prefix + "tokens_total", "tokens consumed")
        self.examples_per_sec = reg.gauge(
            prefix + "examples_per_sec", "throughput of the last step")
        self.tokens_per_sec = reg.gauge(
            prefix + "tokens_per_sec", "token throughput of the last step")
        # registered on the first step that has a peak to divide by: a
        # CPU run exposes no MFU series at all, not a zero
        self.mfu = None
        self.steps = reg.counter(prefix + "steps_total", "steps completed")
        # input-pipeline goodput (paddle_tpu.data.GoodputMeter): attached
        # by fit when the train loader is a data.Pipeline, so one
        # snapshot carries both sides of the host/device boundary
        self._data_goodput = None

    def attach_data(self, goodput):
        self._data_goodput = goodput

    # ---- configuration ----
    def set_flops_per_step(self, flops):
        """Analytic FLOPs of ONE optimizer step (fwd+bwd; e.g.
        ``FlopsCounter.train_step_flops``).  Enables the mfu gauge."""
        self.flops_per_step = flops if flops else None

    def peak_flops(self):
        """Peak FLOP/s of the devices the step runs on: the
        ``peak_flops=`` argument or ``FLAGS_peak_flops`` when the caller
        states one, else the device's row of the one peak table
        (``cost_model.DEVICE_SPECS``) times the devices of the active
        mesh — one when there is none, however many the host holds.
        None on a CPU, which has no peak: no MFU is published there."""
        if self._peak:
            return float(self._peak)
        configured = float(_flag("FLAGS_peak_flops", 0.0) or 0.0)
        if configured > 0:
            return configured
        from ..cost_model import device_peak_flops
        from ..distributed.mesh import get_mesh
        peak = device_peak_flops()
        if peak is None:
            return None
        mesh = get_mesh()
        return peak * (mesh.jax_mesh.size if mesh is not None else 1)

    # ---- the per-step hot path ----
    def begin_step(self):
        self._t0 = time.perf_counter()

    def end_step(self, examples=0, tokens=None):
        if self._t0 is None:
            return None
        dt = time.perf_counter() - self._t0
        self._t0 = None
        if tokens is None and self.tokens_per_example and examples:
            tokens = examples * self.tokens_per_example
        ms = dt * 1e3
        self.step_time_ms.observe(ms)
        self.steps.inc()
        if examples:
            self.examples_total.inc(examples)
            self.examples_per_sec.set(examples / max(dt, 1e-12))
        if tokens:
            self.tokens_total.inc(tokens)
            self.tokens_per_sec.set(tokens / max(dt, 1e-12))
        if self.flops_per_step:
            peak = self.peak_flops()
            if peak:
                if self.mfu is None:
                    self.mfu = self.registry.gauge(
                        self.prefix + "mfu",
                        "achieved / peak FLOPs of the last step")
                self.mfu.set(
                    self.flops_per_step / max(dt, 1e-12) / peak)
        self._steps_seen += 1
        if self._steps_seen % self.memory_every == 1:
            sample_memory_watermarks(self.registry)
        from . import flight_recorder as _fr
        _fr.record("step", self.prefix + "step",
                   step=self._steps_seen, dur_ms=round(ms, 3))
        return dt

    class _StepScope:
        __slots__ = ("sm", "examples", "tokens")

        def __init__(self, sm, examples, tokens):
            self.sm, self.examples, self.tokens = sm, examples, tokens

        def __enter__(self):
            self.sm.begin_step()
            return self

        def __exit__(self, *exc):
            if exc[0] is None:
                self.sm.end_step(self.examples, self.tokens)
            return False

    def step(self, examples=0, tokens=None):
        """Context manager timing one step."""
        return self._StepScope(self, examples, tokens)

    # ---- read side ----
    def snapshot(self):
        snap = {
            "steps": self.steps.value,
            "step_time_ms": self.step_time_ms.snapshot(),
            "examples_total": self.examples_total.value,
            "tokens_total": self.tokens_total.value,
            "examples_per_sec": self.examples_per_sec.value,
            "tokens_per_sec": self.tokens_per_sec.value,
            "mfu": None if self.mfu is None else self.mfu.value,
            "flops_per_step": self.flops_per_step,
            "peak_flops": self.peak_flops() if self.flops_per_step
            else None,
        }
        snap["memory"] = sample_memory_watermarks(self.registry)
        if self._data_goodput is not None:
            snap["data"] = self._data_goodput.snapshot()
        return snap


def sample_memory_watermarks(registry=None):
    """Record device-memory high-watermarks into gauges; returns the
    sampled dict.  TPU/GPU backends expose per-device
    ``memory_stats()``; CPU returns None there, so the fallback
    watermark is the process max-RSS (which is what actually OOMs a
    host run)."""
    reg = registry or _registry.REGISTRY
    out = {}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        devices = []
    for i, d in enumerate(devices):
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        peak = ms.get("peak_bytes_in_use", ms.get("bytes_in_use", 0))
        in_use = ms.get("bytes_in_use", 0)
        limit = ms.get("bytes_limit")
        g = reg.gauge("device.memory.peak_bytes",
                      "per-device allocator high-watermark",
                      labelnames=("device",)).labels(device=str(i))
        g.max(peak)
        out[f"device{i}"] = {"peak_bytes": peak, "bytes_in_use": in_use,
                             "bytes_limit": limit}
        if limit:
            reg.gauge("device.memory.limit_bytes",
                      "per-device allocator capacity",
                      labelnames=("device",)).labels(device=str(i)) \
                .set(limit)
    if not out:
        rss = _max_rss_bytes()
        if rss:
            reg.gauge("host.peak_rss_bytes",
                      "process RSS high-watermark (CPU fallback for "
                      "backends without memory_stats)").max(rss)
            out["host"] = {"peak_rss_bytes": rss}
    return out


def _max_rss_bytes():
    try:
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB, macOS bytes
        import sys
        return ru if sys.platform == "darwin" else ru * 1024
    except Exception:
        return None
