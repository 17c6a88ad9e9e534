"""Throughput / step-time benchmarking + MFU.

Reference capability: profiler/timer.py (`benchmark()` hub with
reader/batch cost and ips) and fleet's step timers
(fleet/utils/timer_helper.py:48); the MFU calculator is the TPU-side
"north star" metric (SURVEY §6).
"""
from __future__ import annotations

import time


class _Event:
    def __init__(self):
        self.reset()

    def reset(self):
        self.total = 0.0
        self.count = 0
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is not None:
            self.total += time.perf_counter() - self._t0
            self.count += 1
            self._t0 = None

    @property
    def avg(self):
        return self.total / max(self.count, 1)


class TimerHub:
    """reference: timer_helper.py get_timers() pattern."""

    def __init__(self):
        self._timers = {}

    def __call__(self, name):
        if name not in self._timers:
            self._timers[name] = _Event()
        return self._timers[name]

    def log(self, names=None, normalizer=1.0, reset=True):
        names = names or list(self._timers)
        parts = []
        for n in names:
            t = self._timers.get(n)
            if t is None:
                continue
            parts.append(f"{n}: {t.total * 1000 / normalizer:.2f}ms")
            if reset:
                t.reset()
        return " | ".join(parts)


class Benchmark:
    """reference: profiler/timer.py benchmark() — reader/batch cost + ips."""

    def __init__(self):
        self.reader = _Event()
        self.batch = _Event()
        self._samples = 0
        self._t_start = None

    def begin(self):
        self._t_start = time.perf_counter()
        self.reader.reset()
        self.batch.reset()
        self._samples = 0

    def before_reader(self):
        self.reader.start()

    def after_reader(self):
        self.reader.stop()
        self.batch.start()

    def after_step(self, num_samples=1):
        self.batch.stop()
        self._samples += num_samples

    def step_info(self, unit="samples"):
        ips = self._samples / max(self.batch.total, 1e-12)
        return (f"reader_cost: {self.reader.avg * 1000:.3f} ms "
                f"batch_cost: {self.batch.avg * 1000:.3f} ms "
                f"ips: {ips:.2f} {unit}/s")

    @property
    def ips(self):
        return self._samples / max(self.batch.total, 1e-12)


_BENCH = Benchmark()


def benchmark():
    return _BENCH


# the one peak table lives in cost_model (DEVICE_SPECS, by device_kind)
from ..cost_model import device_peak_flops  # noqa: E402,F401


def mfu(model_flops_per_step, step_time_s, n_devices=1, device=None):
    """Model FLOPs utilization: achieved / peak.  None on a CPU, which
    has no peak."""
    peak = device_peak_flops(device)
    if peak is None:
        return None
    return model_flops_per_step / max(step_time_s, 1e-12) / \
        (peak * n_devices)
