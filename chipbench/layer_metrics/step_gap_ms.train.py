"""Median idle gap on the device between consecutive executions of the
step program (the program that took most of the traced time)."""
import statistics


def read(run):
    mods = run.reduced["modules"]
    if not mods:
        return None
    spans = sorted(max(mods.values(),
                       key=lambda v: sum(e - s for s, e in v)))
    gaps = [max(0.0, b[0] - a[1]) for a, b in zip(spans, spans[1:])]
    if not gaps:
        return None
    return statistics.median(gaps) * 1e3
