"""What the readers of the program's own spans and counters share (the
ten per-layer metrics of PR 26; not a reader itself).

``run.records["registry"]`` is the delta of ``monitor.all_stats()`` over
the window, so these readers take counters and a histogram's ``.sum`` /
``.count`` pair, never a gauge; ``run.reduced["modules"]`` holds, per
program name on the first device's ``XLA Modules`` line, the
(start, end) of each execution in the window."""
import re
import statistics

TICK = re.compile(r"serving_tick")


def leave_out(run, metric, why):
    """A program from before a metric's span, counter or program name
    has nothing for it: the line leaves the metric out, which the driver
    accepts of a parent commit.  ``emit.py`` refuses a line that lacks a
    listed metric, so it is shown the benchmark without this one
    (PERF.md section 7 asks ``emit.py`` for a rule of its own).  A
    program that has the source and read nothing in the window is not
    let off: its reader returns None and the line is refused."""
    run.say(f"per-layer metric {metric}: left out, {why}")
    run.bench = dict(run.bench, per_layer=[
        m for m in run.bench["per_layer"] if m["name"] != metric])


def registry(run, metric, *keys):
    """The window's registry deltas; None, with ``metric`` left out, when
    the program has none of ``keys`` at all."""
    reg = run.records["registry"]
    missing = [k for k in keys if k not in reg]
    if missing:
        leave_out(run, metric, f"the program's registry has no {missing}")
        return None
    return reg


def mean_ms(run, metric, hist):
    """sum / count of one histogram of milliseconds over the window."""
    reg = registry(run, metric, hist + ".count")
    if reg is None or reg[hist + ".count"] <= 0:
        return None
    return reg[hist + ".sum"] / reg[hist + ".count"]


def compile_ms(run, metric):
    """``jit.compile_ms`` summed over the window; 0.0 when no program was
    built in it."""
    reg = registry(run, metric, "jit.compile_ms.sum")
    return None if reg is None else float(reg["jit.compile_ms.sum"])


def prefill(run, metric, *keys):
    """The registry for a prefill reader: None exactly when
    ``prefill_wall_share.serve`` reads nothing (no chunk in the window)
    or the program has none of ``keys``."""
    reg = registry(run, metric, *keys)
    if reg is None or reg.get("serving.prefill_chunk_ms.count", 0) <= 0:
        return None
    return reg


def executions(run, metric):
    """Every program execution of the window on the first device,
    [(start, end, is a tick)] by start; None, with ``metric`` left out,
    when no program is named ``serving_tick`` (a program from before the
    jitted tick carried a name)."""
    mods = run.reduced["modules"]
    if not any(TICK.search(name) for name in mods):
        leave_out(run, metric, "no program on XLA Modules is named "
                               f"serving_tick: {sorted(mods)[:5]}")
        return None
    return sorted((s, e, bool(TICK.search(name)))
                  for name, spans in mods.items() for s, e in spans)


def median_tick_gap_ms(execs):
    gaps = [max(0.0, b[0] - a[1]) for a, b in zip(execs, execs[1:])
            if a[2] and b[2]]
    return statistics.median(gaps) * 1e3 if gaps else None
