"""From the profiler's ``.xplane.pb`` to the few numbers the per-layer
readers use: per-device busy time as the union of device-operation
intervals, time per operation, and the idle gaps named by what the host
was doing.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU every chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO operation and whose line ``XLA Modules`` one per executed
program.  The traced window is the harness's own ``chipbench:window``
annotation on the host plane; everything is clipped to it."""
from __future__ import annotations

import bisect
import glob
import os
import re
from types import SimpleNamespace

WINDOW = "chipbench:window"
_IDLE_WORDS = ("sleep", "wait", "acquire", "join", "select", "poll",
               "result", WINDOW)
_META_STATS = ("tf_op", "long_name", "hlo_category", "hlo_op", "hlo_module")


def find_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def union(intervals):
    """Merge [start, end) intervals; returns the merged list, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _stats(event):
    out = {}
    for k, v in event.stats:
        if k in _META_STATS:
            out[k] = str(v)[:400]
    return out


def _device_planes(planes):
    dev = [p for p in planes if p.name.startswith("/device:TPU:")]
    if dev:
        return [(p.name, [ln for ln in p.lines if ln.name == "XLA Ops"],
                 [ln for ln in p.lines if ln.name == "XLA Modules"])
                for p in sorted(dev, key=lambda p: p.name)]
    return None


def _host_lines(planes):
    return [ln for p in planes if p.name.startswith("/host:")
            for ln in p.lines]


def reduce_trace(path, rehearsal=False):
    """Reduce one ``.xplane.pb``.  Times in seconds.

    Returns ``window_s``; ``devices``: per device ``busy_s`` and the
    merged busy intervals relative to the window's start; ``busy_s``:
    the mean over devices; ``ops``: {name: {seconds, count, meta}} summed
    over devices and divided by their number; ``modules``: per program
    name the list of (start, end) on the first device; ``idle_gaps``:
    [(what the host was doing, seconds)] on the first device."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    host = _host_lines(planes)
    win = None
    host_events = []                 # (line index, start, end, name)
    for li, ln in enumerate(host):
        for e in ln.events:
            s, d = e.start_ns, e.duration_ns
            if e.name == WINDOW:
                win = (s, s + d)
            elif d > 0:
                host_events.append((li, s, s + d, e.name))
    if win is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    lo, hi = win

    dev = _device_planes(planes)
    if dev is None:
        if not rehearsal:
            raise ValueError(
                f"{path}: no /device:TPU plane; only a rehearsal may "
                "reduce a trace without a chip")
        # CPU rehearsal: XLA's CPU client threads stand in, one pseudo
        # device per thread that ran an HLO op — plumbing only
        dev = []
        for ln in host:
            evs = [e for e in ln.events
                   if any(k == "hlo_op" for k, _ in e.stats)]
            if evs:
                dev.append((ln.name, [SimpleNamespace(events=evs)], []))
        if not dev:
            raise ValueError(f"{path}: no device operation in the trace")

    devices, ops, modules = [], {}, {}
    for di, (name, op_lines, mod_lines) in enumerate(dev):
        spans = []
        for ln in op_lines:
            for e in ln.events:
                c = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
                if c is None:
                    continue
                spans.append(c)
                rec = ops.get(e.name)
                if rec is None:
                    rec = ops[e.name] = {"seconds": 0.0, "count": 0,
                                         "meta": _stats(e)}
                rec["seconds"] += (c[1] - c[0]) * 1e-9
                rec["count"] += 1
        merged = union(spans)
        devices.append({
            "name": name,
            "busy_s": sum(e - s for s, e in merged) * 1e-9,
            "busy": [((s - lo) * 1e-9, (e - lo) * 1e-9)
                     for s, e in merged]})
        if di == 0:
            for ln in mod_lines:
                for e in ln.events:
                    c = _clip(e.start_ns, e.start_ns + e.duration_ns,
                              lo, hi)
                    if c is not None:
                        modules.setdefault(e.name, []).append(
                            ((c[0] - lo) * 1e-9, (c[1] - lo) * 1e-9))
    if rehearsal and not modules:
        # no program line on a CPU: the harness's own step spans stand in
        modules["chipbench:step"] = [
            ((s - lo) * 1e-9, (e - lo) * 1e-9)
            for _, s, e, name in host_events if name == "chipbench:step"]
    n = len(devices)
    for rec in ops.values():
        rec["seconds"] /= n
        rec["count"] /= n
    window_s = (hi - lo) * 1e-9
    return {"window_s": window_s, "devices": devices,
            "busy_s": sum(d["busy_s"] for d in devices) / n,
            "ops": ops, "modules": modules,
            "idle_gaps": _name_gaps(devices[0]["busy"], window_s,
                                    host_events, lo)}


def _name_gaps(busy, window_s, host_events, lo):
    """Idle gaps of one device, each named by the innermost host event
    that covers its middle and is not itself a wait."""
    gaps, t = [], 0.0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window_s > t:
        gaps.append((t, window_s))
    if not gaps:
        return []
    evs = sorted(((s - lo) * 1e-9, (e - lo) * 1e-9, name)
                 for _, s, e, name in host_events
                 if not any(w in name.lower() for w in _IDLE_WORDS))
    starts = [e[0] for e in evs]
    totals = {}
    # the longest gaps carry the time; naming each of thousands costs
    # a scan, so name the 2,000 longest and pool the rest
    gaps.sort(key=lambda g: g[0] - g[1])
    for gs, ge in gaps[:2000]:
        mid = 0.5 * (gs + ge)
        best = None
        i = bisect.bisect_right(starts, mid)
        for s, e, name in reversed(evs[max(0, i - 400):i]):
            if e >= mid and (best is None or e - s < best[0]):
                best = (e - s, name)
        what = best[1] if best else "host: no span"
        totals[what] = totals.get(what, 0.0) + (ge - gs)
    rest = sum(ge - gs for gs, ge in gaps[2000:])
    if rest:
        totals["(shorter gaps, not named)"] = rest
    return sorted(totals.items(), key=lambda kv: -kv[1])


def line_names(path):
    """{plane: [line names]} — what a builder looks at first."""
    from jax.profiler import ProfileData
    return {p.name: [ln.name for ln in p.lines]
            for p in ProfileData.from_file(path).planes}


def short_name(name):
    """An XLA Ops event is named by its whole HLO line; keep the
    instruction's name and what kind of instruction it is."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:80]
    kind = re.search(r"\b(fusion|custom-call|convolution|copy|dot|"
                     r"all-reduce|all-gather|reduce-scatter|"
                     r"collective-permute|all-to-all|while)\b", rest)
    tag = kind.group(1) if kind else rest.split("(")[0].split()[-1][:24]
    if "tpu_custom_call" in rest:
        tag = "pallas"
    return f"{head.lstrip('%')} ({tag})"[:80]


def top_ops(ops, k=10):
    rows = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])[:k]
    return [[short_name(name), rec["seconds"]] for name, rec in rows]


def match_ops(ops, patterns):
    """The operations whose name or metadata matches any regex of
    ``patterns``: (seconds, count, names)."""
    rx = [re.compile(p) for p in patterns]
    sec = cnt = 0.0
    names = []
    for name, rec in ops.items():
        text = name + " | " + " | ".join(rec["meta"].values())
        if any(r.search(text) for r in rx):
            sec += rec["seconds"]
            cnt += rec["count"]
            names.append(name)
    return sec, cnt, names
