"""A builder's tool, run on the chip after a traced run kept with
``CHIPBENCH_KEEP_TRACE`` (and, for the clock check, ``FLAGS_trace_dir``):
which of the program's phase spans are in the xplane and on which host
line, the share of the window each covers, the programs on ``XLA
Modules``, whether a scope's name is in the xplane's HLO metadata, and
how far a ring span's ``wall`` lies from its ``TraceAnnotation`` twin.

    python3 chipbench/tests/span_check.py <xplane.pb> [<FLAGS_trace_dir>]

Prints one JSON object; PERF.md section 6 lists its readings."""
import glob
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

PREFIXES = ("train.step", "serving.")
SCOPES = (b"/attn/", b"/mlp/", b"/embed/", b"/head/", b"/loss/",
          b"/optimizer/")


def main(path, trace_dir=None):
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    start = None
    for p in planes:
        for k, v in p.stats:
            if k == "profile_start_time":
                start = int(v)
    win, spans = None, {}
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == trace_reduce.WINDOW:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(PREFIXES):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, ln.name))
    out = {"profile_start_time_ns": start, "phases": {}, "modules": {},
           "scopes_in_hlo_metadata": {}}
    lo, hi = win
    for name, evs in sorted(spans.items()):
        inside = [(s, d) for s, d, _ in evs if lo <= s < hi]
        out["phases"][name] = {
            "count": len(inside),
            "window_share_pct": 100.0 * sum(d for _, d in inside)
            / (hi - lo),
            "lines": sorted({ln for _, _, ln in evs})}
    for p in planes:
        if p.name.startswith("/device:TPU:0"):
            for ln in p.lines:
                if ln.name == "XLA Modules":
                    for e in ln.events:
                        out["modules"][e.name] = \
                            out["modules"].get(e.name, 0) + 1
    out["modules"] = dict(sorted(out["modules"].items(),
                                 key=lambda kv: -kv[1])[:12])
    with open(path, "rb") as f:
        raw = f.read()
    for s in SCOPES:
        out["scopes_in_hlo_metadata"][s.decode()] = raw.count(s)

    if trace_dir:
        # each ring record against the xplane event of its name that
        # starts nearest: epoch ns = profile_start_time + start_ns
        recs = []
        for fn in glob.glob(os.path.join(trace_dir, "spool-*.jsonl")):
            with open(fn) as f:
                recs += [json.loads(x) for x in f if x.strip()]
        diffs = {}
        for r in recs:
            if r.get("kind") != "phase" or r["name"] not in spans:
                continue
            wall_ns = r["wall"] * 1e9
            near = min(abs(start + s - wall_ns)
                       for s, _, _ in spans[r["name"]])
            if near < 5e6:          # its twin, not another tick's
                diffs.setdefault(r["name"], []).append(near * 1e-3)
        out["wall_minus_xplane_us"] = {
            k: {"n": len(v), "median": statistics.median(v),
                "max": max(v)} for k, v in sorted(diffs.items())}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(*sys.argv[1:3])
