"""Arithmetic the per-layer readers share: registry ratios and a piece
of work's share of its roofline.  The yardstick lives here, under the
benchmark's paths, so that no PR that claims a gain can move it."""
from __future__ import annotations

import glob
import importlib.util
import json
import os

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def load_ops(work):
    spec = importlib.util.spec_from_file_location(
        "kernel_ops_" + work, os.path.join(HERE, "kernels", work, "ops.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def work_seconds(run, work):
    """Device seconds of every trace event that does ``work``, by the
    name patterns of each implementation's file in ``kernels/<work>/``.
    Returns (seconds, implementations seen)."""
    total, seen = 0.0, []
    for path in sorted(glob.glob(os.path.join(HERE, "kernels", work,
                                              "*.json"))):
        with open(path) as f:
            impl = json.load(f)
        if impl.get("rehearsal_only") and not run.rehearsal:
            continue
        sec, cnt, _ = trace_reduce.match_ops(run.reduced["ops"],
                                             impl["patterns"])
        if cnt:
            total += sec
            seen.append(impl["implementation"])
    return total, seen


def roofline_share(run, work):
    """100 x (the least time the chip could take for the work done in the
    traced window) / (the device time its events took); None where no
    event of any known implementation is in the trace."""
    seconds, seen = work_seconds(run, work)
    if not seen:
        run.say(f"{work}: no trace event matches any implementation in "
                f"kernels/{work}/*.json")
        return None
    w = load_ops(work).work(run)
    by_flops = w["flops"] / run.peaks["bf16_flops_per_s"]
    by_bytes = w["bytes"] / run.peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes)
    run.say(f"{work}: {seen} took {seconds:.4f}s for {w['flops']:.3e} "
            f"FLOP and {w['bytes']:.3e} B (least {least:.4f}s, bound by "
            f"{'compute' if by_flops > by_bytes else 'memory'})")
    return 100.0 * least / seconds


def registry_share(run, hit, miss):
    reg = run.records["registry"]
    h, m = reg.get(hit, 0), reg.get(miss, 0)
    if h + m <= 0:
        return None
    return 100.0 * h / (h + m)
