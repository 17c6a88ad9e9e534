"""The comparisons that decide ``correct``.  Each number compared has a
limit of its own, kept in the cell's file with the readings it was set
from (PERF.md section 2 lists them)."""
from __future__ import annotations

import statistics


def leaf_gaps(prog, ref, skip=()):
    """[(gap, leaf)], widest first: the gap between the program's norm
    and the reference's, leaf by leaf, measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    med = statistics.median(ref.values())
    return sorted(((abs(prog[n] - r) / max(r, med), n)
                   for n, r in ref.items() if n not in skip), reverse=True)


def dead_leaves(ref_grad_norm):
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): under Adam they move by round-off
    alone, so they are left out of the change — by this rule, not by
    name."""
    med = statistics.median(ref_grad_norm.values())
    return {n for n, g in ref_grad_norm.items() if g < 1e-3 * med}


def live_change(prog, ref):
    """The change norms with the dead ELEMENTS of each vector left out
    on both sides: an element whose reference gradient is under a
    thousandth of its vector's median element's (a fused QKV bias holds
    the key's bias, dead under softmax, beside two live thirds)."""
    import numpy as np
    p_norm, r_norm = dict(prog["change_norm"]), dict(ref["change_norm"])
    cut = {}
    for name, g in ref["grad1_vec"].items():
        a = np.abs(g)
        live = a >= 1e-3 * np.median(a)
        if live.all():
            continue
        cut[name] = int((~live).sum())
        p_norm[name] = float(np.linalg.norm(prog["change_vec"][name][live]))
        r_norm[name] = float(np.linalg.norm(ref["change_vec"][name][live]))
    return p_norm, r_norm, cut


def train_numbers(prog, ref):
    """({name: value} of every number compared in a training cell,
    notes on the leaves behind them)."""
    out = {}
    for k, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss{k}_rel"] = abs(lp - lr) / abs(lr)
    grad = leaf_gaps(prog["grad1_norm"], ref["grad1_norm"])
    skip = dead_leaves(ref["grad1_norm"])
    p_norm, r_norm, cut = live_change(prog, ref)
    change = leaf_gaps(p_norm, r_norm, skip)
    out["grad1_gap"], out["change_gap"] = grad[0][0], change[0][0]
    top = lambda rows: [[n, round(g, 6)] for g, n in rows[:4]]  # noqa: E731
    return out, {"grad1_worst": top(grad), "change_worst": top(change),
                 "dead_leaves": sorted(skip), "dead_elements": cut}


def judge(numbers, limits):
    """({name: [value, limit]}, correct, names read but not compared):
    every compared number at or under its limit.  A limit of null in the
    cell's file means the number is read and said but not compared
    (PERF.md section 2 names each with its readings); a number with no
    entry at all is an error of the cell, not a pass."""
    compared, ok, left_out = {}, True, []
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the cell's file gives no limit for {name!r}")
        if limits[name] is None:
            left_out.append(name)
            continue
        compared[name] = [value, limits[name]]
        if not value <= limits[name]:       # NaN fails
            ok = False
    return compared, ok, left_out
