"""Device time by program scope (what the by-scope readers of PR 36
share; not a reader itself).

The program names its work with ``observability.tracing.scope`` (``attn``,
``mlp``, ``head``, ...; the tape's backward re-enters the forward's scope
under the marker ``bwd``) and every compiled hot-path program publishes
an instruction -> scope table (``observability.scopes.tables()``).  An
``XLA Ops`` event of the trace is named by its whole HLO line, so the
key of ``run.reduced["ops"]`` starts ``%<instruction> = <type> <kind>(``:
this file joins the two and gives seconds by (scope path, direction).

An instruction resolves to a scope when some table has it under the same
result type and every table that does agrees; otherwise it is
``ambiguous``; an event no table has (an eager op's program, a program
of before the tables) is ``no table``.  A ``while`` / ``conditional`` /
``call`` whose body's instructions have events of their own is a
container and is left out of the leaf time.  Nothing is read (None, the
line is refused) when the leaf time is not within 2 % of ``busy_s``: a
share of time attributed twice or not at all is no reading.  A program
without ``observability.scopes`` has no source for any of this: its
metrics are left out (``span_lib.leave_out``)."""
import json
import os
import time

from layer_metrics import span_lib

UNSCOPED, AMBIGUOUS, NO_TABLE = "(no scope)", "(ambiguous)", "(no table)"
_OTHER = (UNSCOPED, AMBIGUOUS, NO_TABLE)
TOLERANCE = 0.02


def resolve(index, name, typ):
    """(scope, direction, entry) of one event by the tables' index
    ``{instruction: [entry, ...]}``; scope is one of ``_OTHER`` where
    the tables do not say."""
    entries = index.get(name, ())
    if typ is None:
        # a CPU rehearsal names an event by the instruction alone, the
        # programs' events merged: the first table's entry stands in
        entries = entries[:1]
    else:
        entries = [e for e in entries if e["type"] == typ]
    if not entries:
        return NO_TABLE, "fwd", None
    found = {(e["scope"], e["dir"]) for e in entries}
    if len(found) > 1:
        return AMBIGUOUS, "fwd", entries[0]
    scope, direction = found.pop()
    return scope or UNSCOPED, direction, entries[0]


def join(ops, tabs, split):
    """Seconds by (scope, direction) from ``run.reduced["ops"]`` and the
    tables: {"by": {(scope, dir): [seconds, events, {instruction:
    seconds}]}, "containers": seconds left out, "leaf": the leaf time}."""
    index = {}
    for table in tabs.values():
        for name, entry in table.items():
            index.setdefault(name, []).append(entry)
    events = []
    for key, rec in ops.items():
        got = split(key)
        # a CPU rehearsal's event is named by the instruction alone
        name, typ = (got[0], got[1]) if got else (key.lstrip("%"), None)
        events.append((name, typ, rec))
    seen = {name for name, _, _ in events}
    by, containers = {}, 0.0
    for name, typ, rec in events:
        scope, direction, entry = resolve(index, name, typ)
        if entry is not None and any(b in seen
                                     for b in entry.get("body", ())):
            containers += rec["seconds"]
            continue
        row = by.setdefault((scope, direction), [0.0, 0.0, {}])
        row[0] += rec["seconds"]
        row[1] += rec["count"]
        row[2][name] = row[2].get(name, 0.0) + rec["seconds"]
    return {"by": by, "containers": containers,
            "leaf": sum(row[0] for row in by.values())}


def _say_table(run, got, busy):
    run.say(f"device time by scope ({got['leaf']:.4f}s of leaf events, "
            f"busy {busy:.4f}s; containers left out "
            f"{got['containers']:.4f}s):")
    for (scope, direction), (sec, cnt, names) in sorted(
            got["by"].items(), key=lambda kv: -kv[1][0]):
        longest = sorted(names.items(), key=lambda kv: -kv[1])[:5]
        run.say(f"  {scope:<28} {direction}  {sec:9.4f}s  "
                f"{100.0 * sec / busy:6.2f} %  {cnt:8.0f} events  "
                + ", ".join(f"{n} {s:.4f}" for n, s in longest))


def by_scope(run, metric):
    """The join for this run, made once and kept on ``run``; None where
    ``metric`` has nothing to read (said why)."""
    if "scope_time" not in run.records:
        run.records["scope_time"] = _read(run)
    source, got = run.records["scope_time"]
    if not source:
        span_lib.leave_out(run, metric, "the program has no "
                                        "observability.scopes")
    return got


def _read(run):
    """(whether the program has the source, the join or None)."""
    t0 = time.perf_counter()
    try:
        from paddle_tpu.observability import scopes
    except ImportError:
        return False, None
    tabs = scopes.tables()
    t1 = time.perf_counter()
    if not tabs:
        run.say("scope_lib: the program published no table")
        return True, None
    got = join(run.reduced["ops"], tabs, scopes.split_instruction)
    busy = run.reduced["busy_s"]
    run.say(f"scope_lib: {len(tabs)} tables "
            f"({sum(len(t) for t in tabs.values())} instructions) built in "
            f"{t1 - t0:.2f}s, joined with {len(run.reduced['ops'])} event "
            f"names in {time.perf_counter() - t1:.2f}s")
    _say_table(run, got, busy)
    dump = os.environ.get("CHIPBENCH_DUMP")
    if dump:                    # a builder's look, beside run.py's own
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(
                dump, f"{run.name}.{run.seed}.scopes.json"), "w") as f:
            json.dump({"busy_s": busy, "tables": tabs, "ops": {
                k: [v["seconds"], v["count"]]
                for k, v in run.reduced["ops"].items()}}, f)
    if abs(got["leaf"] - busy) > TOLERANCE * busy and not run.rehearsal:
        run.say(f"scope_lib: leaf time {got['leaf']:.4f}s is not within "
                f"{100 * TOLERANCE:.0f} % of busy_s {busy:.4f}s: events "
                "overlap or a container was counted; nothing is read")
        return True, None
    got["busy"] = busy
    return True, got


def seconds(got, *names, direction=None):
    """Seconds of the scopes whose path holds any of ``names`` (of every
    named scope without ``names``), of one direction or both."""
    total = 0.0
    for (scope, d), row in got["by"].items():
        if scope in _OTHER or direction not in (None, d):
            continue
        if not names or any(n in scope.split("/") for n in names):
            total += row[0]
    return total


def share(run, metric, *names):
    """100 x (time under ``names``) / busy time; None where no such time
    is in the trace (a share is never returned as 0)."""
    got = by_scope(run, metric)
    if got is None:
        return None
    sec = seconds(got, *names)
    return 100.0 * sec / got["busy"] if sec > 0 else None


def roofline(run, metric, work, name, less=0.0):
    """100 x (the least time the chip could take for ``work``, by
    ``kernels/<work>/ops.py``) / (the device time under scope ``name``,
    both directions, less ``less`` seconds of kernels counted
    elsewhere); None unless BOTH directions have attributed time — time
    left unattributed would read as a share over 100."""
    import metrics_lib
    got = by_scope(run, metric)
    if got is None:
        return None
    fwd = seconds(got, name, direction="fwd")
    bwd = seconds(got, name, direction="bwd")
    if fwd <= 0 or bwd <= 0:
        run.say(f"{metric}: scope {name!r} has {fwd:.4f}s forward and "
                f"{bwd:.4f}s backward: one direction is not attributed "
                "(an executable loaded from a compile cache carries the "
                "scopes of the process that compiled it)")
        return None
    took = fwd + bwd - less
    w = metrics_lib.load_ops(work).work(run)
    least = max(w["flops"] / run.peaks["bf16_flops_per_s"],
                w["bytes"] / run.peaks["hbm_bytes_per_s"])
    run.say(f"{metric}: {name!r} took {fwd:.4f}s forward and {bwd:.4f}s "
            f"backward, less {less:.4f}s, for {w['flops']:.3e} FLOP and "
            f"{w['bytes']:.3e} B (least {least:.4f}s)")
    if took <= 0:
        return None
    # a rehearsal's peaks are made up and its line names no number: a
    # CPU faster than they say must not trip the validator
    return min(100.0 * least / took, 100.0) if run.rehearsal \
        else 100.0 * least / took
