"""The traced window's share of what the program's counters counted (not
a reader itself).

``run.records["registry"]`` is the registry's delta from before the
profiler starts to after it stops, and the engine runs on through both:
on the chip the profiler's stop alone takes several times the traced
window, and nothing is refilled after the close, so that stretch decodes
and prefills nothing.  A ratio of two counters of one source does not
care; a COUNT set against the trace's device seconds does.  The serving
programs are on both sides: the trace's ``XLA Modules`` line has every
execution of a tick and of a prefill member inside the window, the
registry every such call it counted.  So a tick's counts are scaled by
ticks traced over ticks counted, a prefill member's by prefill calls
traced over prefill calls counted (a program's mean work is the same at
both ends; the mix of the two is not)."""
import re

TICK = re.compile(r"serving_tick")
PREFILL = re.compile(r"serving_prefill")


def shares(run):
    """(ticks traced / ticks counted, prefill calls traced / counted);
    (1.0, 1.0) in a CPU rehearsal whose trace names no program; None
    where the registry counted no tick."""
    reg, mods = run.records["registry"], run.reduced["modules"]
    ticks = reg.get("serving.tick.compiled_hits", 0)
    if ticks <= 0:
        return None

    def traced(pattern):
        return sum(len(v) for k, v in mods.items() if pattern.search(k))

    if run.rehearsal and not traced(TICK):
        return 1.0, 1.0
    calls = reg.get("serving.prefill.compiled_hits", 0)
    out = traced(TICK) / ticks, traced(PREFILL) / calls if calls else 0.0
    run.say(f"window_lib: {traced(TICK)} of {ticks} counted ticks and "
            f"{traced(PREFILL)} of {calls} counted prefill calls lie in "
            "the traced window")
    return out


def counts(run, tick=(), prefill=(), split=()):
    """{key: the registry's delta scaled to the traced window}: ``tick``
    keys are counted by ticks alone, ``prefill`` keys by prefill members
    alone, ``split`` is (total key, its prefill part's key) pairs, given
    back under the total's name.  None where a key is missing or nothing
    can be scaled."""
    reg = run.records["registry"]
    need = list(tick) + list(prefill) + [k for pair in split for k in pair]
    if any(k not in reg for k in need):
        return None
    got = shares(run)
    if got is None:
        return None
    t, p = got
    out = {k: reg[k] * t for k in tick}
    out.update({k: reg[k] * p for k in prefill})
    out.update({total: (reg[total] - reg[part]) * t + reg[part] * p
                for total, part in split})
    return out
