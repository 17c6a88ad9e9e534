"""Device time by program scope: the instruction → scope tables of the
compiled hot-path programs.

A profiler trace names a device operation by its HLO instruction
(``%fusion.515``), and ``tracing.scope`` names reach only the
instruction's ``op_name`` metadata inside the compiled executable.
This module is the join between the two.  Every program a hot path
compiles (``CompiledTrainStep``'s full and micro step, each mode of the
serving tick, each prefill member, ``state_reset``) hands its compiled
executable to :func:`publish`, which keeps nothing but the host-side
``HloModule`` objects the executable already holds — no text, no
device memory, nothing that names the engine or the model.  The first
:func:`tables` call prints and parses them: per program, each
instruction the device runs (the entry computation and the bodies of
``while`` / ``conditional`` / ``call``) mapped to its scope path, its
direction (``fwd``, or ``bwd`` under the tape's marker), its result
type (two programs both have a ``%fusion.12``), its kind, and — for a
``while`` / ``conditional`` / ``call`` — the instructions inside it.

Reading a trace by scope (docs/OBSERVABILITY.md, "Names on the
device")::

    jax.profiler.start_trace(d); ...; jax.profiler.stop_trace()
    tabs = scopes.tables()          # {program: {instruction: entry}}
    # an ``XLA Ops`` event's name starts ``%<instruction> = <type> ``
"""
from __future__ import annotations

import re
import threading

from . import tracing

_lock = threading.Lock()
_published: dict = {}        # program name -> [HloModule, ...] or a table
#: tables parsed so far in this process: nothing is, until ``tables()``
builds = 0

#: instructions that are an executable's plumbing, not device work
_PLUMBING = frozenset({"parameter", "constant", "tuple",
                       "get-tuple-element", "bitcast"})
_CONTAINERS = frozenset({"while", "conditional", "call"})
_PRODUCTS = frozenset({"dot", "convolution"})
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_JIT = re.compile(r"jit\([^()]*\)")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/|\s+")


def publish(compiled):
    """Keep the HLO of ``compiled`` (a ``jax.stages.Compiled``) for the
    tables, under its module's name (``jit_train_step``,
    ``jit_serving_tick_greedy``, ``jit_serving_prefill_r2``).  Host
    objects of the executable's own; nothing is printed or parsed here.
    A program published again under the same name replaces the old."""
    modules = list(compiled.runtime_executable().hlo_modules())
    if modules:
        with _lock:
            _published[modules[0].name] = modules


def tables():
    """``{program name: {instruction name: entry}}`` of every program
    published so far; builds, once, the tables not built yet.  An entry
    is ``{"scope": "attn", "dir": "fwd" | "bwd", "type":
    "bf16[8192,2048]", "kind": "fusion"}``, with ``"mixed": True`` on a
    fusion whose instructions carry different scopes and ``"body":
    [instruction names]`` on a ``while`` / ``conditional`` / ``call``.
    ``scope`` is the path of ``tracing.scope`` names joined by ``/``,
    ``""`` where the instruction carries none."""
    global builds
    with _lock:
        pending = {name: held for name, held in _published.items()
                   if isinstance(held, list)}
    # parsed outside the lock: a hot path that publishes meanwhile does
    # not wait for it
    for name, modules in pending.items():
        table = {}
        for module in modules:
            table.update(build_table(module.to_string()))
        with _lock:
            if _published.get(name) is modules:     # not replaced since
                _published[name] = table
                builds += 1
    with _lock:
        return {name: held for name, held in _published.items()
                if isinstance(held, dict)}


def clear():
    """Forget every published program (tests)."""
    with _lock:
        _published.clear()


def type_key(text):
    """A result type as tables and readers compare it: the shape text
    without layouts, comments and blanks."""
    return _LAYOUT.sub("", text)


def split_instruction(line):
    """(name, result type, kind, rest) of one HLO instruction line —
    also the text of an ``XLA Ops`` trace event —, or None."""
    head, sep, rest = line.strip().partition(" = ")
    if not sep:
        return None
    name = head.split()[-1].lstrip("%")
    if rest.startswith("("):            # a tuple type: balanced parens
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ")
    if end <= 0:
        return None
    kind, paren, tail = rest[end:].lstrip().partition("(")
    if not paren:
        return None
    return name, type_key(rest[:end]), kind, tail


def scope_of(op_name, names=None):
    """(scope path joined by ``/``, direction) from an ``op_name``: the
    elements that are ``tracing.scope`` names, in order, the tape's
    marker taken out and read as the direction."""
    names = tracing.scope_names if names is None else names
    # a name once: the backward's recomputed forward reads
    # ``loss/bwd/transpose(loss)/jvp()/mul``
    path = list(dict.fromkeys(
        t for t in re.findall(r"[^/()]+", _JIT.sub("", op_name))
        if t in names))
    direction = "bwd" if tracing.BACKWARD in path else "fwd"
    return "/".join(t for t in path if t != tracing.BACKWARD), direction


def _vote(votes):
    """The (scope, direction) most of ``votes`` carry, named ones
    first; None without votes."""
    if not votes:
        return None
    named = [v for v in votes if v[0]] or votes
    return max(set(named), key=named.count)


def build_table(text, names=None):
    """The table of one HLO module's text (``HloModule.to_string()``,
    or ``Compiled.as_text()``)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            if line.endswith("{") and not line.startswith(" ") \
                    and "(" in line:
                head = line.split("(", 1)[0].split()
                cur = comps.setdefault(head[-1].lstrip("%"), [])
                if head[0] == "ENTRY":
                    entry = head[-1].lstrip("%")
            continue
        if line.startswith("}"):
            cur = None
            continue
        got = split_instruction(line)
        if got is None:
            continue
        name, typ, kind, tail = got
        found = _OP_NAME.search(tail)
        called = [c.strip().lstrip("%") for m in _CALLED.finditer(tail)
                  for c in (m.group(1) or m.group(2)).split(",")]
        cur.append((name, typ, kind,
                    scope_of(found.group(1), names) if found else None,
                    called))

    def fused_scope(comp, own):
        """A fusion's: its product's, else what most of its instructions
        carry, else its own metadata's; and whether they disagree."""
        inner = [r for r in comps.get(comp, ()) if r[2] not in _PLUMBING]
        votes = [r[3] for r in inner if r[3] is not None]
        products = [r[3] for r in inner
                    if r[2] in _PRODUCTS and r[3] is not None]
        chosen = _vote(products) or _vote(votes) or own or ("", "fwd")
        return chosen, len({v for v in votes if v[0]} | {chosen}) > 1

    table = {}

    def walk(comp):
        """Add the instructions ``comp`` makes the device run; returns
        their names."""
        ran = []
        for name, typ, kind, own, called in comps.get(comp, ()):
            if kind in _PLUMBING:
                continue
            ran.append(name)
            if name in table:
                continue
            entry_ = table[name] = {"type": typ, "kind": kind}
            scope, mixed = own or ("", "fwd"), False
            if kind == "fusion" and called:
                scope, mixed = fused_scope(called[0], own)
            elif kind in _CONTAINERS or kind.startswith("async"):
                body = [n for c in called for n in walk(c)]
                if kind in _CONTAINERS:
                    entry_["body"] = body
                    if own is None or not own[0]:
                        # a loop XLA made carries no name of its own
                        scope = _vote([(table[n]["scope"], table[n]["dir"])
                                       for n in body]) or scope
            entry_["scope"], entry_["dir"] = scope
            if mixed:
                entry_["mixed"] = True
        return ran

    if entry is not None:
        walk(entry)
    return table
