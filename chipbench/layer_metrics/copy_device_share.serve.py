"""Share of the device's busy time spent in XLA's own ``copy``
instructions: relayouts and moves that do no work of the model's.  An
``XLA Ops`` event is named by its whole HLO line, so a copy is the event
whose text reads ``%copy.N = <shape> copy(...)``; the asynchronous
``copy-start`` / ``copy-done`` pairs (operands prefetched under other
work) are not counted.  A page pool that lives in another layout than
its readers and writers want shows up here, many short events the ten
longest ops never list.  A CPU rehearsal's trace names an event by the
instruction alone (``copy.N``): the same share of its pseudo devices
stands in."""
import trace_reduce

#: on the chip, and as a CPU's client threads name the instruction
PATTERNS = (r"^%[\w.\-]+ = \S+ copy\(", r"^copy(\.\d+)?$")


def read(run):
    seconds, _, _ = trace_reduce.match_ops(run.reduced["ops"], PATTERNS)
    return 100.0 * seconds / run.reduced["busy_s"]
