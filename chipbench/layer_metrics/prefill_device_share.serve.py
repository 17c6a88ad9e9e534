"""Share of the device's busy time taken by programs other than the
tick (those whose name does not match ``serving_tick``): the eager
prefill's.  A CPU rehearsal has no ``XLA Modules`` line: the prefill
chunk calls' share of the host time of chunks and ticks stands in."""
from layer_metrics import span_lib

NAME = "prefill_device_share.serve"


def read(run):
    reg = span_lib.prefill(run, NAME)
    if reg is None:
        return None
    if run.rehearsal:
        chunks = reg["serving.prefill_chunk_ms.sum"]
        return 100.0 * chunks / (chunks + reg["serving.decode_ms.sum"])
    execs = span_lib.executions(run, NAME)
    if execs is None:
        return None
    other = sum(e - s for s, e, tick in execs if not tick)
    return 100.0 * other / run.reduced["busy_s"]
