"""Median idle gap on the device between two consecutive executions of
the tick program (its name matches ``serving_tick``) with no other
program between them.  A CPU rehearsal has no ``XLA Modules`` line: the
tick's mean host time stands in."""
from layer_metrics import span_lib


def read(run):
    if run.rehearsal:
        return span_lib.mean_ms(run, "tick_gap_ms.serve",
                                "serving.tick.host_ms")
    execs = span_lib.executions(run, "tick_gap_ms.serve")
    return None if execs is None else span_lib.median_tick_gap_ms(execs)
