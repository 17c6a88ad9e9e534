"""Share of the window's steps that ran the compiled program and not the
eager lane: the program's own counters, deltas over the window."""
import metrics_lib


def read(run):
    return metrics_lib.registry_share(run, "jit.compiled_step_hit",
                                      "jit.compiled_step_fallback")
