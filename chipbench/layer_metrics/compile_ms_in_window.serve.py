"""Milliseconds spent building programs inside the window, compiled or
loaded from the compile cache (``jit.compile_ms``); 0.0 when none was."""
from layer_metrics import span_lib


def read(run):
    return span_lib.compile_ms(run, "compile_ms_in_window.serve")
