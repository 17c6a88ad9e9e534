"""Mean time of one admission's reset of its slot's recurrent state in
the window (``serving.state.reset_ms``)."""
from layer_metrics import span_lib


def read(run):
    return span_lib.mean_ms(run, "state_reset_ms.serve",
                            "serving.state.reset_ms")
