"""Mean host time of one compiled tick in the window: the tick less its
wait for the device's ``fin`` (``serving.tick.host_ms``)."""
from layer_metrics import span_lib


def read(run):
    return span_lib.mean_ms(run, "tick_host_ms.serve",
                            "serving.tick.host_ms")
