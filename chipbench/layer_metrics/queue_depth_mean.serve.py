"""Mean number of requests waiting for a slot over the window
(``serving.queue.request_ms`` over the window's milliseconds); 0.0 when
none waited."""
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "queue_depth_mean.serve",
                            "serving.queue.request_ms")
    if reg is None:
        return None
    return reg["serving.queue.request_ms"] / (run.records["seconds"] * 1e3)
