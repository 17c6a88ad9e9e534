"""Of the state rows the window's compiled ticks passed through the
update, the share that belonged to a decoding request."""
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "state_rows_live_share.serve",
                            "serving.state.row_ticks_live",
                            "serving.state.row_ticks_total")
    if reg is None or reg["serving.state.row_ticks_total"] <= 0:
        return None
    return 100.0 * reg["serving.state.row_ticks_live"] \
        / reg["serving.state.row_ticks_total"]
