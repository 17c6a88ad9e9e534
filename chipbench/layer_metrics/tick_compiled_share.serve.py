"""Share of the window's scheduler iterations that ran as the one
compiled tick program."""
import metrics_lib


def read(run):
    return metrics_lib.registry_share(run, "serving.tick.compiled_hits",
                                      "serving.tick.fallbacks")
