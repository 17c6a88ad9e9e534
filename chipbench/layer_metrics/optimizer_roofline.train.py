"""The AdamW update's share of its roofline (memory-bound): the device
time of the events that do it (kernels/adamw_update/*.json) against 28
bytes a parameter a step."""
import metrics_lib


def read(run):
    return metrics_lib.roofline_share(run, "adamw_update")
