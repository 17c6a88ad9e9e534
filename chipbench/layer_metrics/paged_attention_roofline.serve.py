"""Decode attention's share of its roofline: the device time of the
events that read the pages (kernels/paged_decode_attention/*.json)
against the KV bytes of the tokens decoded in the window."""
import metrics_lib


def read(run):
    return metrics_lib.roofline_share(run, "paged_decode_attention")
