"""Decode attention's share of its roofline in a hybrid decoder: the
device time of the ``paged_decode`` events
(kernels/paged_decode_attention_hybrid/*.json) against the KV bytes of
the tokens decoded in the window, over the attention layers alone."""
import metrics_lib


def read(run):
    return metrics_lib.roofline_share(run, "paged_decode_attention_hybrid")
