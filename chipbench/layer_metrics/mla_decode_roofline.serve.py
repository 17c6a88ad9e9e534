"""Latent decode attention's share of its roofline: the device time of
the ``mla_decode`` events (kernels/mla_decode_attention/*.json) against
the cached rows of every live position, 576 x 2 bytes each a layer a
tick, and the absorbed form's FLOPs over them, by the program's own sum
of contexts."""
import metrics_lib
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "mla_decode_roofline.serve",
                            "serving.kv.context_token_ticks")
    if reg is None or reg["serving.kv.context_token_ticks"] <= 0:
        return None
    return metrics_lib.roofline_share(run, "mla_decode_attention")
