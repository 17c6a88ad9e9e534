"""The routed experts' grouped products' share of their roofline: the
device time of the ``expert_gmm`` events (kernels/expert_ffn/*.json)
against the weights of the experts hit and the pairs computed in the
window, by the program's own counts."""
import metrics_lib
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "expert_ffn_roofline.serve",
                            "serving.moe.pairs_local",
                            "serving.moe.experts_hit",
                            "serving.moe.prefill.pairs",
                            "serving.moe.prefill.experts_hit")
    if reg is None or reg["serving.moe.pairs_local"] <= 0:
        return None
    return metrics_lib.roofline_share(run, "expert_ffn")
