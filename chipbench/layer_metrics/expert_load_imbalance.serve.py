"""How unevenly the router loads the experts held here: the most-loaded
held expert's pairs over the mean over the held experts, over the
window (1 = even; the layer's time follows the experts hit, a tile's
padding the most-loaded)."""
from layer_metrics import span_lib

PREFIX = "serving.moe.pairs_by_expert."


def read(run):
    reg = span_lib.registry(run, "expert_load_imbalance.serve",
                            "serving.moe.pairs_local")
    if reg is None or reg["serving.moe.pairs_local"] <= 0:
        return None
    held = run.model_cfg["held_experts"][1]
    loads = [v for k, v in reg.items() if k.startswith(PREFIX)]
    return max(loads) / (reg["serving.moe.pairs_local"] / held)
