"""The serving step's share of the chip's peak for a per-layer stack of
Mamba-2, attention and latent expert blocks: as ``mfu_moe.serve``, with
the forward FLOPs counted by layer kind
(``kernels/hybrid_moe_model_step/ops.py``): the routed experts by the
pairs the program counted, attention by the positions its layers see,
the tokens the program's own (``serving.tokens_generated``,
``serving.prefill.tokens_useful``).  Every count is scaled to the traced
window (``window_lib``)."""
import metrics_lib
from layer_metrics import span_lib, window_lib


TICK = ("serving.kv.context_token_ticks", "serving.tokens_generated")
PREFILL = ("serving.prefill.context_tokens", "serving.prefill.tokens_useful")
SPLIT = (("serving.moe.pairs_local", "serving.moe.prefill.pairs"),)


def read(run):
    if "moe_latent_size" not in run.model_cfg:
        return None
    reg = span_lib.registry(run, "mfu_hybrid_moe.serve", *TICK, *PREFILL,
                            *SPLIT[0])
    if reg is None:
        return None
    reg = window_lib.counts(run, TICK, PREFILL, SPLIT)
    if reg is None:
        return None
    ops = metrics_lib.load_ops("hybrid_moe_model_step")
    flops = ops.forward_flops(
        run.model_cfg,
        reg["serving.prefill.tokens_useful"] + reg["serving.tokens_generated"],
        reg["serving.moe.pairs_local"],
        reg["serving.kv.context_token_ticks"]
        + reg["serving.prefill.context_tokens"])
    return 100.0 * flops / run.records["seconds"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
