"""The serving step's share of the chip's peak for a decoder with latent
attention and sparse experts: as ``mfu_moe.serve``, with the forward
FLOPs counted by ``kernels/mla_model_step/ops.py`` — the model's own
(up-projected) attention arithmetic over the positions the program
counted, the routed experts by the pairs it counted, the tokens its own
(``serving.tokens_generated``, ``serving.prefill.tokens_useful``).
Every count is scaled to the traced window (``window_lib``)."""
import metrics_lib
from layer_metrics import span_lib, window_lib


TICK = ("serving.kv.context_token_ticks", "serving.tokens_generated")
PREFILL = ("serving.prefill.context_tokens", "serving.prefill.tokens_useful")
SPLIT = (("serving.moe.pairs_local", "serving.moe.prefill.pairs"),)


def read(run):
    if "kv_lora_rank" not in run.model_cfg:
        return None
    reg = span_lib.registry(run, "mfu_mla.serve", *TICK, *PREFILL,
                            *SPLIT[0])
    if reg is None:
        return None
    reg = window_lib.counts(run, TICK, PREFILL, SPLIT)
    if reg is None:
        return None
    ops = metrics_lib.load_ops("mla_model_step")
    flops = ops.forward_flops(
        run.model_cfg,
        reg["serving.prefill.tokens_useful"] + reg["serving.tokens_generated"],
        reg["serving.moe.pairs_local"],
        reg["serving.kv.context_token_ticks"]
        + reg["serving.prefill.context_tokens"])
    return 100.0 * flops / run.records["seconds"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
