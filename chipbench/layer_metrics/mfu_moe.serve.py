"""The serving step's share of the chip's peak for a sparse-expert
decoder with window and full attention layers: as ``mfu.serve``, with
the forward FLOPs counted by what ran here
(``kernels/moe_model_step/ops.py``): the routed experts by the pairs the
program counted, attention by the positions each layer kind sees.  The
tokens are the program's own too (``serving.tokens_generated``,
``serving.prefill.tokens_useful``): the client sees a compiled tick's
tokens only when the scheduler flushes, and work and tokens have to be of
the same window.  Every count is scaled to the traced window
(``window_lib``)."""
import metrics_lib
from layer_metrics import span_lib, window_lib


TICK = ("serving.kv.context_token_ticks", "serving.kv.window.token_ticks",
        "serving.tokens_generated")
PREFILL = ("serving.prefill.context_tokens",
           "serving.prefill.window_context_tokens",
           "serving.prefill.tokens_useful")
SPLIT = (("serving.moe.pairs_local", "serving.moe.prefill.pairs"),)


def read(run):
    reg = span_lib.registry(run, "mfu_moe.serve", *TICK, *PREFILL,
                            *SPLIT[0])
    if reg is None:
        return None
    reg = window_lib.counts(run, TICK, PREFILL, SPLIT)
    if reg is None:
        return None
    ops = metrics_lib.load_ops("moe_model_step")
    rec = run.records
    flops = ops.forward_flops(
        run.model_cfg,
        reg["serving.prefill.tokens_useful"] + reg["serving.tokens_generated"],
        reg["serving.moe.pairs_local"],
        reg["serving.kv.context_token_ticks"]
        + reg["serving.prefill.context_tokens"],
        reg["serving.kv.window.token_ticks"]
        + reg["serving.prefill.window_context_tokens"])
    return 100.0 * flops / rec["seconds"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
