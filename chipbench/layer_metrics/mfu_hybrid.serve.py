"""The serving step's share of the chip's peak for a hybrid decoder: as
``mfu.serve``, with the forward FLOPs counted by layer kind
(``kernels/hybrid_model_step/ops.py``) — attention's context term for
the attention layers only, the recurrence's for the others."""
import metrics_lib


def read(run):
    ops = metrics_lib.load_ops("hybrid_model_step")
    rec = run.records
    flops = ops.forward_flops(
        run.model_cfg, rec["prefill_tokens"] + rec["tokens"],
        rec["prefill_ctx"] + rec["decode_ctx"])
    return 100.0 * flops / rec["seconds"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
