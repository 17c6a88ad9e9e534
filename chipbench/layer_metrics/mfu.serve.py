"""The serving step's share of the chip's peak: the benchmark's forward
FLOPs of every prompt token prefilled and every token decoded in the
window (useful tokens: the padding rows of a prefill round and of a
tick do not count), over window seconds and the published bf16 peak."""
import metrics_lib


def read(run):
    ops = metrics_lib.load_ops("model_step")
    rec = run.records
    flops = ops.forward_flops(
        run.model_cfg, rec["prefill_tokens"] + rec["tokens"],
        rec["prefill_ctx"] + rec["decode_ctx"])
    return 100.0 * flops / rec["seconds"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
