"""The whole training step's share of the chips' peak: the benchmark's
own forward-and-backward FLOPs of the tokens the window finished, over
window seconds, chips and the published bf16 peak."""
import metrics_lib


def read(run):
    ops = metrics_lib.load_ops("model_step")
    rec, mix = run.records, run.mix
    flops = ops.train_flops(run.model_cfg, mix["batch"], mix["seq"],
                            rec["steps"])
    return 100.0 * flops / rec["elapsed_s"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
