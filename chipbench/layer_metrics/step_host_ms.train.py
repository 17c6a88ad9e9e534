"""Mean host time of one ``CompiledTrainStep`` call in the window
(``train.step_ms``: gather, launch, write-back; the device runs on)."""
from layer_metrics import span_lib


def read(run):
    return span_lib.mean_ms(run, "step_host_ms.train", "train.step_ms")
