"""Share of the device's busy time spent in the latent decode read (the
events of kernels/mla_decode_attention/*.json): whether the mechanism
does most of a tick's work.  None where no such event is in the trace
(a share is never returned as 0)."""
import metrics_lib


def read(run):
    seconds, seen = metrics_lib.work_seconds(run, "mla_decode_attention")
    if not seen or seconds <= 0:
        return None
    return 100.0 * seconds / run.reduced["busy_s"]
