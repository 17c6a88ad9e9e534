"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals, mean over the cell's devices)
/ window."""


def read(run):
    r = run.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
