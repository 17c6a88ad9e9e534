"""Mean wall time of one scheduler iteration in the window, by the
program's own ``serving.tick_ms`` histogram (host clock)."""


def read(run):
    reg = run.records["registry"]
    n = reg.get("serving.tick_ms.count", 0)
    if n <= 0:
        return None
    return reg["serving.tick_ms.sum"] / n
