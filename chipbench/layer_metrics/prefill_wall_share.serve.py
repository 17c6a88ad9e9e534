"""Share of the window's wall time that the scheduler thread spent in
prefill chunk calls (``serving.prefill_chunk_ms``, host clock)."""


def read(run):
    reg = run.records["registry"]
    if reg.get("serving.prefill_chunk_ms.count", 0) <= 0:
        return None
    return 100.0 * reg["serving.prefill_chunk_ms.sum"] * 1e-3 \
        / run.records["seconds"]
