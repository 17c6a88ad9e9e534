"""The decode step's state update of the Mamba-2 layers: every decoded
token reads and writes, in every such layer, its whole SSM state [H, P,
N] in float32 — that is all the kernel moves; the 3-row
convolution window is the surrounding XLA's, not the kernel's, and is
left out — at about 6 FLOP an element (decay, outer product, the
product with C), so the memory bounds it."""
from __future__ import annotations


def work(run):
    cfg = run.model_cfg
    layers = sum(kind == "mamba" for kind in cfg["layer_types"])
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    tokens = run.records["tokens"]
    return {"flops": 6.0 * state * layers * tokens,
            "bytes": 2.0 * state * 4 * layers * tokens}
