"""The feed-forward products of a training step, forward and backward,
at the cell's shapes: two products of d x d_ff a token a layer forward
(2 FLOP a multiply-add), and twice that backward (the input's and the
weight's gradient of each): 12 d d_ff a token a layer, 48 d^2 at d_ff =
4 d.  Bytes: both weights read forward and backward and their gradients
written, the layer's input, hidden and output activations read and
written, in the compute type; the products bound it by far."""
from __future__ import annotations


def work(run):
    cfg, mix = run.model_cfg, run.mix
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    tokens = mix["batch"] * mix["seq"]
    steps, layers = run.records["steps"], cfg["num_layers"]
    el = 2 if run.cell["compute_dtype"] == "bfloat16" else 4
    weights = 3 * 2 * d * ff * el
    acts = 3 * 2 * tokens * (d + ff) * el
    return {"flops": 12.0 * d * ff * tokens * layers * steps,
            "bytes": float(weights + acts) * layers * steps}
