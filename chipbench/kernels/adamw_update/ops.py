"""The AdamW update of every parameter: read the float32 parameter and
two moments and the gradient in the type the backward pass leaves it in
(the compute type), write parameter and two moments; about 12 FLOP a
parameter, so the memory bounds it."""
from __future__ import annotations

import importlib
import math


def work(run):
    spec = importlib.import_module(
        "reference." + run.config["reference"]).weight_spec(run.model_cfg)
    n = sum(math.prod(shape) for shape, _, _ in spec.values())
    steps = run.records["steps"]
    grad = 2 if run.cell["compute_dtype"] == "bfloat16" else 4
    return {"flops": 12.0 * n * steps,
            "bytes": (24.0 + grad) * n * steps}
