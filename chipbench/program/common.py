"""Glue shared by the builders: put the benchmark's weights into the
program's model, and read them back out."""
from __future__ import annotations

import jax.numpy as jnp


def drop_weights(model):
    """Free the arrays the program's own initializers made, so that the
    benchmark's weights never sit beside them."""
    for p in model.parameters():
        p._data_ = jnp.zeros((), p._data_.dtype)


def install_weights(model, weights):
    """``weights`` ({name: array}) become the model's parameters.  The
    names and shapes have to be exactly the model's."""
    named = dict(model.named_parameters())
    if set(named) != set(weights):
        raise ValueError(
            "the reference's leaves are not the program's: "
            f"{sorted(set(named) ^ set(weights))[:8]}")
    for name, p in named.items():
        w = weights[name]
        if tuple(p.shape) not in ((), tuple(w.shape)):
            raise ValueError(f"{name}: program {tuple(p.shape)} vs "
                             f"reference {tuple(w.shape)}")
        p._data_ = w


def read_weights(model):
    return {n: p._data_ for n, p in model.named_parameters()}
