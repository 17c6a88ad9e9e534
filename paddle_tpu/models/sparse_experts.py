"""The expert layer that is told which experts it holds, as the decoders
with sparse experts share it (``models/cohere_moe.py``,
``models/sarvam_mla.py``).

``s = sigmoid(W_r x)`` in float32 over all published experts; ``T`` the
``top_k`` of ``s`` (of ``s + b`` with a selection bias ``b``, which moves
the CHOICE alone); ``g_e = gate_scale * s_e / sum_{j in T} s_j`` (no
``gate_scale``: 1); the layer gives ``sum_{e in T, e held} g_e f_e(x)``
plus its shared experts' outputs, their mean or their sum, each ``f`` a
gated-SiLU MLP of ``expert_width``.  ``held_experts`` = (first, count) is
one chip's share: the layer routes over all, computes what its own give
through ``pallas.moe.routed_experts``, drops nothing, keeps static
shapes; what the absent experts would add is left out.

Leaves: ``gate.weight`` [h, E] the router (``gate.expert_bias`` [E] the
selection bias), ``experts.{gate,up,down}_proj`` [E_held, in, out],
``shared_experts.*`` [S, in, out].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from ..observability.tracing import scope

from ..core.dispatch import apply_op
from ..nn import Layer
from ..nn.initializer import Normal
from ..pallas import moe as _moe


class Router(Layer):
    def __init__(self, hidden_size, num_experts, std, selection_bias):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden_size, num_experts), default_initializer=Normal(0.0, std))
        if selection_bias:
            #: added to the scores for the choice alone
            self.expert_bias = self.create_parameter(
                (num_experts,), default_initializer=Normal(0.0, std))


class ExpertStack(Layer):
    """``count`` gated-SiLU experts, their three matrices stacked."""

    def __init__(self, hidden_size, width, count, std, down_std):
        super().__init__()
        self.gate_proj = self.create_parameter(
            (count, hidden_size, width),
            default_initializer=Normal(0.0, std))
        self.up_proj = self.create_parameter(
            (count, hidden_size, width),
            default_initializer=Normal(0.0, std))
        self.down_proj = self.create_parameter(
            (count, width, hidden_size),
            default_initializer=Normal(0.0, down_std))


class SparseExpertMLP(Layer):
    """See the module's text.  ``shared_reduce`` is ``"mean"`` or
    ``"sum"``; ``down_std`` is the down projections' initial deviation
    where it is not ``std``."""

    def __init__(self, hidden_size, expert_width, num_experts, held_experts,
                 top_k, num_shared, std, down_std=None, selection_bias=False,
                 gate_scale=None, shared_reduce="mean"):
        super().__init__()
        if shared_reduce not in ("mean", "sum"):
            raise ValueError(f"shared_reduce {shared_reduce!r}: the shared "
                             "experts' outputs are averaged ('mean') or "
                             "added ('sum')")
        down_std = std if down_std is None else down_std
        self.held_experts, self.top_k = tuple(held_experts), top_k
        self.gate_scale = gate_scale
        self.gate = Router(hidden_size, num_experts, std, selection_bias)
        self.experts = ExpertStack(hidden_size, expert_width,
                                   self.held_experts[1], std, down_std)
        self.shared_experts = ExpertStack(hidden_size, expert_width,
                                          num_shared, std, down_std)
        #: what the sum of the shared experts' outputs is multiplied by
        #: (None: it stands as it is)
        self.shared_scale = 1.0 / num_shared if shared_reduce == "mean" \
            else None

    def route(self, tokens, wr, bias=None):
        """(experts [T, k] int32, gates [T, k] float32) of ``tokens``
        [T, h]: raw arrays in, raw arrays out."""
        logits = _moe.router_logits(tokens, wr)
        if bias is None and self.gate_scale is None:
            # a family with neither passes neither: what stands in for
            # the routing in a test of it takes (logits, k)
            return _moe.route_sigmoid_topk(logits, self.top_k)
        return _moe.route_sigmoid_topk(logits, self.top_k, bias=bias,
                                       scale=self.gate_scale)

    def router_leaves(self):
        """The router's parameters as ``route`` takes them after the
        tokens."""
        bias = getattr(self.gate, "expert_bias", None)
        return (self.gate.weight,) if bias is None \
            else (self.gate.weight, bias)

    def routed(self, x, valid=None):
        """The held routed experts' part of the layer for ``x`` [B, S,
        h], and the pairs computed for each held expert ([E_held] int32;
        with ``valid`` [B], each row's count of real positions, the
        others' pairs are computed and not counted)."""
        b, s, h = x.shape
        held, ex = self.held_experts, self.experts
        leaves = self.router_leaves()

        def routed(xa, *rest):
            router, (wg, wu, wd, *valid) = \
                rest[:len(leaves)], rest[len(leaves):]
            tokens = xa.reshape(b * s, h)
            experts, gates = self.route(tokens, *router)
            real = None
            if valid:
                real = (jnp.arange(s)[None, :] < valid[0][:, None]) \
                    .reshape(-1)
            y, counts = _moe.routed_experts(tokens, experts, gates, wg, wu,
                                            wd, held, real)
            return y.reshape(b, s, h), counts

        args = (x,) + leaves + (ex.gate_proj, ex.up_proj, ex.down_proj)
        return apply_op(
            "routed_experts_biased" if len(leaves) > 1 else "routed_experts",
            routed, args if valid is None else args + (valid,))

    def forward(self, x, cache=None):
        b, s, h = x.shape
        valid = None if cache is None else cache.get("valid_len")
        sh = self.shared_experts

        def shared(xa, wg, wu, wd):
            tokens = xa.reshape(b * s, h)
            acc = None
            for j in range(wg.shape[0]):
                # an expert at a time: slicing the stack's first axis is
                # a view, a product over the stacked axis a transpose
                y = jnp.matmul(jax.nn.silu(jnp.matmul(tokens, wg[j]))
                               * jnp.matmul(tokens, wu[j]), wd[j]) \
                    .astype(jnp.float32)
                acc = y if acc is None else acc + y
            if self.shared_scale is not None:
                acc = acc * self.shared_scale
            return acc.astype(xa.dtype).reshape(b, s, h)

        with scope("moe"):
            y, counts = self.routed(x, valid)
        with scope("moe_shared"):
            y = y + apply_op(
                "shared_experts" if self.shared_scale is not None
                else "shared_experts_sum", shared,
                (x, sh.gate_proj, sh.up_proj, sh.down_proj))
        if valid is not None:
            cache["moe_counts"] = counts
        return y
