"""The expert layer that is told which experts it holds, as the decoders
with sparse experts share it (``models/cohere_moe.py``,
``models/sarvam_mla.py``, ``models/nemotron_h.py``, ``models/mellum.py``).

``s = sigmoid(W_r x)`` in float32 over all published experts; ``T`` the
``top_k`` of ``s`` (of ``s + b`` with a selection bias ``b``, which moves
the CHOICE alone); ``g_e = gate_scale * s_e / sum_{j in T} s_j`` (no
``gate_scale``: 1).  With ``scoring="softmax"`` the scores are ``p =
softmax(W_r x)`` over all experts instead, and ``g_e = p_e / sum_{j in
T} p_j`` (no bias, no scale).  The layer gives ``sum_{e in T, e held}
g_e f_e(x)`` plus its shared experts' outputs (none where
``num_shared`` is 0), their mean or their sum, each ``f`` an
MLP of ``expert_width`` with the activation ``expert_act``: "swiglu"
(``(silu(x Wg) * (x Wu)) Wd``) or "relu2" (``relu(x Wu)^2 Wd``, one up
matrix).  ``held_experts`` = (first, count) is one chip's share: the
layer routes over all, computes what its own give through
``pallas.moe.routed_experts``, drops nothing, keeps static shapes; what
the absent experts would add is left out.

A LATENT layer (``latent_size``; LatentMoE, ``models/nemotron_h.py``)
runs its routed experts in a narrower space: ``l = W_lat_down x``, the
routed sum over ``l``, then ``W_lat_up`` back to ``h``; the router reads
``x`` itself, and the shared experts work at full width (of
``shared_width``).  ``W_lat_up`` is linear, so the chips' shares, each
through it, still add up to the whole layer.

Leaves: ``gate.weight`` [h, E] the router (``gate.expert_bias`` [E] the
selection bias), ``experts.{gate,up,down}_proj`` [E_held, in, out] (no
``gate_proj`` for "relu2"), ``shared_experts.*`` [S, in, out];
``fc1_latent_proj.weight`` [h, latent] and ``fc2_latent_proj.weight``
[latent, h] in a latent layer.  A layer without shared experts has no
``shared_experts`` leaves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from ..observability.tracing import scope

from ..core.dispatch import apply_op
from ..nn import Layer, Linear
from ..nn.initializer import Normal, ParamAttr
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


#: an expert's activations: ``pallas/moe.py``'s, less the plain product
EXPERT_ACTS = tuple(a for a in _moe.ACTS if a is not None)


class ExpertStack(Layer):
    """``count`` experts of the activation ``act``, their matrices
    stacked: "swiglu" has gate, up and down, "relu2" up and down."""

    def __init__(self, hidden_size, width, count, std, down_std,
                 act="swiglu"):
        super().__init__()
        if act not in EXPERT_ACTS:
            raise ValueError(f"expert act {act!r}: one of {EXPERT_ACTS}")
        self.act = act
        if act == "swiglu":
            self.gate_proj = self.create_parameter(
                (count, hidden_size, width),
                default_initializer=Normal(0.0, std))
        self.up_proj = self.create_parameter(
            (count, hidden_size, width),
            default_initializer=Normal(0.0, std))
        self.down_proj = self.create_parameter(
            (count, width, hidden_size),
            default_initializer=Normal(0.0, down_std))

    def matrices(self):
        """(gate, up, down), or (up, down) for "relu2"."""
        up_down = (self.up_proj, self.down_proj)
        return up_down if self.act == "relu2" else (self.gate_proj,) + up_down


class SparseExpertMLP(Layer):
    """See the module's text.  ``shared_reduce`` is ``"mean"`` or
    ``"sum"``; ``down_std`` is the down projections' initial deviation
    where it is not ``std``; ``expert_act`` the routed experts'
    activation, ``latent_size`` the width they work at (None: ``h``);
    ``shared_width`` the shared experts' width (None: the routed
    experts'), of the routed experts' activation; ``scoring`` the
    router's, "sigmoid" or "softmax" (``pallas.moe.SCORINGS``)."""

    def __init__(self, hidden_size, expert_width, num_experts, held_experts,
                 top_k, num_shared, std, down_std=None, selection_bias=False,
                 gate_scale=None, shared_reduce="mean", expert_act="swiglu",
                 latent_size=None, shared_width=None, scoring="sigmoid"):
        super().__init__()
        if shared_reduce not in ("mean", "sum"):
            raise ValueError(f"shared_reduce {shared_reduce!r}: the shared "
                             "experts' outputs are averaged ('mean') or "
                             "added ('sum')")
        if scoring not in _moe.SCORINGS:
            raise ValueError(f"scoring {scoring!r}: one of {_moe.SCORINGS}")
        if scoring == "softmax" and (selection_bias or gate_scale):
            raise ValueError("a softmax router takes no selection bias "
                             "and no gate scale")
        down_std = std if down_std is None else down_std
        self.held_experts, self.top_k = tuple(held_experts), top_k
        self.gate_scale = gate_scale
        self.scoring = scoring
        self.gate = Router(hidden_size, num_experts, std, selection_bias)
        inner = latent_size or hidden_size
        self.experts = ExpertStack(inner, expert_width,
                                   self.held_experts[1], std, down_std,
                                   expert_act)
        self.shared_experts = ExpertStack(
            hidden_size, shared_width or expert_width, num_shared, std,
            down_std, expert_act) if num_shared else None
        self.latent = latent_size is not None
        if self.latent:
            self.fc1_latent_proj = Linear(
                hidden_size, latent_size, bias_attr=False,
                weight_attr=ParamAttr(initializer=Normal(0.0, std)))
            self.fc2_latent_proj = Linear(
                latent_size, hidden_size, bias_attr=False,
                weight_attr=ParamAttr(initializer=Normal(0.0, down_std)))
        #: what the sum of the shared experts' outputs is multiplied by
        #: (None: it stands as it is)
        self.shared_scale = 1.0 / num_shared \
            if shared_reduce == "mean" and num_shared else None

    def route(self, tokens, wr, bias=None):
        """(experts [T, k] int32, gates [T, k] float32) of ``tokens``
        [T, h]: raw arrays in, raw arrays out."""
        logits = _moe.router_logits(tokens, wr)
        if self.scoring == "softmax":
            return _moe.route_softmax_topk(logits, self.top_k)
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
        h] (a latent layer's through its two latent projections), and
        the pairs computed for each held expert ([E_held] int32; with
        ``valid`` [B], each row's count of real positions, the others'
        pairs are computed and not counted)."""
        if not self.latent:
            with scope("moe"):
                return self._routed(x, x, valid)
        with scope("moe_latent"):
            inner = self.fc1_latent_proj(x)
        with scope("moe"):
            y, counts = self._routed(x, inner, valid)
        with scope("moe_latent"):
            return self.fc2_latent_proj(y), counts

    def _routed(self, x, inner, valid):
        """The routed sum over ``inner`` (``x`` or its latent), the
        router over ``x``."""
        b, s, h = x.shape
        width = inner.shape[-1]
        held, act = self.held_experts, self.experts.act
        leaves = self.router_leaves()
        mats = self.experts.matrices()
        latent = self.latent

        def routed(xa, *rest):
            if latent:
                ia, rest = rest[0], rest[1:]
            router, (*ws, wd), valid = (rest[:len(leaves)],
                                        rest[len(leaves):][:len(mats)],
                                        rest[len(leaves) + len(mats):])
            wg, wu = ws if len(ws) == 2 else (None, *ws)
            tokens = xa.reshape(b * s, h)
            experts, gates = self.route(tokens, *router)
            real = None
            if valid:
                real = (jnp.arange(s)[None, :] < valid[0][:, None]) \
                    .reshape(-1)
            inner_t = ia.reshape(b * s, width) if latent else tokens
            y, counts = _moe.routed_experts(inner_t, experts, gates, wg, wu,
                                            wd, held, real, act=act)
            return y.reshape(b, s, width), counts

        args = (x,) + ((inner,) if latent else ()) + leaves + mats
        name = "routed_experts_biased" if len(leaves) > 1 \
            else "routed_experts"
        if latent or act != "swiglu":
            name += f"_{act}" + ("_latent" if latent else "")
        return apply_op(name, routed,
                        args if valid is None else args + (valid,))

    def forward(self, x, cache=None):
        b, s, h = x.shape
        valid = None if cache is None else cache.get("valid_len")
        y, counts = self.routed(x, valid)
        if valid is not None:
            cache["moe_counts"] = counts
        sh = self.shared_experts
        if sh is None:
            return y
        act = sh.act

        def shared(xa, *ws):
            tokens = xa.reshape(b * s, h)
            acc = None
            for j in range(ws[-1].shape[0]):
                # an expert at a time: slicing the stack's first axis is
                # a view, a product over the stacked axis a transpose
                if act == "swiglu":
                    wg, wu, wd = ws
                    mid = jax.nn.silu(jnp.matmul(tokens, wg[j])) \
                        * jnp.matmul(tokens, wu[j])
                else:
                    wu, wd = ws
                    mid = jnp.square(jax.nn.relu(jnp.matmul(tokens, wu[j])))
                y = jnp.matmul(mid, wd[j]).astype(jnp.float32)
                acc = y if acc is None else acc + y
            if self.shared_scale is not None:
                acc = acc * self.shared_scale
            return acc.astype(xa.dtype).reshape(b, s, h)

        name = "shared_experts" if self.shared_scale is not None \
            else "shared_experts_sum"
        if act != "swiglu":
            name += f"_{act}"
        with scope("moe_shared"):
            return y + apply_op(name, shared, (x,) + sh.matrices())
