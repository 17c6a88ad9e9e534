"""``nemotron_h`` decoder (Nemotron-3-Super, NVIDIA 2026-03), one chip's
share of it, as plain jax.numpy in float32.

With ``h`` hidden; Mamba-2 ``H`` heads of ``P``, ``G`` groups of B/C,
state ``N``, ``K`` taps; attention ``Hq`` query heads and ``Hkv`` KV
heads of ``D``; ``E`` published experts of which ``k`` a token, ``L`` the
latent width, ``F`` a routed expert's width and ``F_s`` the shared
expert's; RMSNorm has a learned scale and eps ``rms_norm_eps``:

    x <- x + Mixer_l(RMSNorm_l(x))       (one norm, one residual a block)
    logits = W_head RMSNorm_f(x)          (untied; the embedding unscaled)

    M (Mamba-2): [z, xBC, dt] = W_in n  (H P / H P + 2 G N / H wide);
      xBC <- silu(conv1d_K(xBC) + b), causal, depthwise;
      x [H, P], B, C [G, N]; head i reads group floor(i / (H / G));
      dt <- softplus(dt + dt_bias) (not clamped); A = -exp(A_log);
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t;
      u = y * silu(z), then RMSNorm over EACH of the G groups of H P / G
      channels on its own, times the norm's scale;  out = W_out u.
    * (attention): q = W_q n [Hq, D], k, v = W_k n, W_v n [Hkv, D]; no
      rotation, no bias; softmax(q k^T / sqrt(D), causal) v; W_o.
    E (LatentMoE): s = sigmoid(W_r n) in float32, E wide;
      T = top_k(s + b), the bias b in the SELECTION only;
      g_e = routed_scaling_factor * s_e / sum_{j in T} s_j;
      l = W_lat_down n (L wide);
      out = W_lat_up sum_{e in T, e held} g_e W2_e relu(W1_e l)^2
            + W2_s relu(W1_s n)^2   (W1_e: L -> F, W2_e: F -> L; the
            shared expert h -> F_s -> h).

Only the equations above.  The Mamba mixer is the recurrence as
written, one token at a time in a ``lax.scan`` — not the chunked form
the program prefills with.

**The chip's share.**  ``held_experts`` = [first, count]: the layer
routes over all E, normalises over all k chosen, and computes the held
experts' part (through ``W_lat_up``) plus the shared expert.  What the
absent experts would add is left out and the partial result goes on to
the next layer — in the program alike.  The head and the embedding are
the vocabulary's slice the chip holds.

Departures from the published description, each for the harness's sake:

- ``reference/run.py`` calls ``layer`` without an index: a layer's kind
  is told by its leaves (``mixer.in_proj.weight``, ``mixer.q_proj.weight``
  or ``mixer.gate.weight``).
- attention runs in blocks of ``Q_BLOCK`` queries, each dense over all
  keys (the harness pads a request to the slot length: 32 x 6144^2
  float32 scores are 4.8 GB whole).
- the routed part is dense over the HELD experts with the gate as a mask
  (0 where the token did not choose the expert), one expert at a time in
  a scan, so that only one expert's leaves are ever upcast to float32:
  the bfloat16 weights are 9.3 GB and a float32 copy would not fit.  A
  layer's leaves are upcast inside its own program, which is exact: the
  seeded values are bfloat16.
- ``cfg["intermediate_size"]`` is the width of ONE routed expert (the
  configuration's ``fields`` map the published ``moe_intermediate_size``
  to it); the shared expert's is ``cfg["shared_intermediate_size"]``.

**Inferences** (the configuration's ``assumed`` says each): attention
carries no rotation (the family's modeling code applies none); the
router reads the full-width normed input, not the latent; the latent
projections have no bias and no norm; the gate multiplies by ``silu(z)``
BEFORE the grouped norm; ``time_step_limit`` is (0, inf); no
group-limited routing (``n_group`` / ``topk_group`` 1).

**Seeded weights.**  Every leaf is ``mean + N(0, std)`` at
``initializer_range``; norm scales are ``1 + N(0, std)``; the selection
bias is ``N(0, std)``, non-zero so that it moves choices; ``A_log``,
``dt_bias`` and the convolution are drawn as ``reference/granite_hybrid.py``
argues (so that some heads remember for hundreds of tokens).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import mm_f32

Q_BLOCK = 256
A_LOG_STD = 3.0
DT_BIAS_STD = 1.0
CONV_STD = 0.5          # 4 taps: unit gain through the convolution
_HI = jax.lax.Precision.HIGHEST


def _sizes(cfg):
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    d_inner = heads * p
    return heads, p, g, n, d_inner, d_inner + 2 * g * n


def _layer_spec(cfg, kind):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    spec = {"norm.weight": ((h,), "ones", std)}
    if kind == "mamba":
        heads, p, g, n, d_inner, conv_dim = _sizes(cfg)
        spec.update({
            "mixer.in_proj.weight":
                ((h, d_inner + conv_dim + heads), "normal", std),
            "mixer.conv1d.weight":
                ((conv_dim, cfg["mamba_d_conv"]), "normal", CONV_STD),
            "mixer.conv1d.bias": ((conv_dim,), "zeros", std),
            "mixer.dt_bias": ((heads,), "zeros", DT_BIAS_STD),
            "mixer.A_log": ((heads,), "normal", A_LOG_STD),
            "mixer.D": ((heads,), "ones", std),
            "mixer.norm.weight": ((d_inner,), "ones", std),
            "mixer.out_proj.weight": ((d_inner, h), "normal", std)})
    elif kind == "attention":
        qd = cfg["num_heads"] * cfg["head_dim"]
        kvd = cfg["num_kv_heads"] * cfg["head_dim"]
        spec.update({
            "mixer.q_proj.weight": ((h, qd), "normal", std),
            "mixer.k_proj.weight": ((h, kvd), "normal", std),
            "mixer.v_proj.weight": ((h, kvd), "normal", std),
            "mixer.o_proj.weight": ((qd, h), "normal", std)})
    elif kind == "moe":
        e, held = cfg["num_experts_published"], cfg["held_experts"][1]
        lat, f = cfg["moe_latent_size"], cfg["intermediate_size"]
        fs, ns = cfg["shared_intermediate_size"], cfg["num_shared_experts"]
        spec.update({
            "mixer.gate.weight": ((h, e), "normal", std),
            "mixer.gate.expert_bias": ((e,), "normal", std),
            "mixer.experts.up_proj": ((held, lat, f), "normal", std),
            "mixer.experts.down_proj": ((held, f, lat), "normal", std),
            "mixer.shared_experts.up_proj": ((ns, h, fs), "normal", std),
            "mixer.shared_experts.down_proj": ((ns, fs, h), "normal", std),
            "mixer.fc1_latent_proj.weight": ((h, lat), "normal", std),
            "mixer.fc2_latent_proj.weight": ((lat, h), "normal", std)})
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return spec


def weight_spec(cfg):
    h, std, v = cfg["hidden_size"], cfg["initializer_range"], \
        cfg["vocab_size"]
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{cfg['num_layers']} layers")
    spec = {"backbone.embeddings.weight": ((v, h), "normal", std),
            "backbone.norm_f.weight": ((h,), "ones", std),
            "lm_head.weight": ((h, v), "normal", std)}
    for i, kind in enumerate(kinds):
        spec.update({f"backbone.layers.{i}.{k}": s
                     for k, s in _layer_spec(cfg, kind).items()})
    return spec


def layer_names(cfg, i):
    return [f"backbone.layers.{i}.{k}"
            for k in _layer_spec(cfg, cfg["layer_types"][i])]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def embed(params, ids, cfg):
    return params["backbone.embeddings.weight"].astype(jnp.float32)[ids]


def _mamba(y, w, cfg, mm, n_valid=None, keep=None):
    """The mixer's output and the state after the last token — or, with
    ``n_valid``, after that many tokens (the positions past them carry
    ``dt = 0``).  ``keep`` rounds the state after every token: the
    lower-precision control of the state's type."""
    b, s, _ = y.shape
    heads, p, g, n, d_inner, conv_dim = _sizes(cfg)
    taps = cfg["mamba_d_conv"]
    f32 = jnp.float32
    proj = mm(y, w["mixer.in_proj.weight"])
    z, xbc, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
    # causal depthwise convolution: tap k weighs the token taps-1-k back
    wc = w["mixer.conv1d.weight"].astype(f32)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + s] * wc[:, k] for k in range(taps))
    xbc = jax.nn.silu(conv + w["mixer.conv1d.bias"].astype(f32))
    x, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    x = x.reshape(b, s, heads, p)
    # head i reads group i // (heads / g)
    group = np.arange(heads) // (heads // g)
    bm = bm.reshape(b, s, g, n)[:, :, group]
    cm = cm.reshape(b, s, g, n)[:, :, group]
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"].astype(f32))
    if n_valid is not None:
        dt = jnp.where((jnp.arange(s) < n_valid)[None, :, None], dt, 0.0)
    a = -jnp.exp(w["mixer.A_log"].astype(f32))

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp          # [b,H,P] [b,H,N] [b,H,N] [b,H]
        state = state * jnp.exp(dt_t * a)[:, :, None, None] + \
            (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        if keep is not None:
            state = keep(state)
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    last, ys = jax.lax.scan(token, jnp.zeros((b, heads, p, n), f32),
                            (first(x), first(bm), first(cm), first(dt)))
    out = jnp.moveaxis(ys, 0, 1) + \
        w["mixer.D"].astype(f32)[None, None, :, None] * x
    # the gate goes in before the norm, which is over each group apart
    u = (out.reshape(b, s, d_inner) * jax.nn.silu(z)) \
        .reshape(b, s, g, d_inner // g)
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    u = u.reshape(b, s, d_inner) * w["mixer.norm.weight"].astype(f32)
    return mm(u, w["mixer.out_proj.weight"]), last


def _attention(y, w, cfg, mm, kv_round=None):
    """Grouped-query causal attention, no rotation, in blocks of
    ``Q_BLOCK`` queries each dense over all keys; ``kv_round`` rounds
    the keys and values as a page store of a lower precision would hold
    them (the control of ``attention_gap``)."""
    b, s, _ = y.shape
    nh, nkv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = mm(y, w["mixer.q_proj.weight"]).reshape(b, s, nh, d)
    k = mm(y, w["mixer.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(y, w["mixer.v_proj.weight"]).reshape(b, s, nkv, d)
    if kv_round is not None:
        k, v = kv_round(k), kv_round(v)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    qb = q.reshape(b, s // blk, blk, nh, d).transpose(1, 0, 2, 3, 4)
    kp = jnp.arange(s)[None, :]

    def one(args):
        qi, start = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=_HI) \
            / np.sqrt(d)
        qp = start + jnp.arange(blk)[:, None]
        pr = jax.nn.softmax(jnp.where(kp <= qp, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=_HI)

    out = jax.lax.map(one, (qb, jnp.arange(0, s, blk)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, nh * d)
    return mm(out, w["mixer.o_proj.weight"])


def _relu2(y, w1, w2, mm):
    return mm(jnp.square(jax.nn.relu(mm(y, w1))), w2)


def _routed(y, w, cfg, mm, expert_mm=None):
    """The held routed experts' part of the layer for its normed input
    ``y`` (through both latent projections) and the experts each token
    chose ([B, S, k]); ``expert_mm`` is the experts' own products where
    they are not ``mm`` (the expert-only control)."""
    expert_mm = expert_mm or mm
    k = cfg["num_experts_per_tok"]
    first, held = cfg["held_experts"]
    scores = jax.nn.sigmoid(mm(y, w["mixer.gate.weight"]))       # [B,S,E]
    _, idx = jax.lax.top_k(
        scores + w["mixer.gate.expert_bias"].astype(jnp.float32), k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    gates = cfg["routed_scaling_factor"] * top \
        / jnp.sum(top, -1, keepdims=True)
    # gate of every HELD expert for every token, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(idx - first, held, dtype=jnp.float32)
                    * gates[..., None], axis=-2)                # [B,S,held]
    lat = mm(y, w["mixer.fc1_latent_proj.weight"])

    def one(out, ws):
        gate, w1, w2 = ws
        return out + gate[..., None] * _relu2(lat, w1, w2, expert_mm), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(lat),
        (jnp.moveaxis(dense, -1, 0), w["mixer.experts.up_proj"][:held],
         w["mixer.experts.down_proj"][:held]))
    return mm(out, w["mixer.fc2_latent_proj.weight"]), idx


def _moe(y, w, cfg, mm, expert_mm=None):
    out, _ = _routed(y, w, cfg, mm, expert_mm)
    for j in range(cfg["num_shared_experts"]):
        out = out + _relu2(y, w["mixer.shared_experts.up_proj"][j],
                           w["mixer.shared_experts.down_proj"][j], mm)
    return out


def layer_and_state(x, w, cfg, mm=mm_f32, n_valid=None, keep=None):
    """(the layer's output, the recurrent state [B, H, P, N] a Mamba-2
    layer holds after ``n_valid`` tokens — None for the other kinds)."""
    y = _rms(x, w["norm.weight"], cfg["rms_norm_eps"])
    if "mixer.in_proj.weight" in w:
        mixed, state = _mamba(y, w, cfg, mm, n_valid, keep)
    elif "mixer.q_proj.weight" in w:
        mixed, state = _attention(y, w, cfg, mm), None
    else:
        mixed, state = _moe(y, w, cfg, mm), None
    return x + mixed, state


def layer(x, w, cfg, mm=mm_f32):
    return layer_and_state(x, w, cfg, mm)[0]


def routed_part(x, w, cfg, mm=mm_f32, expert_mm=None):
    """What the routed experts are held by, for ``x`` the stream an
    expert layer is about to enter: the layer's normed input, the held
    routed experts' part of the layer for it (through ``W_lat_up``), and
    the chosen experts."""
    y = _rms(x, w["norm.weight"], cfg["rms_norm_eps"])
    out, idx = _routed(y, w, cfg, mm, expert_mm)
    return y, out, idx


def attention_part(x, w, cfg, mm=mm_f32, kv_round=None):
    """For ``x`` the stream an attention layer is about to enter: the
    layer's normed input and its attention output."""
    y = _rms(x, w["norm.weight"], cfg["rms_norm_eps"])
    return y, _attention(y, w, cfg, mm, kv_round)


def head(params, x, cfg, mm=mm_f32):
    y = _rms(x, params["backbone.norm_f.weight"], cfg["rms_norm_eps"])
    return mm(y, params["lm_head.weight"])


HEAD_NAMES = ("backbone.norm_f.weight", "lm_head.weight")
EMBED_NAMES = ("backbone.embeddings.weight",)
