"""``sarvam_mla`` decoder (Sarvam-105B, sarvamai 2026-03), one chip's
share of it, as plain jax.numpy in float32.

With ``h`` hidden, ``H`` heads, latent rank ``r`` (``kv_lora_rank``), head
widths ``nope`` / ``rope`` / ``v``, ``E`` published experts of which ``k``
a token and ``S`` shared, expert width ``F``, dense width ``F_d``;
RMSNorm has a learned scale and eps ``rms_norm_eps``:

    x <- x + Attn(RMSNorm(x));  x <- x + FFN(RMSNorm(x))    (sequential)

    Attn: q = RMSNorm_{nope+rope}(W_q n) per head [H, nope + rope];
      [c, k_r] = W_kva n (r + rope);  c <- RMSNorm_r(c);
      [k_nope_h, v_h] = W_kvb,h c  (nope + v a head);
      k_h = [k_nope_h, rope(k_r)],  q_h = [q_nope_h, rope(q_rope_h)];
      o = W_o concat_h softmax(q_h . k_h * scale, causal) v_h.
      rope: deepseek_yarn over the ``rope`` dims, interleaved pairs
      (x_2m, x_2m+1): inv_freq = blend of theta^(-2m/rope) and the same
      over ``factor`` by the linear ramp between the correction dims of
      beta_fast and beta_slow; cos/sin times mscale(factor, mscale) /
      mscale(factor, mscale_all_dim) (1 as published);
      scale = (nope + rope)^-1/2 * (0.1 mscale_all_dim ln factor + 1)^2.
    FFN, layers < first_k_dense_replace: W_down(silu(W_gate n) * W_up n),
      width F_d.
    FFN, the others: s = sigmoid(W_r n) in float32, E wide;
      T = top_k(s + b), the bias b in the SELECTION only;
      g_e = routed_scaling_factor * s_e / sum_{j in T} s_j;
      FFN(n) = sum_{e in T} g_e f_e(n) + sum_j f^shared_j(n), each f a
      SwiGLU of width F.

    logits = W_head RMSNorm_f(x)                             (untied)

Only the equations above: attention is the UP-PROJECTED form; the
absorbed form the program decodes with appears nowhere here.

**The chip's share.**  ``held_experts`` = [first, count]: the layer
routes over all E, normalises over all k chosen, and computes ``sum_{e
in T, e held} g_e f_e(n)`` plus the shared term.  What the absent
experts would add is left out and the partial result goes on to the
next layer — in the program alike.

Departures from the published description, each for the harness's sake:

- ``reference/run.py`` calls ``layer`` without an index, so the stream
  carries its layer counter: ``embed`` returns ``(x, 0)``, ``layer`` maps
  ``(x, i)`` to ``(x', i + 1)``, ``head`` drops it.  A dense layer and an
  expert layer have different leaves: which one ``layer`` computes it
  reads from the leaves it is given.
- attention runs in ``HEAD_GROUPS`` groups of heads one after another,
  each in blocks of ``Q_BLOCK`` queries (the harness pads a request to
  the slot length: 64 x 20480^2 float32 scores are 107 GB whole, and a
  layer's queries, keys and values 3.4 GB beside the served weights); a
  block's scores are dense over all keys.
- the expert part is dense over the HELD experts with the gate as a
  mask (0 where the token did not choose the expert), one expert at a
  time; the dense layer's SwiGLU runs in ``WIDTH_BLOCKS`` blocks of its
  width.  Each is a scan, so that the compiler cannot hold every piece's
  temporaries at once: a layer's program has to fit beside the served
  weights and the head's 2.7 GB of logits.
- the head and the embedding are the vocabulary's slice the chip holds.
- ``cfg["intermediate_size"]`` is the width of ONE expert (the
  configuration's ``fields`` map the published ``moe_intermediate_size``
  to it, so that the benchmark's expert readers read this cell as they
  stand); the dense layer's width is ``cfg["dense_intermediate_size"]``.

**Inferences** (the configuration's ``assumed`` says each): no
``q_lora_rank`` in the config, so ``W_q`` is direct; ``use_qk_norm`` is
read as the latent's RMSNorm before ``W_kvb`` and a per-head RMSNorm over
the ``nope + rope`` query dims before the split; sigmoid scores, no
expert groups, gates normalised over the chosen before the factor.

**Seeded weights.**  Every leaf is ``mean + N(0, std)`` at
``initializer_range``; norm scales are ``1 + N(0, std)``; the selection
bias is ``N(0, std)``, non-zero so that it moves choices.
``reference/cohere_moe.py`` argues the scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import mm_f32

Q_BLOCK = 256
#: groups of heads / blocks of the dense width computed one after another
HEAD_GROUPS = 4
WIDTH_BLOCKS = 4
_HI = jax.lax.Precision.HIGHEST


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def _layer_spec(cfg, i):
    h, nh, r, nope, rope, v = _dims(cfg)
    f, fd = cfg["intermediate_size"], cfg["dense_intermediate_size"]
    std = cfg["initializer_range"]
    held = cfg["held_experts"][1]
    shared = cfg["num_shared_experts"]
    spec = {
        "input_layernorm.weight": ((h,), "ones", std),
        "self_attn.q_proj.weight": ((h, nh * (nope + rope)), "normal", std),
        "self_attn.q_norm.weight": ((nope + rope,), "ones", std),
        "self_attn.kv_a_proj_with_mqa.weight": ((h, r + rope), "normal",
                                                std),
        "self_attn.kv_a_layernorm.weight": ((r,), "ones", std),
        "self_attn.kv_b_proj.weight": ((r, nh * (nope + v)), "normal", std),
        "self_attn.o_proj.weight": ((nh * v, h), "normal", std),
        "post_attention_layernorm.weight": ((h,), "ones", std)}
    if _is_dense(cfg, i):
        spec.update({
            "mlp.gate_proj.weight": ((h, fd), "normal", std),
            "mlp.up_proj.weight": ((h, fd), "normal", std),
            "mlp.down_proj.weight": ((fd, h), "normal", std)})
    else:
        spec.update({
            "mlp.gate.weight":
                ((h, cfg["num_experts_published"]), "normal", std),
            "mlp.gate.expert_bias":
                ((cfg["num_experts_published"],), "normal", std),
            "mlp.experts.gate_proj": ((held, h, f), "normal", std),
            "mlp.experts.up_proj": ((held, h, f), "normal", std),
            "mlp.experts.down_proj": ((held, f, h), "normal", std),
            "mlp.shared_experts.gate_proj": ((shared, h, f), "normal", std),
            "mlp.shared_experts.up_proj": ((shared, h, f), "normal", std),
            "mlp.shared_experts.down_proj": ((shared, f, h), "normal",
                                             std)})
    return spec


def weight_spec(cfg):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    spec = {"model.embed_tokens.weight":
            ((cfg["vocab_size"], h), "normal", std),
            "model.norm.weight": ((h,), "ones", std),
            "lm_head.weight": ((h, cfg["vocab_size"]), "normal", std)}
    for i in range(cfg["num_layers"]):
        spec.update({f"model.layers.{i}.{k}": v
                     for k, v in _layer_spec(cfg, i).items()})
    return spec


def layer_names(cfg, i):
    return [f"model.layers.{i}.{k}" for k in _layer_spec(cfg, i)]


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """float32 [rope / 2]."""
    sc, dim, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    orig = sc["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / sc["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    all_dim = sc.get("mscale_all_dim", 0)
    m = _mscale(sc["factor"], all_dim) if all_dim else 1.0
    return m * m / math.sqrt(d)


def _rope_pairs(x, cfg):
    """Rotate [B, S, ..., D] by position over the pairs (x_2m, x_2m+1)."""
    sc = cfg["rope_scaling"]
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(cfg)[None]                                # [S, D/2]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + ang.shape[-1:])
    mult = _mscale(sc["factor"], sc.get("mscale", 1)) \
        / _mscale(sc["factor"], sc.get("mscale_all_dim", 0))
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, scale):
    """q, k [B,S,H,Dk], v [B,S,H,Dv]; causal; in blocks of ``Q_BLOCK``
    queries, each dense over all keys."""
    b, s, h, dk = q.shape
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    qb = q.reshape(b, s // blk, blk, h, dk).transpose(1, 0, 2, 3, 4)
    kp = jnp.arange(s)[None, :]

    def one(args):
        qi, start = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=_HI) * scale
        qp = start + jnp.arange(blk)[:, None]
        p = jax.nn.softmax(jnp.where(kp <= qp, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=_HI)

    out = jax.lax.map(one, (qb, jnp.arange(0, s, blk)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h * v.shape[-1])


def _latent_row(y, w, cfg, mm, row_round=None):
    """``(c, rope(k_r))`` of every position of the normed input ``y``:
    the two parts of the row a latent cache holds a token."""
    r = cfg["kv_lora_rank"]
    ckr = mm(y, w["self_attn.kv_a_proj_with_mqa.weight"])
    c = _rms_norm(ckr[..., :r], w["self_attn.kv_a_layernorm.weight"],
                  cfg["rms_norm_eps"])
    k_r = _rope_pairs(ckr[..., r:], cfg)
    if row_round is not None:
        c, k_r = row_round(c), row_round(k_r)
    return c, k_r


def _attn(y, w, cfg, mm, row_round=None, kvb_mm=None):
    """The attention layer's output for its normed input ``y`` [B,S,h],
    ``HEAD_GROUPS`` groups of heads one after another (each group's
    queries, up-projected keys and values, scores and its rows of
    ``W_o``; the groups' outputs add up).  ``row_round`` rounds the
    latent row ``[c, rope(k_r)]`` as a cache in a lower precision would
    hold it, ``kvb_mm`` is the up-projection's product where it is not
    ``mm`` (the controls of ``latent_gap``)."""
    b, s, hid = y.shape
    _, nh, r, nope, rope, v = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    groups = HEAD_GROUPS if nh % HEAD_GROUPS == 0 else 1
    hg = nh // groups
    c, k_r = _latent_row(y, w, cfg, mm, row_round)
    scale = softmax_scale(cfg)

    def by_group(weight, rows, width):
        """[rows, H * width] -> [groups, rows, hg * width]."""
        return weight.reshape(rows, groups, hg * width).transpose(1, 0, 2)

    def one(out, ws):
        wq, wkvb, wo = ws
        q = _rms_norm(mm(y, wq).reshape(b, s, hg, nope + rope),
                      w["self_attn.q_norm.weight"], eps)
        q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], cfg)],
                            axis=-1)
        kv = (kvb_mm or mm)(c, wkvb).reshape(b, s, hg, nope + v)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r[:, :, None], (b, s, hg, rope))], axis=-1)
        return out + mm(_attention(q, k, kv[..., nope:], scale), wo), None

    out, _ = jax.lax.scan(
        one, jnp.zeros((b, s, hid), jnp.float32),
        (by_group(w["self_attn.q_proj.weight"], hid, nope + rope),
         by_group(w["self_attn.kv_b_proj.weight"], r, nope + v),
         w["self_attn.o_proj.weight"].reshape(groups, hg * v, hid)))
    return out


def _swiglu(y, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)


def _dense_mlp(y, w, cfg, mm):
    """The dense layer's SwiGLU, ``WIDTH_BLOCKS`` blocks of its width one
    after another (a block's columns of gate and up, its rows of down;
    the blocks' outputs add up)."""
    wg, wu, wd = (w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                  w["mlp.down_proj.weight"])
    h, f = wg.shape
    blocks = WIDTH_BLOCKS if f % WIDTH_BLOCKS == 0 else 1

    def cols(weight):
        return weight.reshape(h, blocks, f // blocks).transpose(1, 0, 2)

    out, _ = jax.lax.scan(
        lambda acc, ws: (acc + _swiglu(y, *ws, mm), None),
        jnp.zeros_like(y), (cols(wg), cols(wu),
                            wd.reshape(blocks, f // blocks, h)))
    return out


def _routed(y, w, cfg, mm, expert_mm=None):
    """The held routed experts' part of ``FFN(y)`` and the experts each
    token chose ([B, S, k]); ``expert_mm`` is the experts' product where
    it is not the router's (the expert-only control)."""
    expert_mm = expert_mm or mm
    k = cfg["num_experts_per_tok"]
    first, held = cfg["held_experts"]
    scores = jax.nn.sigmoid(mm(y, w["mlp.gate.weight"]))        # [B,S,E]
    _, idx = jax.lax.top_k(
        scores + w["mlp.gate.expert_bias"].astype(jnp.float32), k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    gates = cfg["routed_scaling_factor"] * top \
        / jnp.sum(top, -1, keepdims=True)
    # gate of every HELD expert for every token, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(idx - first, held, dtype=jnp.float32)
                    * gates[..., None], axis=-2)                # [B,S,held]

    def one(out, ws):
        gate, wg, wu, wd = ws
        return out + gate[..., None] * _swiglu(y, wg, wu, wd, expert_mm), \
            None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (jnp.moveaxis(dense, -1, 0), w["mlp.experts.gate_proj"][:held],
         w["mlp.experts.up_proj"][:held],
         w["mlp.experts.down_proj"][:held]))
    return out, idx


def _ffn(y, w, cfg, mm, expert_mm=None):
    if "mlp.gate.weight" not in w:
        return _dense_mlp(y, w, cfg, mm)
    out, _ = _routed(y, w, cfg, mm, expert_mm)
    for j in range(cfg["num_shared_experts"]):
        out = out + _swiglu(y, w["mlp.shared_experts.gate_proj"][j],
                            w["mlp.shared_experts.up_proj"][j],
                            w["mlp.shared_experts.down_proj"][j], mm)
    return out


def embed(params, ids, cfg):
    x = params["model.embed_tokens.weight"].astype(jnp.float32)[ids]
    return x, jnp.int32(0)


def attention_part(stream, w, cfg, mm=mm_f32, row_round=None, kvb_mm=None):
    """What ``drive_serve_mla`` holds latent attention by: the normed
    input of the attention layer ``stream`` is about to enter, and the
    layer's output (after ``W_o``, before the residual) for it."""
    y = _rms_norm(stream[0], w["input_layernorm.weight"],
                  cfg["rms_norm_eps"])
    return y, _attn(y, w, cfg, mm, row_round, kvb_mm)


def latent_rows(stream, w, cfg, mm=mm_f32, row_round=None):
    """What ``drive_serve_mla`` holds the SERVED run by: the rows
    ``[c, rope(k_r)]`` [B, S, r + rope] that the layer ``stream`` is
    about to enter caches, a position each."""
    y = _rms_norm(stream[0], w["input_layernorm.weight"],
                  cfg["rms_norm_eps"])
    return jnp.concatenate(_latent_row(y, w, cfg, mm, row_round), axis=-1)


def attend(stream, w, cfg, mm=mm_f32, row_round=None, kvb_mm=None):
    """``x + Attn(RMSNorm(x))``: the stream between a block's halves."""
    return stream[0] + attention_part(stream, w, cfg, mm, row_round,
                                      kvb_mm)[1], stream[1]


def routed_part(mid, w, cfg, mm=mm_f32, expert_mm=None):
    """What the routed experts are held by, for ``mid`` the stream
    BETWEEN an expert layer's halves (``attend``'s): the FFN's normed
    input, the held routed experts' part of the FFN for it, and the
    chosen experts."""
    y = _rms_norm(mid[0], w["post_attention_layernorm.weight"],
                  cfg["rms_norm_eps"])
    out, idx = _routed(y, w, cfg, mm, expert_mm)
    return y, out, idx


def feed_forward(mid, w, cfg, mm=mm_f32, expert_mm=None):
    """``x + FFN(RMSNorm(x))`` for the stream between a block's halves;
    ``expert_mm`` is the routed experts' product where it is not ``mm``
    (the expert-only control)."""
    x, i = mid
    y = _rms_norm(x, w["post_attention_layernorm.weight"],
                  cfg["rms_norm_eps"])
    return x + _ffn(y, w, cfg, mm, expert_mm), i + 1


def layer(stream, w, cfg, mm=mm_f32):
    return feed_forward(attend(stream, w, cfg, mm), w, cfg, mm)


def head(params, stream, cfg, mm=mm_f32):
    x, _ = stream
    y = _rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])
    return mm(y, params["lm_head.weight"])


HEAD_NAMES = ("model.norm.weight", "lm_head.weight")
EMBED_NAMES = ("model.embed_tokens.weight",)
