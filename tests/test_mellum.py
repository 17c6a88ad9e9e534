"""Mellum 2 (sequential blocks of window or full attention, each rotated
by its own rule, then softmax-routed experts) against the plain
reference of ``chipbench/reference/`` on seeded weights, at a tiny size
on the CPU: the whole-sequence forward, chunked prefill and decode
through the serving engine's eager and compiled lanes, the softmax
router, both rotations and the window by hand, the expert layer's
shares adding up, and the typed refusals."""
import math
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import mellum, rotary
from paddle_tpu.models.mellum import (
    TINY_MELLUM, MellumAttention, MellumConfig, MellumForCausalLM,
    MellumSparseMLP)
from paddle_tpu.pallas import moe
from paddle_tpu.serving import (Engine, ServingConfig, WindowLayerError,
                                serving_stats)
from paddle_tpu.utils import flags as _flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
from reference import common as refc            # noqa: E402
from reference import mellum as ref             # noqa: E402
from reference import run as refrun             # noqa: E402

# hidden 64, two periods of [window, window, window, full], 4 query / 2 KV
# heads of 16, window 8, 16 experts of 32, 4 a token, all held
TINY = dict(TINY_MELLUM)
VOCAB = TINY["vocab_size"]
CHUNK = 8
PAGE = 4
MAX_LEN = 64


def _reference_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_layers", "layer_types",
            "num_heads", "num_kv_heads", "head_dim", "sliding_window",
            "rope_parameters", "rms_norm_eps", "intermediate_size",
            "num_experts_published", "num_experts_per_tok",
            "initializer_range")
    out = {k: getattr(cfg, k) for k in keys}
    out["held_experts"] = list(cfg.held_experts)
    return out


def _install(model, weights):
    named = dict(model.named_parameters())
    assert set(named) == set(weights)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p._data_ = weights[name]


@pytest.fixture(scope="module")
def tiny():
    """(model, reference config, weights, reference runner)."""
    cfg = MellumConfig(**TINY)
    model = MellumForCausalLM(cfg)
    model.eval()
    rcfg = _reference_cfg(cfg)
    weights = refc.make_weights(ref.weight_spec(rcfg), 2**31 + 41,
                                jnp.float32)
    _install(model, weights)
    return model, rcfg, weights, refrun.ServeReference("mellum", rcfg)


@pytest.fixture
def tick_flag():
    saved = _flags._FLAGS["FLAGS_compiled_tick"]
    yield _flags._FLAGS
    _flags._FLAGS["FLAGS_compiled_tick"] = saved


def _cfg(**kw):
    base = dict(num_slots=4, max_seq_len=MAX_LEN, page_size=PAGE,
                prefill_chunk_tokens=CHUNK, enable_prefix_cache=False)
    base.update(kw)
    return ServingConfig(**base)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype("int32") for n in lens]


def _reference_logits(runner, weights, ids):
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(runner.logits(weights, padded))[:len(ids)]


def test_leaves_follow_the_checkpoint(tiny):
    names = set(dict(tiny[0].named_parameters()))
    assert {"model.embed_tokens.weight", "model.norm.weight",
            "lm_head.weight", "model.layers.3.self_attn.q_proj.weight",
            "model.layers.3.post_attention_layernorm.weight",
            "model.layers.3.mlp.gate.weight",
            "model.layers.3.mlp.experts.down_proj"} <= names
    # no shared expert, no dense MLP
    assert not any("shared" in n or "mlp.up_proj" in n for n in names)


# the whole sequence ---------------------------------------------------------
def test_whole_sequence_logits_match_reference(tiny):
    model, _, weights, runner = tiny
    ids = _prompts([37, 37], seed=1)        # 37 tokens: 4.6 windows
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(np.stack(ids)))._data_)
    for row, seq in zip(got, ids):
        np.testing.assert_allclose(
            row, _reference_logits(runner, weights, seq), atol=5e-5)


def test_generate_refuses_dense_caches_by_name(tiny):
    model = tiny[0]
    ids = paddle.to_tensor(np.stack(_prompts([5], seed=2)))
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        model.generate(ids, max_new_tokens=3)
    with paddle.no_grad():                  # the cache-free lane works
        out = model.generate(ids, max_new_tokens=3, use_cache=False)
    assert tuple(out.shape) == (1, 8)


# through the engine ---------------------------------------------------------
def _serve(model, waves, compiled, cfg=None, max_new=20):
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    outs = []
    with Engine(model, cfg or _cfg()) as eng:
        for wave in waves:
            with eng._work:
                futs = [eng.submit(p, max_new_tokens=max_new) for p in wave]
            outs.append([f.result(timeout=600).output_ids for f in futs])
        stats = serving_stats()
    return outs, stats


@pytest.mark.parametrize("compiled", [False, True],
                         ids=["eager", "compiled"])
def test_engine_logits_match_the_full_forward(tiny, tick_flag, compiled):
    """Through ``Engine``: a 21-token prompt prefilled in chunks of 8, 8
    and a ragged 5 — across the window of 8, chunk and page boundaries —
    then 30 decoded tokens, against the reference's one full forward.
    The eager lane's every call's logits (a forward hook shows them); the
    compiled lane's prefill members' logits at each chunk's last
    position (what they hand the host), and its ticks' greedy tokens,
    each the reference's first choice by a margin wider than the two
    lanes' float32 roundings."""
    model, _, weights, runner = tiny
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    prompt = _prompts([21], seed=3)[0]
    seen, picked = [], []
    if compiled:
        with Engine(model, _cfg()) as eng:
            run = eng._tick.run_prefill

            def keep(member, slots, starts, tokens, last, lora_rows):
                out = run(member, slots, starts, tokens, last, lora_rows)
                picked.append((int(starts[0] + last[0]),
                               np.asarray(out._data_)[0]))
                return out

            eng._tick.run_prefill = keep
            out = eng.generate(prompt, max_new_tokens=30)
            stats = eng.stats()
        assert stats["tick_compiled_hits"] > 0
        assert stats["tick_fallbacks"] == stats["prefill_fallbacks"] == 0
    else:
        hook = model.register_forward_post_hook(
            lambda layer, inputs, out: seen.append(np.asarray(out._data_)))
        try:
            with Engine(model, _cfg()) as eng:
                out = eng.generate(prompt, max_new_tokens=30)
        finally:
            hook.remove()
    ids = np.concatenate([prompt, out.output_ids])
    want = _reference_logits(runner, weights, ids)
    if compiled:
        assert [p for p, _ in picked] == [7, 15, 20]
        for p, row in picked:
            np.testing.assert_allclose(row, want[p], atol=5e-5)
    else:
        chunks = [c for c in seen if c.shape[1] == CHUNK]
        steps = [c for c in seen if c.shape[1] == 1]
        assert len(chunks) == 3 and len(steps) == 29
        pos = 0
        for c, n in zip(chunks, (8, 8, 5)):
            np.testing.assert_allclose(c[0, :n], want[pos:pos + n],
                                       atol=5e-5)
            pos += n
        for i, c in enumerate(steps):
            np.testing.assert_allclose(c[0, 0], want[21 + i], atol=5e-5)
    served = want[20:20 + 30]
    np.testing.assert_array_equal(out.output_ids, served.argmax(-1))
    top2 = np.sort(served, -1)[:, -2:]
    assert (top2[:, 1] - top2[:, 0]).min() > 1e-4


def test_compiled_lanes_match_eager_lane(tiny, tick_flag):
    model = tiny[0]
    waves = [_prompts([37, 21, 9], seed=4), _prompts([30, 5], seed=5)]
    eager, st_e = _serve(model, waves, False)
    comp, st_c = _serve(model, waves, True)
    for a, b in zip(eager, comp):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert st_e["tick_compiled_hits"] == 0
    assert st_c["tick_compiled_hits"] > 0 and st_c["tick_fallbacks"] == 0
    assert st_c["prefill_compiled_hits"] > 0
    assert st_c["prefill_fallbacks"] == 0
    # all 16 experts held, 4 a token: four pairs a token
    assert st_c["expert_pairs_per_token"] == pytest.approx(4.0)
    assert st_c["expert_router_scoring"] == "softmax"
    # three window layers in four: each slot's ring, not its whole table
    assert 0 < st_c["window_pages_held_share"] < 1
    assert st_c["window_pages_reclaimed"] > 0


def test_greedy_served_tokens_are_the_references_first_choice(tiny,
                                                              tick_flag):
    model, _, weights, runner = tiny
    prompts = _prompts([37, 12], seed=6)
    outs, _ = _serve(model, [prompts], True, max_new=24)
    for prompt, out in zip(prompts, outs[0]):
        ids = np.concatenate([prompt, out])
        want = _reference_logits(runner, weights, ids)
        n = len(prompt)
        np.testing.assert_array_equal(out, want[n - 1:-1].argmax(-1))


# routing, rotation and window by hand ---------------------------------------
def test_route_softmax_topk_is_softmax_topk_renormalised():
    logits = jax.random.normal(jax.random.PRNGKey(0), (50, 64)) * 2.0
    experts, gates = moe.route_softmax_topk(logits, 8)
    p = np.exp(np.asarray(logits, np.float64))
    p /= p.sum(-1, keepdims=True)
    want_idx = np.argsort(-p, -1)[:, :8]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(want_idx, -1))
    top = np.take_along_axis(p, np.asarray(experts), -1)
    np.testing.assert_allclose(np.asarray(gates),
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    # softmax's renormalised gates are not sigmoid's: the scoring matters
    _, sig = moe.route_sigmoid_topk(logits, 8)
    assert np.abs(np.asarray(sig) - np.asarray(gates)).max() > 1e-2


def _rotate_by_hand(x, pos, inv, mult):
    """Rotate-half by a plain formula, float64: pair m is (x_m,
    x_{m+D/2}), turned by ``pos * inv[..., m]``."""
    d = x.shape[-1]
    out = np.empty_like(x)
    for m in range(d // 2):
        a = pos * inv[..., m:m + 1]
        c, s = np.cos(a) * mult, np.sin(a) * mult
        out[..., m] = x[..., m] * c - x[..., m + d // 2] * s
        out[..., m + d // 2] = x[..., m + d // 2] * c + x[..., m] * s
    return out


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rotation_by_hand(kind):
    """Both kinds at the published settings and head size 128: the
    window layer at theta^(-2m/D), the full layer by YaRN — plain below
    pair 18, divided by 16 from pair 35, a linear ramp between — and cos
    and sin times attention_factor."""
    cfg = MellumConfig(num_layers=4, hidden_size=256, num_heads=2,
                       num_kv_heads=1, vocab_size=64,
                       num_experts_published=8, num_experts_per_tok=2,
                       intermediate_size=32)
    att = MellumAttention(cfg, kind)
    d = 128
    plain = 500000.0 ** (-np.arange(0, d, 2) / d)
    if kind == "sliding_attention":
        inv, mult = plain, 1.0
    else:
        # correction dims: 128 ln(8192 / (2 pi 32)) / (2 ln 5e5) = 18.08,
        # 128 ln(8192 / (2 pi)) / (2 ln 5e5) = 34.99
        lo, hi = 18, 35
        ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
        inv = plain * (1 - ramp) + plain / 16 * ramp
        mult = 1.2772588722239782
        assert mult == pytest.approx(0.1 * math.log(16) + 1)
        np.testing.assert_allclose(inv[:18], plain[:18], rtol=1e-6)
        np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    rope = cfg.rope_parameters[kind]
    inv32, mult32 = mellum.rotation(rope, d)
    np.testing.assert_allclose(np.asarray(inv32), inv, rtol=1e-6)
    assert mult32 == mult
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, d)))
    pos = np.array([0, 1, 2, 7, 100, 1023, 1024, 8191, 13823])
    got = np.asarray(att.rotate(jnp.asarray(x), jnp.asarray(pos)))
    # the angle is a float32 product, as the program and transformers
    # take it (at 13,823 its rounding alone is ~1e-3 rad): the formula is
    # checked over the same angles, the frequencies above
    angles = pos[:, None].astype(np.float32) * np.asarray(inv32)
    want = _rotate_by_hand(x[0].astype(np.float64), 1.0,
                           angles.astype(np.float64), mult)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # the reference computes the same on its own
    np.testing.assert_allclose(
        np.asarray(ref._rotate_halves(jnp.asarray(x[:, :4]), rope)),
        _rotate_by_hand(x[0, :4].astype(np.float64),
                        np.arange(4)[:, None], inv, mult)[None],
        atol=2e-5)
    # the shared helper's YaRN is the same function of the scaling that
    # the latent family's interleaved pairs read
    if kind == "full_attention":
        np.testing.assert_allclose(
            np.asarray(rotary.yarn_inv_freq(d, 500000.0, rope)), inv,
            rtol=1e-6)


def test_a_window_layer_sees_s_minus_1023_through_s():
    """At the published window of 1024: query s's output moves when key
    s - 1023 moves, and not when key s - 1024 does; a full layer's moves
    for both."""
    kw = dict(num_layers=4, hidden_size=32, num_heads=2, num_kv_heads=1,
              head_dim=16, vocab_size=64, num_experts_published=8,
              num_experts_per_tok=2, intermediate_size=32,
              initializer_range=0.05)
    cfg = MellumConfig(**kw)
    s = 1100
    x = jax.random.normal(jax.random.PRNGKey(2), (1, s, 32), jnp.float32)
    for kind in ("sliding_attention", "full_attention"):
        paddle.seed(3)
        att = MellumAttention(cfg, kind)
        with paddle.no_grad():
            base = np.asarray(att(paddle.to_tensor(x))._data_)[0, s - 1]
            moved = {}
            for back in (1023, 1024):
                y = x.at[0, s - 1 - back].add(10.0)
                moved[back] = np.abs(np.asarray(
                    att(paddle.to_tensor(y))._data_)[0, s - 1] - base).max()
        # diffuse scores (std 0.05): one key of ~1,000 moves the output
        # by ~1e-3, far above float32's rounding
        assert moved[1023] > 1e-5, (kind, moved)
        if kind == "sliding_attention":
            assert moved[1024] == 0.0, moved
        else:
            assert moved[1024] > 1e-5, moved


# the shares of the expert layer ---------------------------------------------
def _mlp_share(weights, layer, held):
    """The program's expert layer holding ``held`` of 64 experts, with
    layer ``layer``'s seeded weights cut to the run."""
    cfg = MellumConfig(**dict(TINY, num_experts_published=64,
                              num_experts_per_tok=8, held_experts=held))
    mlp = MellumSparseMLP(cfg)
    p = f"model.layers.{layer}.mlp."
    lo, hi = held[0], held[0] + held[1]
    mlp.gate.weight._data_ = weights[p + "gate.weight"]
    for name in ("gate_proj", "up_proj", "down_proj"):
        getattr(mlp.experts, name)._data_ = \
            weights[p + "experts." + name][lo:hi]
    return mlp


def test_two_halves_of_the_experts_add_up_to_all_64():
    """Experts 0-31 and 32-63, each routed over all 64, add up to the
    reference's layer with all 64 held; neither half alone is it."""
    full = dict(TINY, num_experts_published=64, num_experts_per_tok=8,
                held_experts=(0, 64))
    rcfg = _reference_cfg(MellumConfig(**full))
    weights = refc.make_weights(ref.weight_spec(rcfg), 77, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64), jnp.float32)
    w = refrun._layer_weights(ref, rcfg, weights, 1)
    want = np.asarray(ref._routed(x, w, rcfg, refc.mm_f32)[0])
    total = np.zeros_like(want)
    cache = {"valid_len": paddle.to_tensor(np.full(2, 19, np.int32))}
    pairs = 0
    with paddle.no_grad():
        for first in (0, 32):
            part = np.asarray(_mlp_share(weights, 1, (first, 32))(
                paddle.to_tensor(x), cache=cache)._data_)
            assert np.abs(part - want).max() > 1e-3
            total += part
            pairs += int(np.asarray(cache["moe_counts"]._data_).sum())
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert pairs == 2 * 19 * 8


def test_a_softmax_router_takes_no_bias_or_scale():
    with pytest.raises(ValueError, match="softmax"):
        from paddle_tpu.models.sparse_experts import SparseExpertMLP
        SparseExpertMLP(16, 8, 4, (0, 4), 2, 0, 0.1, gate_scale=2.0,
                        scoring="softmax")


# refusals -------------------------------------------------------------------
def test_typed_refusals_name_the_layer_kind(tiny):
    model = tiny[0]
    with pytest.raises(WindowLayerError, match="enable_prefix_cache"):
        Engine(model, ServingConfig(enable_prefix_cache=True))
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    draft = LlamaForCausalLM(llama_config("tiny", vocab_size=VOCAB,
                                          max_seq_len=MAX_LEN))
    with pytest.raises(WindowLayerError, match="speculation_k"):
        Engine(model, _cfg(draft_model=draft, speculation_k=2))
    with pytest.raises(WindowLayerError, match="draft_model"):
        Engine(model, _cfg(draft_model=model, speculation_k=2))
