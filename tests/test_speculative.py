"""Speculative decoding + quantized KV (ISSUE 11): draft/verify/rollback
on the paged engine, accept-mask page accounting, eos mid-window,
speculative_generate parity, and int8 KV round-trip/capacity."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import Engine, PagedKVCache, ServingConfig


def _np(t):
    return np.asarray(t._data_)


def _make_model(seed=0, num_layers=2, hidden=64, heads=2, vocab=128,
                max_seq=64):
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    paddle.seed(seed)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=num_layers, hidden_size=hidden,
        num_heads=heads, vocab_size=vocab, max_seq_len=max_seq))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _make_model()


@pytest.fixture(scope="module")
def agreeing_draft(model):
    """1-block draft computing the target's exact function: the target's
    block 1 gets zeroed output projections (residual identity) and the
    draft shares embeddings + block 0 + final norm — the bench's
    perfect-agreement construction in miniature."""
    import jax.numpy as jnp
    block = list(model.gpt.h)[1]
    for lin in (block.attn.out_proj, block.mlp.fc_out):
        lin.weight._data_ = jnp.zeros_like(lin.weight._data_)
        if lin.bias is not None:
            lin.bias._data_ = jnp.zeros_like(lin.bias._data_)
    draft = _make_model(seed=1, num_layers=1)
    tgt = dict(model.named_parameters())
    for name, p in draft.named_parameters():
        p._data_ = tgt[name]._data_
    return draft


class _Negator:
    """Adversarial draft: the target's logits negated, so its greedy
    proposal is the target's argmin — every window is all-reject."""

    def __init__(self, inner):
        self.inner = inner
        self.config = inner.config

    def eval(self):
        return self

    def __call__(self, ids, caches=None):
        return self.inner(ids, caches=caches) * -1.0


def _ref_greedy(model, prompt, max_new, eos_token_id=None):
    ids = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=max_new, temperature=0.0,
                         eos_token_id=eos_token_id)
    return _np(ids)[0, prompt.size:]


def _prompts(lens, seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


# ------------------------------------------------------------------
# engine: speculation on/off equivalence
# ------------------------------------------------------------------

def test_k0_with_draft_is_plain_decode(model, agreeing_draft):
    """speculation_k=0 degenerates to the plain decode loop bitwise —
    the draft model is ignored and no spec counters move."""
    (p,) = _prompts([9], seed=3)
    ref = _ref_greedy(model, p, 8)
    cfg = ServingConfig(num_slots=2, draft_model=agreeing_draft,
                        speculation_k=0)
    with Engine(model, cfg) as eng:
        out = eng.submit(p, max_new_tokens=8).result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(out.output_ids, ref)
    assert snap["spec_windows"] == 0
    assert eng.draft_cache is None


def test_all_accept_windows_bit_equal(model, agreeing_draft):
    """A function-identical draft: every proposal accepted, a+1 tokens
    per window, greedy outputs bit-equal to sequential generate()."""
    prompts = _prompts([9, 5], seed=4)
    K = 4
    cfg = ServingConfig(num_slots=2, draft_model=agreeing_draft,
                        speculation_k=K, enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        snap = eng.stats()
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o.output_ids, _ref_greedy(model, p, 10))
    assert snap["spec_accepted_tokens"] == snap["spec_proposed_tokens"] > 0
    assert snap["spec_acceptance_rate"] == 1.0
    # 10 tokens at K+1=5 per window: far fewer windows than tokens
    assert snap["spec_windows"] <= 6
    assert snap["spec_draft_ms_avg"] > 0
    assert snap["spec_verify_ms_avg"] > 0
    assert snap["spec_rollback_ms_avg"] > 0


def test_all_reject_windows_bit_equal(model):
    """An adversarial (argmin-proposing) draft: zero acceptance, one
    emitted token per window — and the output is STILL bit-equal to
    generate(), because every emitted token is a target argmax."""
    (p,) = _prompts([7], seed=5)
    cfg = ServingConfig(num_slots=1, draft_model=_Negator(model),
                        speculation_k=3, enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        out = eng.submit(p, max_new_tokens=6).result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(out.output_ids, _ref_greedy(model, p, 6))
    assert snap["spec_accepted_tokens"] == 0
    assert snap["spec_proposed_tokens"] > 0
    assert snap["spec_acceptance_rate"] == 0.0
    # first token comes from prefill; each window then emits exactly 1
    assert snap["spec_windows"] == 5


def test_eos_mid_window_truncates(model, agreeing_draft):
    """EOS landing inside an accepted window truncates the rest of it:
    the request completes at the eos exactly as generate() does, and
    the slot's pages all return."""
    (p,) = _prompts([8], seed=15)
    free_ref = _ref_greedy(model, p, 10)
    # pick the token emitted at position 5 as the eos: with K=4 it lands
    # mid-window, not on a window boundary
    eos = int(free_ref[5])
    if eos in free_ref[:5]:      # pragma: no cover - seed-dependent
        pytest.skip("eos token appears earlier; pick another seed")
    ref = _ref_greedy(model, p, 10, eos_token_id=eos)
    cfg = ServingConfig(num_slots=1, draft_model=agreeing_draft,
                        speculation_k=4, enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        out = eng.submit(p, max_new_tokens=10,
                         eos_token_id=eos).result(timeout=300)
        assert eng.cache.pages_in_use == 0
        assert eng.draft_cache.pages_in_use == 0
    assert out.finish_reason == "eos"
    np.testing.assert_array_equal(out.output_ids, ref)
    assert out.output_ids[-1] == eos and out.output_ids.size == 6


def test_mixed_sampling_falls_back_to_plain_step(model, agreeing_draft):
    """A non-greedy request in the batch disables speculation for the
    iteration (accept needs exact argmax matching); everything still
    completes and the greedy request stays correct."""
    from paddle_tpu.serving import SamplingParams
    prompts = _prompts([6, 6], seed=8)
    cfg = ServingConfig(num_slots=2, draft_model=agreeing_draft,
                        speculation_k=4, enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        f_greedy = eng.submit(prompts[0], max_new_tokens=6)
        f_sampled = eng.submit(prompts[1], max_new_tokens=6,
                               sampling=SamplingParams(temperature=0.9))
        out_g = f_greedy.result(timeout=300)
        out_s = f_sampled.result(timeout=300)
    assert out_s.output_ids.size == 6
    assert out_g.output_ids.size == 6


def test_spec_config_validation(model, agreeing_draft):
    with pytest.raises(ValueError, match="draft_model"):
        ServingConfig(speculation_k=2).validate()
    with pytest.raises(ValueError, match="max_seq_len"):
        Engine(model, ServingConfig(
            speculation_k=2,
            draft_model=_make_model(seed=2, num_layers=1, max_seq=32)))
    with pytest.raises(ValueError, match="vocab"):
        Engine(model, ServingConfig(
            speculation_k=2,
            draft_model=_make_model(seed=2, num_layers=1, vocab=64)))


# ------------------------------------------------------------------
# accept-mask rollback: pool accounting
# ------------------------------------------------------------------

def test_rollback_returns_exact_pages():
    """Rollback frees exactly the private pages wholly past the new
    write horizon, re-credits the reservation (available_pages is
    invariant), zeroes the table tail, and regrowth + release round-trip
    to an empty pool."""
    cache = PagedKVCache(num_layers=1, num_slots=2, max_len=64,
                         num_kv_heads=2, head_dim=4, page_size=8,
                         num_pages=10)
    slot = cache.allocate(6)
    avail0 = cache.available_pages
    cache.ensure_capacity(slot, 39)            # 5 pages assigned
    assert cache.pages_in_use == 5 and cache._reserved[slot] == 1
    cache.rollback(slot, 17)                   # keep pages 0..2 (pos 17)
    assert cache.pages_in_use == 3
    assert cache._reserved[slot] == 3
    assert cache.available_pages == avail0     # +free == +reserved
    assert (cache.table[slot, 3:] == 0).all()
    assert (cache.table[slot, :3] > 0).all()
    # the horizon page itself is kept: rollback to a mid-page position
    cache.rollback(slot, 16)                   # pos 16 is page 2's first
    assert cache.pages_in_use == 3
    # regrowth after rollback works (the reservation was re-credited)
    cache.ensure_capacity(slot, 47)
    assert cache.pages_in_use == 6 and cache._reserved[slot] == 0
    cache.release(slot)
    assert cache.pages_in_use == 0 and cache.available_pages == 10


def test_rollback_never_touches_shared_pages():
    cache = PagedKVCache(num_layers=1, num_slots=1, max_len=64,
                         num_kv_heads=2, head_dim=4, page_size=8,
                         num_pages=8)
    # simulate 2 tree-owned prefix pages + private growth behind them
    shared = [cache._free_pages.pop(), cache._free_pages.pop()]
    slot = cache.allocate(3, shared_pages=shared)
    cache.ensure_capacity(slot, 39)            # pages 2..4 private
    assert cache.pages_in_use == 5             # 2 shared + 3 private
    cache.rollback(slot, 0)                    # rewind everything
    assert list(cache.table[slot, :2]) == shared
    assert (cache.table[slot, 2:] == 0).all()
    assert cache._reserved[slot] == 3


def test_spec_engine_all_pages_return_after_load(model, agreeing_draft):
    """After a speculative load with rollbacks every iteration, both
    caches' pools drain to zero — no page leaked through the
    grow/rollback/release cycle."""
    prompts = _prompts([9, 6, 11], seed=9)
    cfg = ServingConfig(num_slots=2, draft_model=agreeing_draft,
                        speculation_k=4, enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        assert eng.cache.pages_in_use == 0
        assert eng.draft_cache.pages_in_use == 0
        assert sum(eng.cache._reserved.values()) == 0
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o.output_ids,
                                      _ref_greedy(model, p, 12))


# ------------------------------------------------------------------
# speculative_generate (models/generation.py)
# ------------------------------------------------------------------

def test_speculative_generate_matches_generate(model):
    """Batch-2 greedy speculative_generate == generate bitwise, with an
    arbitrary (disagreeing) random draft — acceptance only changes the
    speed, never the tokens."""
    from paddle_tpu.models.generation import generate, speculative_generate
    draft = _make_model(seed=11, num_layers=1, hidden=32)
    rng = np.random.default_rng(2)
    ids = paddle.to_tensor(rng.integers(0, 128, (2, 7)).astype("int32"))
    ref = _np(generate(model, ids, max_new_tokens=9, temperature=0.0))
    out = _np(speculative_generate(model, draft, ids, max_new_tokens=9,
                                   speculation_k=4))
    np.testing.assert_array_equal(ref, out)
    # K=0 is exactly generate
    out0 = _np(speculative_generate(model, draft, ids, max_new_tokens=9,
                                    speculation_k=0))
    np.testing.assert_array_equal(ref, out0)


def test_speculative_generate_eos_rows(model):
    """Rows finishing at different eos positions: each row's output up
    to (and including) its eos matches generate's."""
    from paddle_tpu.models.generation import generate, speculative_generate
    rng = np.random.default_rng(3)
    ids = paddle.to_tensor(rng.integers(0, 128, (2, 6)).astype("int32"))
    free = _np(generate(model, ids, max_new_tokens=8, temperature=0.0))
    eos = int(free[0, 6 + 3])                 # row 0 hits it mid-stream
    ref = _np(generate(model, ids, max_new_tokens=8, temperature=0.0,
                       eos_token_id=eos))
    out = _np(speculative_generate(model, model, ids, max_new_tokens=8,
                                   speculation_k=3, eos_token_id=eos))

    def trim(row):
        toks = list(row[6:])
        return toks[:toks.index(eos) + 1] if eos in toks else toks

    for r in range(2):
        assert trim(ref[r]) == trim(out[r])


# ------------------------------------------------------------------
# int8 / quantized KV
# ------------------------------------------------------------------

def test_int8_kv_roundtrip_allclose():
    """Per-token-row quantize -> dequantize round-trips within half a
    quantization step of the original values."""
    import jax.numpy as jnp
    from paddle_tpu.quantization import (dequantize_kv, kv_quant_params,
                                         quantize_kv_rows)
    store, qmax = kv_quant_params("int8")
    assert store == jnp.int8 and qmax == 127.0
    assert kv_quant_params("float32") is None
    assert kv_quant_params("bfloat16") is None
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(4, 7, 2, 8)) *
         rng.uniform(0.1, 30.0, size=(4, 7, 1, 1))).astype(np.float32)
    q, s = quantize_kv_rows(jnp.asarray(x), qmax, store)
    assert np.asarray(q).dtype == np.int8
    xr = np.asarray(dequantize_kv(q, s))
    # error bound: half an lsb per row
    lsb = np.abs(x).max(axis=(-2, -1), keepdims=True) / 127.0
    assert (np.abs(xr - x) <= 0.5001 * lsb).all()


def test_int8_paged_op_allclose_dense():
    """The int8 paged op (quantized write + dequant-fused gather read)
    tracks the dense fp32 op within quantization tolerance."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    rng = np.random.default_rng(4)
    B, H, D, psz, N = 2, 2, 8, 8, 3
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    offs = np.zeros(B, np.int32)
    dense_k = np.zeros((B, N * psz, H, D), np.float32)
    dense_v = np.zeros_like(dense_k)
    cache = {
        "k_pool": Tensor(np.zeros((1 + B * N, psz, H, D), np.int8)),
        "v_pool": Tensor(np.zeros((1 + B * N, psz, H, D), np.int8)),
        "k_scale": Tensor(np.ones((1 + B * N, psz), np.float32)),
        "v_scale": Tensor(np.ones((1 + B * N, psz), np.float32)),
        "page_table": Tensor(np.arange(1, 1 + B * N, dtype=np.int32)
                             .reshape(B, N)),
        "offset": Tensor(offs), "page_size": psz,
    }
    dk = Tensor(dense_k)
    dv = Tensor(dense_v)
    out_q = out_d = None
    for step in range(10):           # fill 10 positions token by token
        k = rng.normal(size=(B, 1, H, D)).astype(np.float32)
        v = rng.normal(size=(B, 1, H, D)).astype(np.float32)
        off_t = Tensor(np.full(B, step, np.int32))
        cache["offset"] = off_t
        out_q = IF.paged_cache_attention(Tensor(q), Tensor(k),
                                         Tensor(v), cache)
        out_d, dk, dv = IF.masked_multihead_attention(
            Tensor(q), Tensor(k), Tensor(v), dk, dv, off_t)
    np.testing.assert_allclose(_np(out_q), _np(out_d),
                               rtol=0.05, atol=0.05)


def test_int8_paged_chunk_reads_its_own_keys_with_their_scales():
    """A chunk of several tokens written into int8 pages is read back
    through the scales the write just made, not the ones it found."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    rng = np.random.default_rng(5)
    B, S, H, D, psz, N = 2, 4, 2, 8, 8, 3
    q, k, v = (rng.normal(size=(B, S, H, D)).astype(np.float32) * 3
               for _ in range(3))
    offs = Tensor(np.array([0, 5], np.int32))
    cache = {
        "k_pool": Tensor(np.zeros((1 + B * N, psz, H, D), np.int8)),
        "v_pool": Tensor(np.zeros((1 + B * N, psz, H, D), np.int8)),
        "k_scale": Tensor(np.ones((1 + B * N, psz), np.float32)),
        "v_scale": Tensor(np.ones((1 + B * N, psz), np.float32)),
        "page_table": Tensor(np.arange(1, 1 + B * N, dtype=np.int32)
                             .reshape(B, N)),
        "offset": offs, "page_size": psz,
    }
    out_q = IF.paged_cache_attention(Tensor(q), Tensor(k), Tensor(v), cache)
    zeros = Tensor(np.zeros((B, N * psz, H, D), np.float32))
    out_d, _, _ = IF.masked_multihead_attention(
        Tensor(q), Tensor(k), Tensor(v), zeros, zeros, offs)
    np.testing.assert_allclose(_np(out_q), _np(out_d), rtol=0.05, atol=0.1)


def test_int8_engine_pages_halve_at_equal_load(model):
    """The capacity claim: int8 pages pack 2x the tokens in half the
    bytes, so the pages-in-use peak at equal token load halves vs the
    fp32 pool (64 positions/request: 4 fp32 pages vs 2 int8 pages)."""
    prompts = _prompts([16, 16], seed=12)
    peaks, outs = {}, {}
    for dtype in ("float32", "int8"):
        cfg = ServingConfig(num_slots=2, cache_dtype=dtype,
                            enable_prefix_cache=False)
        with Engine(model, cfg) as eng:
            futs = [eng.submit(p, max_new_tokens=48) for p in prompts]
            outs[dtype] = [f.result(timeout=300) for f in futs]
            peaks[dtype] = eng.stats()["kv_pages_peak"]
    assert peaks["int8"] * 2 == peaks["float32"], peaks
    for o in outs["int8"]:
        assert o.output_ids.size == 48


def test_int8_spec_engine_combined(model, agreeing_draft):
    """Speculation over a quantized cache: both features compose — the
    engine completes, accepts proposals, and rollback keeps the pool
    clean (outputs may differ from fp32 greedy by quantization)."""
    (p,) = _prompts([9], seed=13)
    cfg = ServingConfig(num_slots=1, cache_dtype="int8",
                        draft_model=agreeing_draft, speculation_k=4,
                        enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        out = eng.submit(p, max_new_tokens=10).result(timeout=300)
        snap = eng.stats()
        assert eng.cache.pages_in_use == 0
    assert out.output_ids.size == 10
    assert snap["spec_windows"] > 0
    assert snap["spec_accepted_tokens"] > 0
