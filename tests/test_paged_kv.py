"""Paged KV cache serving (serving/paged_kv.py): page-pool bookkeeping,
pool-exhaustion backpressure, prefix-tree refcounts/eviction, chunked
prefill equivalence, and the paged attention op/kernel."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (
    DeadlineExceededError, Engine, PagedKVCache, PrefixTree,
    QueueFullError, ServingConfig, serving_stats,
)


def _np(t):
    return np.asarray(t._data_)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=128, num_heads=4,
        vocab_size=512, max_seq_len=128))
    m.eval()
    return m


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


def _ref_greedy(model, prompt, max_new):
    ids = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=max_new, temperature=0.0)
    return _np(ids)[0, prompt.size:]


# ------------------------------------------------------------------
# pool bookkeeping
# ------------------------------------------------------------------

def test_paged_cache_bookkeeping():
    cache = PagedKVCache(num_layers=2, num_slots=2, max_len=64,
                         num_kv_heads=2, head_dim=4, page_size=16,
                         num_pages=6)
    assert cache.usable_pages == 6 and cache.pages_in_use == 0
    assert cache.capacity == 64 and cache.pages_per_slot == 4
    assert cache.free_slots == 2
    # reservation counts against availability before any page moves
    slot = cache.allocate(3)
    assert slot is not None and cache.free_slots == 1
    assert cache.pages_in_use == 0 and cache.available_pages == 3
    # growth assigns pages lazily, one per boundary crossing
    cache.ensure_capacity(slot, 0)
    assert cache.pages_in_use == 1
    cache.ensure_capacity(slot, 15)           # same page: no-op
    assert cache.pages_in_use == 1
    cache.ensure_capacity(slot, 33)           # crosses into page 3
    assert cache.pages_in_use == 3 and cache.available_pages == 3
    assert (cache.table[slot, :3] > 0).all()  # scratch page 0 never used
    assert cache.table[slot, 3] == 0
    # a second reservation past availability is refused, not crashed
    assert cache.allocate(4) is None
    other = cache.allocate(3)
    assert other is not None and cache.available_pages == 0
    # release returns private pages AND the unclaimed reservation
    cache.release(slot)
    assert cache.pages_in_use == 0 and cache.available_pages == 3
    with pytest.raises(ValueError, match="already free"):
        cache.release(slot)
    cache.release(other)
    assert cache.available_pages == 6 and cache.free_slots == 2
    # offsets/page table ride ONE shared device array across layers
    s2 = cache.allocate(1)
    cache.set_offset(s2, 5)
    cache.advance([s2])
    lays = cache.layer_caches()
    assert _np(lays[0]["offset"])[s2] == 6
    assert lays[0]["offset"] is lays[1]["offset"]
    assert lays[0]["page_table"] is lays[1]["page_table"]


def test_submit_rejects_infeasible_request(model):
    cfg = ServingConfig(num_slots=1, page_size=16, kv_pool_pages=2)
    with Engine(model, cfg) as eng:
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit(np.zeros(40, np.int32), max_new_tokens=20)
        # a request the pool CAN hold still flows
        out = eng.submit(np.zeros(10, np.int32),
                         max_new_tokens=4).result(timeout=300)
        assert out.output_ids.size == 4


def test_pool_exhaustion_backpressure(model):
    """More concurrent demand than pages: requests queue (never crash),
    QueueFullError only past max_queue, and everything completes."""
    # pool fits ONE request at a time (each needs 3 of the 4 pages)
    cfg = ServingConfig(num_slots=4, page_size=16, kv_pool_pages=4,
                        max_queue=2, enable_prefix_cache=False)
    prompts = _prompts([10, 12, 9, 11], seed=5)
    eng = Engine(model, cfg).start()
    try:
        import time
        first = eng.submit(prompts[0], max_new_tokens=24)
        t0 = time.monotonic()
        while serving_stats()["queue_depth"] > 0:      # admitted?
            time.sleep(0.005)
            assert time.monotonic() - t0 < 60
        queued = [eng.submit(p, max_new_tokens=24) for p in prompts[1:3]]
        with pytest.raises(QueueFullError):
            eng.submit(prompts[3], max_new_tokens=24)
        outs = [f.result(timeout=300) for f in [first] + queued]
        for p, o in zip(prompts[:3], outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 24))
        assert eng.cache.pages_in_use == 0        # all pages returned
        snap = eng.stats()
        assert snap["requests_completed"] == 3
    finally:
        eng.shutdown()


def test_deadline_evict_and_drain_return_all_pages(model):
    """Satellite: deadline eviction (mid-decode AND mid-prefill) and
    drain leak no pages across engine restarts."""
    cfg = ServingConfig(num_slots=2, page_size=16,
                        enable_prefix_cache=False,
                        prefill_chunk_tokens=8)
    (short, long) = _prompts([5, 100], seed=2)
    eng = Engine(model, cfg).start()
    try:
        doomed = eng.submit(short, max_new_tokens=10000, deadline_s=0.05)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=300)
        # a 100-token prompt at 8 tokens/chunk cannot beat a 1ms
        # deadline: evicted mid-prefill
        slow = eng.submit(long, max_new_tokens=4, deadline_s=0.001)
        with pytest.raises(DeadlineExceededError):
            slow.result(timeout=300)
        ok = eng.submit(short, max_new_tokens=3).result(timeout=300)
        np.testing.assert_array_equal(ok.output_ids,
                                      _ref_greedy(model, short, 3))
        assert eng.cache.pages_in_use == 0
        eng.drain(deadline_s=5.0)
        assert eng.cache.pages_in_use == 0
    finally:
        eng.shutdown()
    # restart reuses nothing stale: fresh pool, requests still exact
    eng = Engine(model, cfg).start()
    try:
        assert eng.cache.pages_in_use == 0
        out = eng.submit(short, max_new_tokens=4).result(timeout=300)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, short, 4))
    finally:
        eng.shutdown()


# ------------------------------------------------------------------
# prefix tree
# ------------------------------------------------------------------

def test_prefix_tree_refcounts_and_eviction():
    cache = PagedKVCache(num_layers=1, num_slots=2, max_len=64,
                         num_kv_heads=2, head_dim=4, page_size=4,
                         num_pages=8)
    tree = PrefixTree(page_size=4)
    prompt = np.arange(10, dtype=np.int32)          # 2 full pages + 2
    nodes, pages = tree.match(prompt)
    assert nodes == [] and pages == []
    slot = cache.allocate(3)
    for pos in (0, 4, 8):
        cache.ensure_capacity(slot, pos)
    held = []
    assert tree.insert(prompt, cache, slot, held) == 2
    assert [n.refs for n in held] == [1, 1]
    assert tree.cached_pages() == 2
    # a second request matching the prefix bumps refcounts
    nodes2, pages2 = tree.match(prompt)
    assert len(pages2) == 2 and [n.refs for n in nodes2] == [2, 2]
    # match never hands out the whole prompt: last token is recomputed
    exact = np.arange(8, dtype=np.int32)            # == 2 full pages
    nodes3, pages3 = tree.match(exact)
    assert len(pages3) == 1                         # (8-1)//4 == 1 page
    tree.release(nodes3)
    # refcounts drop to zero on release...
    tree.release(held)
    tree.release(nodes2)
    assert all(n.refs == 0 for n in held)
    # ...but pages stay cached (warm) until pool pressure evicts LRU
    assert tree.cached_pages() == 2
    freed = tree.evict(10, cache.reclaim)
    assert freed == 2 and tree.cached_pages() == 0
    cache.release(slot)
    assert cache.pages_in_use == 0                  # nothing leaked


def test_prefix_reuse_bit_equal_and_counted(model):
    """Requests sharing a system prompt reuse its KV pages: greedy
    output stays bit-equal to sequential generate(), hits are counted,
    and releasing every request drops tree refcounts to zero."""
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, 512, (48,)).astype("int32")
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 512, (4,)).astype("int32")])
               for _ in range(3)]
    cfg = ServingConfig(num_slots=2, page_size=16,
                        prefill_chunk_tokens=16)
    with Engine(model, cfg) as eng:
        warm = eng.submit(prompts[0], max_new_tokens=5).result(timeout=300)
        np.testing.assert_array_equal(
            warm.output_ids, _ref_greedy(model, prompts[0], 5))
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        snap = eng.stats()
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 5))
        assert snap["prefix_cache_hits"] >= 3
        assert snap["prefix_cache_hit_tokens"] >= 3 * 48
        # every request released: only the tree still owns pages
        tree_pages = eng.prefix_tree.cached_pages()
        assert tree_pages >= 3                      # 48-token prefix
        assert eng.cache.pages_in_use == tree_pages


# ------------------------------------------------------------------
# chunked prefill
# ------------------------------------------------------------------

def test_chunked_prefill_byte_equal_one_shot(model):
    """The same prompt prefilled 8 tokens at a time vs in one shot:
    byte-identical outputs (and both equal generate())."""
    (p,) = _prompts([50], seed=9)
    outs = {}
    for chunk in (8, 128):          # 128 >= prompt: single chunk
        cfg = ServingConfig(num_slots=2, prefill_chunk_tokens=chunk,
                            enable_prefix_cache=False)
        with Engine(model, cfg) as eng:
            outs[chunk] = eng.submit(p, max_new_tokens=6).result(
                timeout=300)
            snap = eng.stats()
        assert snap["prefill_chunks"] == (7 if chunk == 8 else 1)
        assert snap["prefill_chunk_ms_avg"] > 0
    np.testing.assert_array_equal(outs[8].output_ids,
                                  outs[128].output_ids)
    np.testing.assert_array_equal(outs[8].output_ids,
                                  _ref_greedy(model, p, 6))


def test_long_prompt_does_not_starve_inflight_decode(model):
    """Chunked prefill interleaves with decode: a stream that is
    already decoding keeps producing tokens while a long prompt
    prefills, instead of stalling for the whole prompt pass."""
    (short, long) = _prompts([4, 100], seed=13)
    cfg = ServingConfig(num_slots=2, prefill_chunk_tokens=8,
                        enable_prefix_cache=False)
    with Engine(model, cfg) as eng:
        first = eng.submit(short, max_new_tokens=40)
        # wait until the short request is decoding
        import time
        t0 = time.monotonic()
        while serving_stats()["active_slots"] < 1:
            time.sleep(0.005)
            assert time.monotonic() - t0 < 60
        before = serving_stats()["decode_steps"]
        fut = eng.submit(long, max_new_tokens=4)
        out_long = fut.result(timeout=300)
        snap = eng.stats()
        out_short = first.result(timeout=300)
    # 100 tokens / 8-token chunks = 13 chunks; decode ran meanwhile
    assert snap["prefill_chunks"] >= 13
    assert snap["decode_steps"] - before >= 5
    np.testing.assert_array_equal(out_short.output_ids,
                                  _ref_greedy(model, short, 40))
    np.testing.assert_array_equal(out_long.output_ids,
                                  _ref_greedy(model, long, 4))


def test_paged_admits_more_sequences_than_preallocation(model):
    """The acceptance bound: a pool of the bytes that 2 full
    max_seq_len stretches take runs 4 sequences concurrently, because
    pages are claimed as sequences grow."""
    pages_per_slot = 128 // 16
    cfg = ServingConfig(num_slots=4, page_size=16,
                        kv_pool_pages=2 * pages_per_slot,   # 2 stripes
                        enable_prefix_cache=False)
    prompts = _prompts([6, 9, 7, 8], seed=21)
    with Engine(model, cfg) as eng:
        futs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        snap = eng.stats()
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o.output_ids,
                                      _ref_greedy(model, p, 16))
    assert snap["max_active_slots"] == 4      # > the 2 stripes' worth


# ------------------------------------------------------------------
# op / kernel equivalence
# ------------------------------------------------------------------

def test_paged_op_matches_dense_op():
    """Same logical cache through the paged layout and the dense slot
    layout: the same attention output to float32 rounding (the paged
    read folds its keys into an online softmax, the dense op divides
    first), and bit-identical writes."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    rng = np.random.default_rng(3)
    B, S_max, H, Hkv, D, psz = 2, 32, 4, 2, 8, 8
    n_pages = S_max // psz
    offs = np.array([5, 19], np.int32)
    dense_k = rng.normal(size=(B, S_max, Hkv, D)).astype(np.float32)
    dense_v = rng.normal(size=(B, S_max, Hkv, D)).astype(np.float32)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    k = rng.normal(size=(B, 1, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, 1, Hkv, D)).astype(np.float32)
    # paged copy of the same cache through a shuffled page table
    table = np.zeros((B, n_pages), np.int32)
    perm = rng.permutation(np.arange(1, 1 + B * n_pages))
    k_pool = np.zeros((1 + B * n_pages, psz, Hkv, D), np.float32)
    v_pool = np.zeros_like(k_pool)
    for b in range(B):
        for j in range(n_pages):
            pg = int(perm[b * n_pages + j])
            table[b, j] = pg
            k_pool[pg] = dense_k[b, j * psz:(j + 1) * psz]
            v_pool[pg] = dense_v[b, j * psz:(j + 1) * psz]
    out_d, ck, cv = IF.masked_multihead_attention(
        Tensor(q), Tensor(k), Tensor(v), Tensor(dense_k),
        Tensor(dense_v), Tensor(offs))
    out_p, kp, vp = IF.paged_masked_multihead_attention(
        Tensor(q), Tensor(k), Tensor(v), Tensor(k_pool),
        Tensor(v_pool), Tensor(table), Tensor(offs), psz)
    np.testing.assert_allclose(_np(out_d), _np(out_p), rtol=2e-6, atol=2e-7)
    # and the write landed in the right page/position
    for b in range(B):
        pg = table[b, offs[b] // psz]
        np.testing.assert_array_equal(_np(kp)[pg, offs[b] % psz], k[b, 0])
        np.testing.assert_array_equal(_np(vp)[pg, offs[b] % psz], v[b, 0])


def _gather_reference(q, kf, vf, off, scale):
    """Attention of one query token a row over the gathered view
    ``[B, S, Hkv, D]``, positions <= off, in float64."""
    B, H, D = q.shape
    Hkv = kf.shape[2]
    rep = H // Hkv
    ref = np.zeros((B, H, D))
    for b in range(B):
        for h in range(H):
            s = (kf[b, :, h // rep].astype(np.float64)
                 @ q[b, h].astype(np.float64)) * scale
            s[np.arange(kf.shape[1]) > off[b]] = -np.inf
            p = np.exp(s - s.max())
            ref[b, h] = (p / p.sum()) @ vf[b, :, h // rep].astype(np.float64)
    return ref


#  B  H  Hkv D  page pages offsets   pool  empty rows  scale  pages a step
_PAGED_KERNEL_CASES = {
    "f32-gqa": (3, 16, 8, 16, 8, 6, [5, 17, 30], "float32", (), None, 0),
    "int8-scales": (2, 16, 8, 16, 8, 3, [6, 19], "int8", (), None, 0),
    "offset-0": (2, 16, 8, 16, 8, 6, [0, 9], "float32", (), None, 2),
    "fills-the-slot": (2, 16, 8, 16, 8, 6, [47, 3], "float32", (), None, 2),
    "empty-row-beside-live": (4, 16, 8, 16, 8, 6, [21, 0, 40, 0],
                              "float32", (1, 3), None, 2),
    "ends-mid-group": (2, 16, 8, 16, 8, 6, [20, 36], "float32", (), None,
                       2),
    "ends-on-a-groups-last-page": (2, 16, 8, 16, 8, 6, [31, 15], "float32",
                                   (), None, 2),
    "n_rep-1": (2, 8, 8, 16, 8, 6, [11, 44], "float32", (), None, 2),
    "d64-explicit-scale": (2, 8, 2, 64, 8, 6, [13, 38], "float32", (),
                           1.0 / 64, 2),
    "bf16-pool": (2, 32, 8, 128, 16, 4, [37, 63], "bfloat16", (), None, 2),
    "int8-two-groups": (2, 16, 8, 16, 8, 6, [6, 41], "int8", (), None, 2),
}


@pytest.mark.parametrize("case", sorted(_PAGED_KERNEL_CASES))
def test_paged_pallas_kernel_matches_gather_path(case, monkeypatch):
    """The Pallas paged-decode kernel — its own page DMAs through the
    scalar-prefetched table, run by the TPU interpreter
    (``pltpu.InterpretParams``: scratch starts as NaN) — agrees with a
    gather of the row's pages and a plain softmax."""
    import jax.numpy as jnp
    from paddle_tpu.pallas import flash_attention as fa
    B, H, Hkv, D, psz, N, off, pool, empty, scale, group = \
        _PAGED_KERNEL_CASES[case]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    if group:       # several steps a row at these small shapes
        item = {"float32": 4, "bfloat16": 2, "int8": 1}[pool]
        monkeypatch.setattr(fa, "_PAGED_STEP_BYTES",
                            group * psz * Hkv * D * item)
        assert fa.paged_decode_pages_per_step(psz, Hkv, D, item) == group
    rng = np.random.default_rng(sorted(_PAGED_KERNEL_CASES).index(case))
    P = 1 + B * N
    shape = (P, psz, Hkv, D)
    pt = rng.permutation(np.arange(1, P)).reshape(B, N).astype(np.int32)
    off = np.array(off, np.int32)
    for b in empty:                      # a free slot: scratch page 0
        pt[b], off[b] = 0, 0
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    scales = {}
    if pool == "int8":
        k_pool = rng.integers(-127, 128, shape).astype(np.int8)
        v_pool = rng.integers(-127, 128, shape).astype(np.int8)
        k_scale = rng.uniform(0.005, 0.03, (P, psz)).astype(np.float32)
        v_scale = rng.uniform(0.005, 0.03, (P, psz)).astype(np.float32)
        scales = dict(k_scale=jnp.asarray(k_scale),
                      v_scale=jnp.asarray(v_scale))
        kd = k_pool.astype(np.float32) * k_scale[:, :, None, None]
        vd = v_pool.astype(np.float32) * v_scale[:, :, None, None]
        kj, vj, qj, tol = jnp.asarray(k_pool), jnp.asarray(v_pool), \
            jnp.asarray(q), 1e-5
    else:
        dt = jnp.dtype(pool)
        kj = jnp.asarray(rng.normal(size=shape), dt)
        vj = jnp.asarray(rng.normal(size=shape), dt)
        qj = jnp.asarray(q, dt)
        kd, vd = np.asarray(kj.astype(jnp.float32)), \
            np.asarray(vj.astype(jnp.float32))
        q = np.asarray(qj.astype(jnp.float32))
        # bfloat16: the probabilities take the pool's type for P.V
        tol = 1e-5 if pool == "float32" else 2e-2
    out = fa.paged_decode_attention(
        qj, kj, vj, jnp.asarray(pt), jnp.asarray(off), scale=scale,
        **scales)
    assert out.shape == (B, H, D) and out.dtype == qj.dtype
    ref = _gather_reference(
        q, kd[pt].reshape(B, N * psz, Hkv, D),
        vd[pt].reshape(B, N * psz, Hkv, D), off,
        scale if scale is not None else 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), ref,
                               rtol=tol, atol=tol)


def _ring_cache(rng, B, ring, psz, Hkv, D, off, window, dt):
    """Pools and ring tables holding, for each row, the keys of the
    positions its window can see at offset ``off[b]`` (logical page p at
    entry p % ring), 1e4 everywhere else (finite, as stale pages are: a
    masked key still meets a probability of exactly 0): a read that
    strays out of the window swamps the result.  Returns (k_pool, v_pool, table, {b: {pos: (k, v)}})."""
    import jax.numpy as jnp
    P = 1 + B * ring
    k_pool = np.full((P, psz, Hkv, D), 1e4, np.float32)
    v_pool = np.full((P, psz, Hkv, D), 1e4, np.float32)
    table = rng.permutation(np.arange(1, P)).reshape(B, ring).astype(np.int32)
    kept = {}
    for b in range(B):
        kept[b] = {}
        for pos in range(max(0, off[b] + 1 - window), off[b] + 1):
            page = table[b, (pos // psz) % ring]
            kv = rng.normal(size=(2, Hkv, D)).astype(np.float32)
            k_pool[page, pos % psz], v_pool[page, pos % psz] = kv
            kept[b][pos] = kv
    return (jnp.asarray(k_pool, dt), jnp.asarray(v_pool, dt),
            jnp.asarray(table), kept)


def _window_reference(q, kept, scale):
    B, H, D = q.shape
    ref = np.zeros((B, H, D))
    for b in range(B):
        pos = sorted(kept[b])
        k = np.stack([kept[b][p][0] for p in pos]).astype(np.float64)
        v = np.stack([kept[b][p][1] for p in pos]).astype(np.float64)
        rep = H // k.shape[1]
        for h in range(H):
            s = k[:, h // rep] @ q[b, h].astype(np.float64) * scale
            p = np.exp(s - s.max())
            ref[b, h] = (p / p.sum()) @ v[:, h // rep]
    return ref


#  window  ring entries  offsets: the row's context is off + 1
_WINDOW_CASES = {
    "window-over-the-context": (24, 6, [5, 17, 20]),
    "window-equal-to-the-context": (24, 6, [23, 23, 0]),
    "window-under-the-context": (24, 6, [24, 37, 100]),
    "window-not-a-page-multiple": (21, 6, [30, 21, 77]),
    "window-of-one-page-wraps-often": (8, 3, [8, 63, 200]),
}


@pytest.mark.parametrize("group", [0, 2], ids=["one-step", "steps-of-2"])
@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_paged_kernel_lower_bound_matches_the_window(case, group,
                                                     monkeypatch):
    """The kernel starts at the row's first in-window page, masks the
    partial first page by position and reads the table as a ring: it
    agrees with a plain softmax over exactly the window's positions, and
    so does the XLA lane; every position outside the window holds 1e4."""
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    from paddle_tpu.pallas import flash_attention as fa
    window, ring, off = _WINDOW_CASES[case]
    B, H, Hkv, D, psz = 3, 16, 8, 16, 8
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    if group:
        monkeypatch.setattr(fa, "_PAGED_STEP_BYTES",
                            group * psz * Hkv * D * 4)
    rng = np.random.default_rng(sorted(_WINDOW_CASES).index(case))
    off = np.array(off, np.int32)
    kj, vj, pt, kept = _ring_cache(rng, B, ring, psz, Hkv, D, off, window,
                                   jnp.float32)
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    ref = _window_reference(q, kept, 1.0 / np.sqrt(D))
    out = fa.paged_decode_attention(jnp.asarray(q), kj, vj, pt,
                                    jnp.asarray(off), window=window)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)
    # the XLA lane, through the op: it writes the new token first, so
    # hand it the cache one token short and that token as k, v
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    new_k = np.stack([kept[b][off[b]][0] for b in range(B)])[:, None]
    new_v = np.stack([kept[b][off[b]][1] for b in range(B)])[:, None]
    xla, _, _ = IF.paged_masked_multihead_attention(
        Tensor(q[:, None]), Tensor(new_k), Tensor(new_v), Tensor(kj),
        Tensor(vj), Tensor(pt), Tensor(off), psz, window=window)
    np.testing.assert_allclose(_np(xla)[:, 0], ref, rtol=1e-5, atol=1e-5)


def test_chunk_read_over_a_ring_sees_each_querys_window():
    """A prefill chunk over a ring table: every query of the chunk sees
    its own ``window`` latest positions, the chunk's own keys written
    first, in blocks of keys."""
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    B, H, Hkv, D, psz, S, window = 2, 4, 2, 8, 4, 8, 10
    ring = -(-(window + S) // psz) + 1
    rng = np.random.default_rng(9)
    start = np.array([0, 37], np.int32)
    last = start + S - 1
    kj, vj, pt, kept = _ring_cache(rng, B, ring, psz, Hkv, D, last,
                                   window + S - 1, jnp.float32)
    new_k = np.stack([[kept[b][p][0] for p in range(start[b], last[b] + 1)]
                      for b in range(B)])
    new_v = np.stack([[kept[b][p][1] for p in range(start[b], last[b] + 1)]
                      for b in range(B)])
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    out, _, _ = IF.paged_masked_multihead_attention(
        Tensor(q), Tensor(new_k), Tensor(new_v), Tensor(kj), Tensor(vj),
        Tensor(pt), Tensor(start), psz, window=window)
    for i in range(S):
        seen = {b: {p: kv for p, kv in kept[b].items()
                    if start[b] + i - window < p <= start[b] + i}
                for b in range(B)}
        np.testing.assert_allclose(
            _np(out)[:, i], _window_reference(q[:, i], seen,
                                              1.0 / np.sqrt(D)),
            rtol=1e-5, atol=1e-5)


def test_no_window_traces_the_kernel_it_traced_before():
    """Without a window the lower bound is a static None, not a traced
    zero: the call's jaxpr is, to the character, what the kernel traced
    before windows existed (the digests are of the parent commit's
    trace), so a model with one kind of paged layer keeps the tick
    program the compile cache holds."""
    import hashlib
    import os
    import jax
    import jax.numpy as jnp
    from paddle_tpu.pallas import flash_attention as fa
    want = {
        "float32": "36660f5cec23471c0a6aa2a68145f141152209be5db7e88b0c1e"
                   "7561580d1e92",
        "bfloat16": "35d90a0b743832243e28cf32d32fcfc690c50b42959c072e2d7"
                    "c68a66753eebb"}
    saved = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    try:
        for name, digest in want.items():
            b, h, hkv, d, psz, n = 3, 8, 2, 128, 8, 6
            q = jnp.zeros((b, h, d), name)
            pool = jnp.zeros((b * n + 1, psz, hkv, d), name)
            pt = jnp.zeros((b, n), jnp.int32)
            off = jnp.zeros((b,), jnp.int32)
            text = str(jax.make_jaxpr(
                lambda *a: fa.paged_decode_attention(*a))(q, pool, pool, pt,
                                                          off))
            assert hashlib.sha256(text.encode()).hexdigest() == digest, name
            windowed = str(jax.make_jaxpr(
                lambda *a: fa.paged_decode_attention(*a, window=16))(
                    q, pool, pool, pt, off))
            assert windowed != text
    finally:
        if saved is None:
            del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
        else:
            os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = saved


_RULE_SHAPES = [
    (16, 8, 128, 2, 16),       # mistral-7b: 32 KB pages, 512 KB a step
    (16, 8, 64, 2, 32),        # granite-4.0-h-micro: two heads a row
    (16, 12, 64, 4, 8),        # gpt-2 124m, float32 pages
    (32, 12, 64, 1, 16),       # gpt-2 124m, int8 pages
    (16, 16, 128, 2, 8),       # gpt-3 1.3b
    (8, 2, 16, 4, 0),          # 2 kv heads of 16 do not fill a row
    (16, 8, 256, 2, 0),        # a head wider than the lanes
    (4, 8, 128, 2, 64),        # small pages: more of them a step
    (2, 2, 64, 4, 0),          # a page of 2 rows is no whole tile
]


@pytest.mark.parametrize("page_size,h_kv,d,itemsize,pages", _RULE_SHAPES)
def test_paged_decode_pages_per_step_is_a_rule_on_shapes(
        page_size, h_kv, d, itemsize, pages):
    from paddle_tpu.pallas.flash_attention import \
        paged_decode_pages_per_step
    assert paged_decode_pages_per_step(page_size, h_kv, d,
                                       itemsize) == pages


@pytest.mark.parametrize("h_kv,lane", [(8, "kernel"), (2, "xla_lane")])
def test_paged_decode_lane_is_counted_where_it_is_traced(
        h_kv, lane, monkeypatch):
    """A single-token paged read counts, once a trace, which lane the
    shape rule gave it; a prefill chunk (the XLA lane by definition)
    counts nothing; ``serving_stats()`` shows both counters."""
    import jax
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    from paddle_tpu.utils import monitor
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    B, D, psz, N = 2, 16, 8, 3
    P = 1 + B * N
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(P, psz, h_kv, D)).astype(np.float32)
    table = np.arange(1, P).reshape(B, N).astype(np.int32)
    offs = np.array([4, 13], np.int32)

    def read(s_new):
        x = rng.normal(size=(B, s_new, h_kv, D)).astype(np.float32)

        def f(x, kp, vp, pt, off):
            out, _, _ = IF.paged_masked_multihead_attention(
                Tensor(x), Tensor(x), Tensor(x), Tensor(kp), Tensor(vp),
                Tensor(pt), Tensor(off), psz)
            return out._data_
        return jax.jit(f)(x, pool, pool, table, offs)

    def counts():
        s = monitor.all_stats()
        return {k: s.get("pallas.paged_decode." + k, 0)
                for k in ("kernel", "xla_lane")}

    before = counts()
    read(4)
    assert counts() == before
    out = read(1)
    after = counts()
    other = "xla_lane" if lane == "kernel" else "kernel"
    assert after[lane] == before[lane] + 1 and after[other] == before[other]
    assert np.isfinite(np.asarray(out)).all()
    stats = serving_stats()
    assert stats["paged_decode_kernel_traces"] == after["kernel"]
    assert stats["paged_decode_xla_lane_traces"] == after["xla_lane"]


# ------------------------------------------------------------------
# lane-dense pools: a pool of narrow heads lives as the kernel reads it
# ------------------------------------------------------------------

_POOL_DTYPES = {4: "float32", 2: "bfloat16", 1: "int8"}


@pytest.mark.parametrize("table", ["full", "ring"])
@pytest.mark.parametrize("page_size,h_kv,d,itemsize,pages", _RULE_SHAPES)
def test_pool_lives_in_the_shape_the_rule_gives(
        page_size, h_kv, d, itemsize, pages, table):
    """The cache stores a pool ``[P, rows, 128]`` exactly where the
    kernel hosts it and its heads are narrower than the lanes; every
    other pool stays ``[P, page_size, H, D]`` — for the full table's
    pools and the ring table's alike, from shapes alone."""
    dtype = _POOL_DTYPES[itemsize]
    ring = table == "ring"
    kw = {"layer_windows": [page_size, None], "window_slack": 1} \
        if ring else {}
    if ring and dtype == "int8":
        with pytest.raises(ValueError, match="no window layers"):
            PagedKVCache(2, 2, 4 * page_size, h_kv, d,
                         page_size=page_size, dtype=dtype, **kw)
        return
    cache = PagedKVCache(2, 2, 4 * page_size, h_kv, d,
                         page_size=page_size, dtype=dtype, **kw)
    dense = pages > 0 and d < 128
    page = (page_size * h_kv * d // 128, 128) if dense \
        else (page_size, h_kv, d)
    assert cache.page_shape == (page_size, h_kv, d)
    assert cache.stored_page_shape == page
    assert cache.pools == 4
    assert cache.pools_lane_dense == (4 if dense else 0)
    assert bool(cache.ring_pages) is ring
    for i, lay in enumerate(cache.layers):
        n = 2 * cache.ring_pages + 1 if ring and i == 0 else 2 * 4 + 1
        for key in ("k_pool", "v_pool"):
            assert tuple(lay[key].shape) == (n,) + page, (i, key)
            assert lay[key]._data_.dtype.itemsize == itemsize
    if (page_size, h_kv, d) == (16, 8, 64):     # granite-4.0-h-micro
        assert cache.stored_page_shape == (64, 128)


_DENSE_CASES = [(lane, s_new, kind)
                for lane in ("xla", "kernel")
                for s_new in (1, 5)
                for kind in ("plain", "ring", "int8")
                if not (lane == "kernel" and s_new > 1)]


@pytest.mark.parametrize("lane,s_new,kind", _DENSE_CASES)
def test_op_on_a_lane_dense_pool_equals_the_op_on_the_4d_pool(
        lane, s_new, kind, monkeypatch):
    """The same bytes as ``[P, page_size, H, D]`` and as ``[P, rows,
    128]`` through the op: outputs and the written pools (and scales)
    bit-equal — a single token on both lanes, a chunk, a window ring,
    int8 pages."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    if lane == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(11)
    B, H, Hkv, D, psz, N = 3, 4, 2, 64, 8, 4
    P, rows = 1 + B * N, psz * Hkv * D // 128
    window = 11 if kind == "ring" else None
    table = rng.permutation(np.arange(1, P)).reshape(B, N).astype(np.int32)
    # a ring's positions run past the table: logical page p at p % N
    offs = np.array([3, 17, 26 if window is None else 41], np.int32)
    q = rng.normal(size=(B, s_new, H, D)).astype(np.float32)
    k = rng.normal(size=(B, s_new, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, s_new, Hkv, D)).astype(np.float32)
    kw = {}
    if kind == "int8":
        k_pool = rng.integers(-127, 128, (P, psz, Hkv, D)).astype(np.int8)
        v_pool = rng.integers(-127, 128, (P, psz, Hkv, D)).astype(np.int8)
        scales = [rng.uniform(0.005, 0.03, (P, psz)).astype(np.float32)
                  for _ in range(2)]
    else:
        k_pool = rng.normal(size=(P, psz, Hkv, D)).astype(np.float32)
        v_pool = rng.normal(size=(P, psz, Hkv, D)).astype(np.float32)

    def call(shape):
        if kind == "int8":
            kw.update(k_scale=Tensor(scales[0]), v_scale=Tensor(scales[1]))
        res = IF.paged_masked_multihead_attention(
            Tensor(q), Tensor(k), Tensor(v),
            Tensor(k_pool.reshape(shape)), Tensor(v_pool.reshape(shape)),
            Tensor(table), Tensor(offs), psz, window=window, **kw)
        return [_np(r) for r in res]

    four, dense = call((P, psz, Hkv, D)), call((P, rows, 128))
    assert dense[1].shape == dense[2].shape == (P, rows, 128)
    assert len(four) == len(dense) == (5 if kind == "int8" else 3)
    np.testing.assert_array_equal(dense[0], four[0])
    for got, want in zip(dense[1:3], four[1:3]):
        np.testing.assert_array_equal(got.reshape(want.shape), want)
    for got, want in zip(dense[3:], four[3:]):
        np.testing.assert_array_equal(got, want)
    # and the write did land: row b's first new token, in its page
    b = 1
    entry = (offs[b] // psz) % N if window is not None else offs[b] // psz
    page = four[1][table[b, entry], offs[b] % psz]
    if kind == "int8":
        np.testing.assert_allclose(
            page.astype(np.float32) * four[3][table[b, entry],
                                              offs[b] % psz],
            k[b, 0], atol=0.03)
    else:
        np.testing.assert_array_equal(page, k[b, 0])


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_lane_dense_pages_migrate_in_the_wire_shape(dtype):
    """``export_pages`` -> ``adopt_pages`` at head size 64: the wire
    carries ``[layers, n, page_size, H, D]`` whatever shape the pools
    live in, the adopted pages are bit-equal, and a pool of another
    geometry — the same bytes a page — still refuses them."""
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving import PageMigrationError
    rng = np.random.default_rng(5)
    psz, Hkv, D = 16, 2, 64
    a = PagedKVCache(2, 2, 64, Hkv, D, page_size=psz, dtype=dtype)
    assert a.stored_page_shape == (16, 128)
    slot = a.allocate(4)
    a.ensure_capacity(slot, 47)           # 3 pages assigned
    a.set_offset(slot, 37)
    for lay in a.layers:
        for key in ("k_pool", "v_pool", "k_scale", "v_scale"):
            if key not in lay:
                continue
            shp, dt = lay[key].shape, lay[key]._data_.dtype
            vals = rng.integers(-127, 127, shp) if dt == jnp.int8 \
                else rng.random(shp)
            lay[key] = Tensor(jnp.asarray(vals, dt))
    off, k, v, ks, vs = a.export_pages(slot)
    assert off == 37 and k.shape == v.shape == (2, 3, psz, Hkv, D)
    assert (ks is None) == (dtype == "float32")
    for li in range(2):                   # a page's bytes, reshaped
        pool = np.asarray(a.layers[li]["k_pool"]._data_)
        for j in range(3):
            np.testing.assert_array_equal(
                k[li, j], pool[a.table[slot, j]].reshape(psz, Hkv, D))
    b = PagedKVCache(2, 2, 64, Hkv, D, page_size=psz, num_pages=8,
                     dtype=dtype)
    s2 = b.adopt_pages(1, off, k, v, ks, vs)
    assert s2 is not None and int(b.offsets[s2]) == 37
    for li in range(2):
        for key in ("k_pool", "v_pool", "k_scale", "v_scale"):
            if key not in a.layers[li]:
                continue
            pa = np.asarray(a.layers[li][key]._data_)
            pb = np.asarray(b.layers[li][key]._data_)
            assert pb.shape[1:] == pa.shape[1:]
            for j in range(3):
                np.testing.assert_array_equal(
                    pb[b.table[s2, j]], pa[a.table[slot, j]])
    # 4 kv heads of 32: as many bytes a page, another geometry
    c = PagedKVCache(2, 2, 64, 4, 32, page_size=psz, dtype=dtype)
    assert c.stored_page_shape == a.stored_page_shape
    with pytest.raises(PageMigrationError, match="does not fit"):
        c.adopt_pages(1, off, k, v, ks, vs)


@pytest.fixture(scope="module")
def model64():
    """Two kv heads of 64: a pool of theirs lives lane-dense."""
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=128, num_heads=2,
        vocab_size=512, max_seq_len=128))
    m.eval()
    return m


@pytest.mark.parametrize("lane", ["xla", "kernel"])
def test_tick_program_reshapes_no_pool(model64, lane, monkeypatch):
    """The traced tick program of a head-size-64 engine holds no
    ``reshape`` / ``transpose`` of an array as large as a page pool, on
    the XLA lane and with the decode kernel in it: the pools go through
    the program in the shape they live in."""
    import re
    if lane == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    (p,) = _prompts([9], seed=6)
    cfg = ServingConfig(num_slots=2, max_seq_len=64, page_size=8,
                        prefill_chunk_tokens=8)
    with Engine(model64, cfg) as eng:
        eng.submit(p, max_new_tokens=3).result(timeout=300)
        text = eng._tick.lowered_text("greedy")
        pools = [lay["k_pool"] for lay in eng.cache.layers]
        snap = eng.stats()
    assert text is not None
    assert snap["kv_pools"] == snap["kv_pools_lane_dense"] == 4
    assert snap["paged_decode_kernel_traces" if lane == "kernel"
                else "paged_decode_xla_lane_traces"] > 0
    assert all(tuple(t.shape)[1:] == (8, 128) for t in pools)
    count = int(np.prod(pools[0].shape))
    assert text.count(f"tensor<{'x'.join(map(str, pools[0].shape))}x") > 0
    moved = []
    for line in text.splitlines():
        if not re.search(r"stablehlo\.(reshape|transpose)\b", line):
            continue
        for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", line):
            if int(np.prod([int(n) for n in dims[:-1].split("x")])) \
                    == count:
                moved.append(line.strip()[:160])
    assert not moved, moved[:4]


@pytest.mark.parametrize("speculation", [0, 2], ids=["tick", "spec-k2"])
def test_lane_dense_engine_equals_the_eager_lane(model64, speculation):
    """Greedy tokens of a head-size-64 engine — lane-dense pools, prefix
    cache on, through the compiled tick and with speculation (whose
    draft cache stores the same way) — equal the eager reference lane's
    and sequential ``generate()``'s."""
    import warnings
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    from paddle_tpu.utils import flags as _flags
    paddle.seed(1)
    draft = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=1, hidden_size=128, num_heads=2,
        vocab_size=512, max_seq_len=128))
    draft.eval()
    shared = _prompts([24], seed=7)[0]
    prompts = [np.concatenate([shared, t])
               for t in _prompts([5, 9, 3], seed=8)]
    news = [12, 7, 10]
    spec = {"draft_model": draft, "speculation_k": speculation} \
        if speculation else {}

    def serve(compiled):
        saved = _flags._FLAGS["FLAGS_compiled_tick"]
        _flags._FLAGS["FLAGS_compiled_tick"] = compiled
        try:
            cfg = ServingConfig(num_slots=2, max_seq_len=64, page_size=8,
                                prefill_chunk_tokens=16,
                                enable_prefix_cache=True, **spec)
            with warnings.catch_warnings():
                # speculation latches the uncompiled iteration, loudly
                warnings.simplefilter("ignore")
                with Engine(model64, cfg) as eng:
                    assert eng.cache.pools_lane_dense == 4
                    if speculation:
                        assert eng.draft_cache.pools_lane_dense == 2
                    outs = [eng.submit(p, max_new_tokens=n)
                            .result(timeout=300)
                            for p, n in zip(prompts, news)]
                    snap = eng.stats()
            return [o.output_ids for o in outs], snap
        finally:
            _flags._FLAGS["FLAGS_compiled_tick"] = saved

    got, snap = serve(True)
    want, _ = serve(False)
    assert snap["prefix_cache_hits"] > 0
    if speculation:
        assert snap["spec_windows"] > 0
    else:
        assert snap["tick_compiled_hits"] > 0
        assert snap["tick_fallbacks"] == 0
    for g, w, p, n in zip(got, want, prompts, news):
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(g, _ref_greedy(model64, p, n))


def test_paged_metrics_reach_prometheus(model):
    """Satellite: the new serving gauges/counters/histogram flow
    through the PR 4 registry into Prometheus exposition."""
    from paddle_tpu import observability as obs
    (p,) = _prompts([40], seed=4)
    with Engine(model, ServingConfig(num_slots=1,
                                     prefill_chunk_tokens=8)) as eng:
        eng.submit(p, max_new_tokens=4).result(timeout=300)
        snap = eng.stats()
    assert snap["kv_pages_in_use"] >= 0
    assert snap["prefill_chunks"] >= 5
    text = obs.render_prometheus()
    for series in ("serving_kv_pages_in_use", "serving_kv_pages_free",
                   "serving_prefix_cache_misses",
                   "serving_prefill_chunk_ms"):
        assert series in text, f"{series} missing from exposition"


# ---------------- the latent store: a third kind of per-layer pages --------
def _latent_cache(**kw):
    base = dict(num_layers=3, num_slots=3, max_len=64, page_size=4,
                layer_latents=[40, 40, 40])
    base.update(kw)
    return PagedKVCache(**base)


def test_latent_kind_owns_its_page_shape():
    """A latent layer is ONE pool of rows padded to whole lane tiles
    behind the same table; the K/V kind's shapes are not built at all
    for a model without K/V layers, and both kinds live side by side."""
    cache = _latent_cache()
    assert cache.latent_pools == 3 and cache.pools == 0
    assert cache.pools_lane_dense == 0
    assert cache.page_shape is None and cache.stored_page_shape is None
    assert cache.latent_page_shape == (4, 128)
    assert cache.latent_width == 40 and cache.latent_row_bytes == 160
    for lay in cache.layers:
        assert set(lay) >= {"latent_pool", "page_table", "offset",
                            "page_size", "latent_width"}
        assert "k_pool" not in lay
        assert tuple(lay["latent_pool"].shape) == (3 * 16 + 1, 4, 128)
    assert len(cache.flat_pools()) == 3
    # a row of 576 lives in 640 lanes
    wide = _latent_cache(num_layers=1, layer_latents=[576], page_size=16,
                         dtype="bfloat16")
    assert wide.latent_page_shape == (16, 640)
    assert wide.latent_row_bytes == 1152
    # K/V layers and latent layers in one cache, one table
    mixed = PagedKVCache(2, 2, 32, 2, 16, page_size=4,
                         layer_latents=[None, 40])
    assert mixed.pools == 2 and mixed.latent_pools == 1
    assert mixed.page_shape == (4, 2, 16)
    assert [sorted(k for k in lay if k.endswith("pool"))
            for lay in mixed.layers] == [["k_pool", "v_pool"],
                                         ["latent_pool"]]
    assert mixed.layers[0]["page_table"] is mixed.layers[1]["page_table"]
    with pytest.raises(ValueError, match="num_kv_heads"):
        PagedKVCache(2, 2, 32, page_size=4, layer_latents=[None, 40])
    with pytest.raises(ValueError, match="one store holds one"):
        _latent_cache(layer_latents=[40, 48, 40])
    with pytest.raises(ValueError, match="no window"):
        _latent_cache(layer_windows=[8, None, None])


def test_latent_store_allocate_grow_release_and_reuse():
    """The slot lifecycle is the page table's: a latent page is a page."""
    cache = _latent_cache()
    free0 = cache.free_page_count
    a = cache.allocate(16)
    b = cache.allocate(10)
    assert cache.available_pages == free0 - 26
    for pos in range(37):
        cache.ensure_capacity(a, pos)
    assert cache.pages_in_use == 10
    assert (cache.table[a, :10] > 0).all() and not cache.table[a, 10:].any()
    cache.ensure_capacity(b, 5)
    assert not set(cache.table[a, :10]) & set(cache.table[b, :2])
    # views carry every latent layer behind the one table
    views = cache.views_over(cache.flat_pools(), *cache.table_arrays())
    assert all("latent_pool" in v and v["latent_width"] == 40
               for v in views)
    assert views[0]["page_table"] is views[2]["page_table"]
    cache.absorb_pools(cache.flat_pools(views))
    held = list(cache.table[a, :10])
    cache.release(a)
    assert cache.pages_in_use == 2 and not cache.table[a].any()
    # the next tenant of the slot gets the pages back, from offset 0
    c = cache.allocate(16)
    assert c == a and cache.offsets[c] == 0
    for pos in range(37):
        cache.ensure_capacity(c, pos)
    assert set(cache.table[c, :10]) == set(held)
    cache.release(b)
    cache.release(c)
    assert cache.free_page_count == free0
    assert cache.allocate(free0 + 1) is None


def test_latent_store_keeps_rollback_and_sharing_and_refuses_export():
    """What moves pages through the table alone works unchanged; what
    carries K and V by name refuses the store kind by name."""
    from paddle_tpu.serving import LatentStoreError, PageMigrationError
    cache = _latent_cache()
    slot = cache.allocate(16)
    for pos in range(30):
        cache.ensure_capacity(slot, pos)
    avail = cache.available_pages
    cache.rollback(slot, 9)                 # pages 3.. go back
    assert cache.pages_in_use == 3 and cache.available_pages == avail
    page = cache.make_shared(slot, 0)       # the prefix tree's transfer
    assert page == cache.table[slot, 0]
    cache.release(slot)
    assert cache.pages_in_use == 1          # the shared page is the tree's
    cache.reclaim(page)
    assert cache.pages_in_use == 0
    slot = cache.allocate(4)
    with pytest.raises(LatentStoreError, match="latent page store"):
        cache.export_pages(slot)
    with pytest.raises(LatentStoreError, match="adopt_pages"):
        cache.adopt_pages(1, 4, np.zeros((3, 1, 4, 2, 16), np.float32),
                          np.zeros((3, 1, 4, 2, 16), np.float32))
    with pytest.raises(LatentStoreError, match="cache_dtype"):
        _latent_cache(dtype="int8")
    # the K/V store's own geometry check names the kind it refused
    plain = PagedKVCache(2, 2, 32, 2, 16, page_size=4)
    with pytest.raises(PageMigrationError, match="K/V page pool"):
        plain.adopt_pages(1, 4, np.zeros((2, 1, 4, 2, 8), np.float32),
                          np.zeros((2, 1, 4, 2, 8), np.float32))


def test_latent_op_writes_rows_through_the_table():
    """The op's write lands each new row at (page of the table, row of
    the page), padded lanes zero, other pages untouched."""
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn import functional as IF
    cache = _latent_cache(num_layers=1, layer_latents=[40], num_slots=2)
    for n in (9, 3):
        slot = cache.allocate(16)
        cache.ensure_capacity(slot, n)
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((2, 6, 40)).astype("float32")
    q = rng.standard_normal((2, 6, 4, 24)).astype("float32")
    w = rng.standard_normal((32, 4 * 32)).astype("float32")
    cache.set_offset(0, 3)
    view = cache.layer_caches()[0]
    with paddle.no_grad():
        out = IF.paged_latent_attention(
            paddle.to_tensor(q), paddle.to_tensor(rows),
            paddle.to_tensor(w), view, nope_dim=16, scale=0.2)
    assert tuple(out.shape) == (2, 6, 4, 16)
    pool = np.asarray(view["latent_pool"]._data_)
    for slot, start in ((0, 3), (1, 0)):
        for j in range(6):
            pos = start + j
            got = pool[cache.table[slot, pos // 4], pos % 4]
            np.testing.assert_array_equal(got[:40], rows[slot, j])
            assert not got[40:].any()
    touched = {int(cache.table[s, p]) for s, n in ((0, 9), (1, 6))
               for p in range(-(-n // 4))}
    for page in range(1, pool.shape[0]):
        if page not in touched:
            assert not pool[page].any()


def test_read_latent_gives_a_slots_rows_in_position_order():
    """``read_latent`` hands back what the op wrote for one slot, row by
    position, the padded lanes gone, as far as the slot's offset."""
    from paddle_tpu.incubate.nn import functional as IF
    cache = _latent_cache(num_layers=2, layer_latents=[40, 40], num_slots=2)
    for n in (9, 6):
        cache.ensure_capacity(cache.allocate(16), n)
    rng = np.random.default_rng(1)
    rows = rng.standard_normal((2, 2, 6, 40)).astype("float32")
    q = rng.standard_normal((2, 6, 4, 24)).astype("float32")
    w = rng.standard_normal((32, 4 * 32)).astype("float32")
    views = cache.layer_caches()
    with paddle.no_grad():
        for view, layer_rows in zip(views, rows):
            IF.paged_latent_attention(
                paddle.to_tensor(q), paddle.to_tensor(layer_rows),
                paddle.to_tensor(w), view, nope_dim=16, scale=0.2)
    cache.absorb_view(views)
    cache.set_offset(0, 6)
    cache.set_offset(1, 5)
    for slot, n in ((0, 6), (1, 5)):
        got = cache.read_latent(slot)
        assert sorted(got) == [0, 1]
        for layer in (0, 1):
            np.testing.assert_array_equal(got[layer], rows[layer, slot, :n])
