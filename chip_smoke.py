"""The quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width and depth of GPT-2 124M (random weights from a
seed), and checks what comes out by the repo's own means:

1. train  — ``CompiledTrainStep`` as a training script builds it: seq 1024,
   batch 8, bf16 O2, flash attention, AdamW; warm-up at batch 1, then
   compiled full-batch steps on a fixed batch.
2. serve  — ``serving.Engine(model, ServingConfig()).start()`` with the
   defaults (paged KV, compiled tick): 8 greedy requests submitted
   together so slots refill mid-flight, one checked against
   ``model.generate()`` on the same chip; then the Pallas decode kernel
   against the XLA gather read at the tick's shapes, f32 and int8 pages.
3. sparse — a small ``CohereMoeForCausalLM`` (window and full attention
   layers with page tables of their own, routed and shared experts of
   which 4 of 16 are held) through the same engine, at shapes the
   kernels host: which lane the windowed paged read and the grouped
   expert product took is printed, and on a TPU has to be the kernel's.
4. latent — a small ``SarvamMLAForCausalLM`` (latent attention over a
   latent page store, a dense layer then sparse experts) through the
   same engine, at rows the latent decode kernel hosts: on a TPU the
   single-token read has to be the kernel's, never the XLA lane's.
5. latent experts — a small ``NemotronHForCausalLM`` (Mamba-2, attention
   and latent relu² expert blocks, each a mixer or a feed-forward part
   alone) through the same engine: its expert layers hold no page and no
   state row, its SSM state is stored lane-packed two heads a row of
   lanes, and on a TPU the relu² up products have to run on the
   ``expert_gmm_relu2`` kernel and the state update on ``ssm_update``,
   never an XLA lane.
6. hybrid — with >= 4 devices: ``ParallelGPTForCausalLM`` at the same
   widths under ``fleet.init`` (mp 2, dp the rest) through the same
   ``CompiledTrainStep``, losses against phase 1.

It passes only on a TPU: platform, compiled lanes, zero fallbacks and
Pallas custom calls in both programs are asserted, and no failure of a
phase is caught.  The last line of stdout is one JSON object.  Wall
times printed on the way are information, not a measurement.

    python chip_smoke.py              # on the chip (through the chip tool)
    JAX_PLATFORMS=cpu python chip_smoke.py --dry-run
        # the same code at a tiny size on the CPU; changes the sizes and
        # the platform assert, nothing else
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time
import warnings

import numpy as np

SEED = 0
# Losses of the dp2×mp2 run against the one-chip run of the same seed and
# batches: the two differ in the order of bf16-rounded partial sums (the
# row-parallel all-reduce, the vocab-parallel softmax), and bf16 keeps 8
# bits — one rounding is 2**-8 relative; allow a few roundings deep.
HYBRID_LOSS_RTOL = 2e-2
# A served token may differ from model.generate()'s only on a near-tie:
# the engine's decode softmax is the Pallas online one over f32 pages
# after a chunked prefill, generate()'s the XLA one over a dense cache
# after a one-shot prefill, both under bf16 matmuls.  The reference's own
# logits must then rank the two tokens within two bf16 epsilons (2**-7
# each) of the logit range.  (PR 21, v5e: one divergence at token 28 of
# 64, gap 0.0012 of a range of 4.7 — 0.03%.)
SERVE_LOGIT_GAP_RTOL = 2.0 ** -6
# The decode kernel against the XLA gather read of the same pages, both
# in f32 with the reference's matmuls at "highest": only the order of
# the sums differs, over outputs of magnitude ~1.
PAGED_KERNEL_ATOL = 1e-4


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


class Sizes:
    def __init__(self, dry_run):
        # the dry run cuts every size; head_dim stays 64 and seq a
        # multiple of 128 so the same kernels are eligible
        self.model = dict(num_layers=2, hidden_size=128, num_heads=2,
                          vocab_size=512, max_seq_len=256) if dry_run \
            else dict(max_seq_len=1024)
        self.seq = self.model["max_seq_len"]
        self.batch = 4 if dry_run else 8
        self.steps = 6
        self.amp = not dry_run          # bf16 O2 on a TPU
        self.prompt_lens = [8, 21, 33, 40, 64, 90, 100, 12] if dry_run \
            else [32, 57, 100, 128, 200, 333, 512, 64]
        self.new_tokens = [8, 12, 16, 9, 10, 16, 8, 12] if dry_run \
            else [32, 48, 64, 40, 33, 64, 32, 50]


def device_phase(dry_run):
    import jax
    import jaxlib
    from paddle_tpu.core.op_cache import ensure_compile_cache

    dev = jax.devices()[0]
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"devices={jax.device_count()} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile cache: {ensure_compile_cache()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") and not dry_run:
        raise SystemExit(
            "PADDLE_TPU_PALLAS_INTERPRET is set: the Pallas kernels would "
            "run in the interpreter, which proves nothing about the chip")
    if dev.platform != "tpu" and not dry_run:
        raise SystemExit(
            f"chip_smoke.py needs a TPU and found platform "
            f"{dev.platform!r}; --dry-run runs the same code on the CPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def no_fallback_warnings(caught, phase):
    from paddle_tpu.framework.train_step import MeshFallbackWarning
    from paddle_tpu.serving.compiled_tick import TickFallbackWarning
    bad = [w for w in caught
           if issubclass(w.category, (MeshFallbackWarning,
                                      TickFallbackWarning))
           or "disabled" in str(w.message)]
    assert not bad, f"{phase}: fallback warning(s): " + \
        "; ".join(f"{w.category.__name__}: {w.message}" for w in bad)


def pallas_calls(text, what, dry_run):
    n = text.count("tpu_custom_call")
    say(f"{what}: {n} Pallas custom call(s) in the lowered program")
    if not dry_run:
        assert n > 0, f"{what} holds no Pallas (tpu_custom_call) kernel"
    return n


def batch_data(sizes, vocab):
    rng = np.random.default_rng(SEED)
    return rng.integers(0, vocab, (sizes.batch, sizes.seq + 1),
                        dtype=np.int32)


def train_model(model, cfg, sizes, warm_rows, dry_run, what):
    """``sizes.steps`` compiled full-batch steps on the fixed batch after
    a warm-up on its ``warm_rows``; returns (step, losses, lowered
    program text)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.train_step import CompiledTrainStep
    from paddle_tpu.utils import monitor

    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01)
    data = batch_data(sizes, cfg.vocab_size)
    x = paddle.to_tensor(data[:, :-1])
    y = paddle.to_tensor(data[:, 1:])
    xw = paddle.to_tensor(data[warm_rows, :-1])
    yw = paddle.to_tensor(data[warm_rows, 1:])

    def forward(x, y):
        with paddle.amp.auto_cast(enable=sizes.amp, level="O2",
                                  dtype="bfloat16"):
            _, loss = model(x, labels=y)
        return loss

    def eager_step(x, y, update=True):
        loss = forward(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    fallbacks0 = monitor.all_stats().get("jit.compiled_step_fallback", 0)
    cstep = CompiledTrainStep(forward, opt, network=model,
                              eager_step=eager_step)
    t0 = time.perf_counter()
    # warm-up: the eager + discovery call and the first compiled call
    # on a small batch, then the full batch re-traces
    for _ in range(2):
        loss = cstep(xw, yw, update=True)
    jax.block_until_ready(loss._data_)
    say(f"{what}: warm-up (eager step, discovery, first compile) "
        f"{time.perf_counter() - t0:.1f}s")
    losses, walls = [], []
    for _ in range(sizes.steps):
        t0 = time.perf_counter()
        loss = cstep(x, y, update=True)
        losses.append(float(loss))      # the value read ends the step
        walls.append(time.perf_counter() - t0)
    say(f"{what}: losses {[round(v, 4) for v in losses]}")
    say(f"{what}: step wall s {[round(w, 3) for w in walls]} "
        "(first includes the full-batch compile)")

    assert cstep.compiled, f"{what}: step not compiled"
    assert cstep.fallback_reason is None, cstep.fallback_reason
    fallbacks = monitor.all_stats().get("jit.compiled_step_fallback", 0)
    assert fallbacks == fallbacks0, \
        f"{what}: jit.compiled_step_fallback rose by {fallbacks - fallbacks0}"
    assert np.all(np.isfinite(losses)), f"{what}: non-finite loss {losses}"
    assert losses[-1] < losses[0], \
        f"{what}: loss did not fall on the fixed batch: {losses}"
    text = cstep.lowered_text(x, y)
    pallas_calls(text, f"{what} step", dry_run)
    return cstep, losses, text


def train_phase(sizes, dry_run):
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_config

    import paddle_tpu as paddle

    from paddle_tpu.utils import monitor

    def kernel_traces():
        stats = monitor.all_stats()
        return {name: stats.get(name, 0) for name in
                ("pallas.flash.resident", "pallas.flash.streamed",
                 "pallas.adamw_update.in_place",
                 "pallas.adamw_update.xla_lane")}

    cfg = gpt_config("gpt2-124m", **sizes.model)
    before = kernel_traces()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        paddle.seed(SEED)
        with paddle.amp.auto_cast(enable=sizes.amp, level="O2",
                                  dtype="bfloat16"):
            model = GPTForCausalLM(cfg)
        _, losses, _ = train_model(model, cfg, sizes, [0], dry_run,
                                   "train")
    no_fallback_warnings(caught, "train")
    # every flash kernel of the step walks a resident head (a rule on
    # the call's shapes: flash_attention._walk_vmem_bytes); none streams;
    # every matrix is updated in place by the AdamW kernel
    # (fused.adam_update_blocks), the vectors by the jnp lane
    paths = {k: v - before[k] for k, v in kernel_traces().items()}
    say(f"train: kernel traces by path {paths}")
    if not dry_run or os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        assert paths["pallas.flash.resident"] > 0, paths
        assert paths["pallas.flash.streamed"] == 0, paths
        assert paths["pallas.adamw_update.in_place"] > 0, paths
    return model, cfg, losses


def pool_sized_copies(hlo_text, element_counts):
    """The ``copy`` instructions of an optimized HLO text whose operand
    has as many elements as a page pool."""
    found = []
    for line in hlo_text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* copy\(", line)
        if m and m.group(1) and int(np.prod(
                [int(n) for n in m.group(1).split(",")])) in element_counts:
            found.append(line.strip()[:160])
    return found


def serve_phase(model, cfg, sizes, dry_run):
    import paddle_tpu as paddle
    from paddle_tpu.serving import Engine, ServingConfig
    from paddle_tpu.serving.stats import serving_stats

    model.eval()
    rng = np.random.default_rng(SEED + 1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in sizes.prompt_lens]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        eng = Engine(model, ServingConfig()).start()
        try:
            futs = [eng.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, sizes.new_tokens)]
            outs = [f.result(timeout=900) for f in futs]
            say(f"serve: {len(outs)} requests, "
                f"{sum(sizes.new_tokens)} tokens in "
                f"{time.perf_counter() - t0:.1f}s (compiles included)")
            tick_text = eng._tick.lowered_text("greedy")
            tick_hlo = eng._tick.lowered_text("greedy", optimized=True)
            pool_elements = {int(np.prod(lay["k_pool"].shape))
                             for lay in eng.cache.layers}
        finally:
            eng.shutdown()
    no_fallback_warnings(caught, "serve")

    for out, n in zip(outs, sizes.new_tokens):
        assert out.finish_reason == "length" and len(out.output_ids) == n, \
            (out.request_id, out.finish_reason, len(out.output_ids), n)
        assert ((0 <= out.output_ids) &
                (out.output_ids < cfg.vocab_size)).all()
    snap = serving_stats()
    say(f"serve: tick_compiled_hits={snap['tick_compiled_hits']} "
        f"tick_fallbacks={snap['tick_fallbacks']} "
        f"prefill_compiled_hits={snap['prefill_compiled_hits']} "
        f"prefill_fallbacks={snap['prefill_fallbacks']} "
        f"scheduler_restarts={snap['scheduler_restarts']} "
        f"max_active_slots={snap.get('max_active_slots')} "
        f"tick_overlap_share={snap['tick_overlap_share']:.3f} "
        f"prefill_overlap_share={snap['prefill_overlap_share']:.3f} "
        f"tick_drains={snap['tick_drains']}")
    assert snap["tick_compiled_hits"] > 0
    assert snap["tick_fallbacks"] == 0
    # ticks launched over an unread one: their inputs were donated
    # futures, which only a device with real donation puts to the test
    assert snap["tick_overlap_share"] > 0
    assert snap["prefill_compiled_hits"] > 0
    assert snap["prefill_fallbacks"] == 0
    # members read after the next tick's launch: their outputs were
    # futures under a running tick
    assert snap["prefill_overlap_share"] > 0
    assert snap["scheduler_restarts"] == 0
    assert tick_text is not None, "no greedy tick program ran"
    pallas_calls(tick_text, "serve tick", dry_run)
    # GPT-2's pools (12 kv heads of 64) live as the paged kernel reads
    # them, so the tick program relays none of them
    say(f"serve: kv_pools_lane_dense={snap['kv_pools_lane_dense']} of "
        f"kv_pools={snap['kv_pools']}")
    assert snap["kv_pools_lane_dense"] == snap["kv_pools"] > 0
    pool_copies = pool_sized_copies(tick_hlo, pool_elements)
    assert not pool_copies, \
        f"the tick program copies a whole page pool: {pool_copies[:4]}"

    # one request against model.generate() on the same device
    k = 2
    ref = model.generate(paddle.to_tensor(prompts[k][None]),
                         max_new_tokens=sizes.new_tokens[k])
    ref = np.asarray(ref.numpy())[0, prompts[k].size:]
    got = outs[k].output_ids
    diff = np.nonzero(ref != got)[0]
    if diff.size == 0:
        say(f"serve: request {k} equals model.generate() on all "
            f"{got.size} tokens")
        return
    # tokens after a divergence condition on different text; judge the
    # first one by the reference model's own logits at that position
    pos = int(diff[0])
    ctx = np.concatenate([prompts[k], got[:pos]])[None]
    with paddle.no_grad():
        logits = np.asarray(
            model(paddle.to_tensor(ctx)).numpy()[0, -1], np.float32)
    gap = float(logits[ref[pos]] - logits[got[pos]])
    span = float(logits.max() - logits.min())
    say(f"serve: request {k} diverges from model.generate() at token "
        f"{pos}: engine {got[pos]} vs reference {ref[pos]}, reference "
        f"logit gap {gap:.5f} of range {span:.3f}")
    assert abs(gap) <= SERVE_LOGIT_GAP_RTOL * span, \
        "the engine's token is not a near-tie under the reference logits"


def paged_kernel_phase(cfg, sizes):
    """The Pallas decode kernel against the XLA gather read on the same
    device, at the tick's shapes, over f32 and int8 pages."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.functional import _cache_attend
    from paddle_tpu.pallas import flash_attention as fa
    from paddle_tpu.quantization import dequantize_kv

    if not fa._unsharded_kernels_on():
        say("paged kernel check not run: no TPU and no Pallas interpreter")
        return
    rng = np.random.default_rng(SEED + 2)
    b, h, d = 4, cfg.num_heads, cfg.head_dim
    for what, psz, quant in (("f32", 16, False), ("int8", 32, True)):
        n = sizes.seq // psz
        pool = (1 + b * n, psz, h, d)
        pt = jnp.asarray(rng.permutation(np.arange(1, pool[0]))
                         .reshape(b, n).astype(np.int32))
        off = jnp.asarray(rng.integers(1, sizes.seq, (b,)).astype(np.int32))
        q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
        if quant:
            kp, vp = (jnp.asarray(rng.integers(-127, 128, pool)
                                  .astype(np.int8)) for _ in range(2))
            ks, vs = (jnp.asarray(rng.uniform(0.005, 0.03, pool[:2])
                                  .astype(np.float32)) for _ in range(2))
            kf, vf = dequantize_kv(kp[pt], ks[pt]), \
                dequantize_kv(vp[pt], vs[pt])
        else:
            kp, vp = (jnp.asarray(rng.standard_normal(pool)
                                  .astype(np.float32)) for _ in range(2))
            ks = vs = None
            kf, vf = kp[pt], vp[pt]
        out = fa.paged_decode_attention(q, kp, vp, pt, off,
                                        k_scale=ks, v_scale=vs)
        # the same bytes as the cache stores pools of this head size
        dense = (pool[0], *fa.paged_pool_page_shape(
            psz, h, d, kp.dtype.itemsize))
        if dense != pool:
            same = fa.paged_decode_attention(
                q, kp.reshape(dense), vp.reshape(dense), pt, off,
                k_scale=ks, v_scale=vs, h_kv=h)
            assert bool(jnp.all(same == out)), \
                f"{what} pages: a lane-dense pool reads differently"
        # the reference's f32 einsums would run at bf16 matmul precision
        # on a TPU by default; at "highest" only the order of the f32
        # sums differs from the kernel's
        with jax.default_matmul_precision("highest"):
            ref = _cache_attend(q[:, None], kf.reshape(b, n * psz, h, d),
                                vf.reshape(b, n * psz, h, d), off,
                                None)[:, 0]
        err = float(jnp.max(jnp.abs(out - ref)))
        say(f"paged kernel vs XLA gather read, {what} pages: "
            f"max abs difference {err:.2e} (tolerance {PAGED_KERNEL_ATOL})")
        assert err <= PAGED_KERNEL_ATOL


def small_family_run(model, what, seed, lane_names):
    """Four requests of a small model of another family through the
    compiled tick and the prefill member at shapes its kernels host:
    (``serving_stats()``, the phase's trace counts of ``lane_names``),
    the compiled lanes and zero fallbacks asserted."""
    from paddle_tpu.serving import Engine, ServingConfig
    from paddle_tpu.serving.stats import serving_stats
    from paddle_tpu.utils import monitor

    model.eval()
    rng = np.random.default_rng(seed)
    lens, new = [100, 150, 40, 90], [40, 24, 48, 32]
    prompts = [rng.integers(0, model.config.vocab_size, (n,))
               .astype(np.int32) for n in lens]
    before = monitor.all_stats()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng = Engine(model, ServingConfig(
            num_slots=4, page_size=16, prefill_chunk_tokens=32,
            enable_prefix_cache=False)).start()
        try:
            futs = [eng.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, new)]
            outs = [f.result(timeout=900) for f in futs]
        finally:
            eng.shutdown()
    no_fallback_warnings(caught, what)
    assert [len(o.output_ids) for o in outs] == new
    snap, after = serving_stats(), monitor.all_stats()
    lanes = {name: after.get(name, 0) - before.get(name, 0)
             for name in lane_names}
    say(f"{what}: tick_compiled_hits={snap['tick_compiled_hits']} "
        f"tick_fallbacks={snap['tick_fallbacks']} "
        f"prefill_fallbacks={snap['prefill_fallbacks']} "
        f"tick_overlap_share={snap['tick_overlap_share']:.3f} "
        f"expert_pairs_per_token={snap['expert_pairs_per_token']:.3f}")
    say(f"{what}: traces in this phase " + " ".join(
        f"{k}={v}" for k, v in lanes.items()))
    assert snap["tick_compiled_hits"] > 0 and snap["tick_fallbacks"] == 0
    assert snap["prefill_compiled_hits"] > 0
    assert snap["prefill_fallbacks"] == 0
    assert snap["tick_overlap_share"] > 0
    return snap, lanes


def sparse_phase(dry_run):
    """The sparse-expert family through the compiled tick and the prefill
    member; a lane that silently fell to XLA on the chip shows here."""
    import paddle_tpu as paddle
    from paddle_tpu.models.cohere_moe import (CohereMoeConfig,
                                              CohereMoeForCausalLM)

    paddle.seed(SEED)
    # 8 kv heads of 128 in pages of 16, hidden and expert width 256: the
    # paged kernel and expert_gmm both host these
    cfg = CohereMoeConfig(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=16,
        num_kv_heads=8, head_dim=128, sliding_window=64,
        intermediate_size=256, num_experts_published=16,
        num_experts_per_tok=4, num_shared_experts=2, held_experts=(4, 4),
        max_seq_len=256, initializer_range=0.05)
    snap, lanes = small_family_run(
        CohereMoeForCausalLM(cfg), "sparse", SEED + 2,
        ("pallas.paged_decode.kernel", "pallas.paged_decode.xla_lane",
         "pallas.expert_gmm.kernel", "pallas.expert_gmm.xla_lane"))
    say(f"sparse: window_pages_held_share="
        f"{snap['window_pages_held_share']:.3f} "
        f"paged_decode_kernel_traces={snap['paged_decode_kernel_traces']} "
        f"paged_decode_xla_lane_traces="
        f"{snap['paged_decode_xla_lane_traces']}")
    assert 0 < snap["window_pages_held_share"] < 1
    say(f"sparse: prefill_overlap_share="
        f"{snap['prefill_overlap_share']:.3f}")
    assert snap["prefill_overlap_share"] > 0
    if not dry_run:
        assert lanes["pallas.paged_decode.kernel"] > 0, lanes
        assert lanes["pallas.expert_gmm.kernel"] > 0, lanes
        assert lanes["pallas.expert_gmm.xla_lane"] == 0, lanes


def latent_phase(dry_run):
    """The latent-attention family through the compiled tick and the
    prefill member: rows in a latent page store, the decode read in the
    absorbed form.  A read that silently fell to the XLA lane on the chip
    shows here."""
    import paddle_tpu as paddle
    from paddle_tpu.models.sarvam_mla import (SarvamMLAConfig,
                                              SarvamMLAForCausalLM)

    paddle.seed(SEED)
    # rows of 128 + 64 values in 256 lanes, pages of 16: the latent
    # decode kernel hosts these in bfloat16 and float32 alike
    cfg = SarvamMLAConfig(
        vocab_size=512, hidden_size=256, num_layers=3, num_heads=8,
        kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=64, intermediate_size=512, moe_intermediate_size=256,
        num_experts_published=16, num_experts_per_tok=4,
        num_shared_experts=1, held_experts=(4, 4), max_seq_len=256,
        initializer_range=0.05)
    snap, lanes = small_family_run(
        SarvamMLAForCausalLM(cfg), "latent", SEED + 3,
        ("pallas.mla_decode.kernel", "pallas.mla_decode.xla_lane",
         "pallas.expert_gmm.kernel", "pallas.expert_gmm.xla_lane"))
    say(f"latent: kv_latent_pools={snap['kv_latent_pools']} "
        f"kv_latent_row_bytes={snap['kv_latent_row_bytes']} "
        f"mla_decode_pages_per_step={snap['mla_decode_pages_per_step']}")
    assert snap["kv_latent_pools"] == cfg.num_layers
    assert snap["kv_pools"] == 0
    if not dry_run:
        assert lanes["pallas.mla_decode.kernel"] > 0, lanes
        assert lanes["pallas.mla_decode.xla_lane"] == 0, lanes
        assert snap["mla_decode_pages_per_step"] > 0, snap


def latent_moe_phase(dry_run):
    """The per-layer stack of Mamba-2, attention and LATENT expert blocks
    through the compiled tick and the prefill member: its expert layers
    hold nothing, its SSM state is lane-packed (heads of 64: two a row of
    lanes), and the relu² up products and the state update have to run
    on their kernels on the chip, never an XLA lane."""
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    paddle.seed(SEED)
    # latent 128, expert width 256: expert_gmm hosts both products
    cfg = NemotronHConfig(
        vocab_size=512, hidden_size=256, num_layers=5,
        layer_types=["moe", "mamba", "moe", "mamba", "attention"],
        num_heads=2, num_kv_heads=1, head_dim=128, max_seq_len=256,
        mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=2, mamba_chunk_size=32, moe_intermediate_size=256,
        moe_latent_size=128, moe_shared_expert_intermediate_size=512,
        num_experts_published=16, num_experts_per_tok=6,
        held_experts=(4, 4), initializer_range=0.05)
    snap, lanes = small_family_run(
        NemotronHForCausalLM(cfg), "latent_moe", SEED + 4,
        ("pallas.expert_gmm.relu2.kernel", "pallas.expert_gmm.relu2.xla_lane",
         "pallas.expert_gmm.kernel", "pallas.expert_gmm.xla_lane",
         "pallas.ssm_update.kernel", "pallas.ssm_update.xla_lane"))
    say(f"latent_moe: kv_layers_without_store="
        f"{snap['kv_layers_without_store']} kv_pools={snap['kv_pools']} "
        f"state_bytes={snap['state_bytes']} "
        f"state_heads_per_tile={snap['state_heads_per_tile']}")
    assert snap["kv_layers_without_store"] == 2
    assert snap["kv_pools"] == 2 and snap["state_bytes"] > 0
    assert snap["state_heads_per_tile"] == 2
    if not dry_run:
        assert lanes["pallas.expert_gmm.relu2.kernel"] > 0, lanes
        assert lanes["pallas.expert_gmm.relu2.xla_lane"] == 0, lanes
        assert lanes["pallas.expert_gmm.xla_lane"] == 0, lanes
        assert lanes["pallas.ssm_update.kernel"] > 0, lanes
        assert lanes["pallas.ssm_update.xla_lane"] == 0, lanes


def hybrid_phase(cfg, sizes, one_chip_losses, dry_run):
    import jax
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import ParallelGPTForCausalLM

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": -1, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = dist.get_mesh()
    dp, mp = mesh.get_dim_size("dp"), mesh.get_dim_size("mp")
    say(f"hybrid: mesh dp{dp} x mp{mp} over {mesh.jax_mesh.size} devices")
    assert mp == 2 and dp * mp == mesh.jax_mesh.size >= 4

    import paddle_tpu as paddle
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        paddle.seed(SEED)
        with paddle.amp.auto_cast(enable=sizes.amp, level="O2",
                                  dtype="bfloat16"):
            model = fleet.distributed_model(ParallelGPTForCausalLM(cfg))
        # the one-chip warm-up row, repeated to split over dp: the same
        # mean loss and gradient, so the trajectories stay comparable
        cstep, losses, text = train_model(model, cfg, sizes, [0] * dp,
                                          dry_run, "hybrid")
    no_fallback_warnings(caught, "hybrid")
    assert cstep._gspmd and cstep._dp == dp and cstep._mp == mp

    specs = {str(p._data_.sharding.spec) for p in model.parameters()}
    assert any("mp" in s for s in specs), f"no mp-sharded parameter: {specs}"
    held = set().union(*(p._data_.sharding.device_set
                         for p in model.parameters()))
    assert held == set(mesh.jax_mesh.devices.flat), \
        f"parameters live on {len(held)} of {mesh.jax_mesh.size} devices"
    batch_arg = (f"%arg0: tensor<{sizes.batch}x{sizes.seq}xi32> "
                 '{sdy.sharding = #sdy.sharding<@mesh, [{"dp"}, {}]>}')
    assert batch_arg in text, "the program's batch is not split over dp"
    in_use = {}
    for d in mesh.jax_mesh.devices.flat:
        stats = d.memory_stats()
        if stats is None:               # a CPU reports none (dry run)
            assert dry_run
            continue
        in_use[d.id] = stats["bytes_in_use"]
        assert stats["bytes_in_use"] > 0, f"device {d.id} holds nothing"
    say(f"hybrid: parameter specs {sorted(specs)}")
    say(f"hybrid: bytes_in_use per device {in_use}")

    worst = max(abs(a - b) / abs(b)
                for a, b in zip(losses, one_chip_losses))
    say(f"hybrid: losses vs one chip, worst relative difference "
        f"{worst:.2e} (tolerance {HYBRID_LOSS_RTOL:.0e})")
    assert worst <= HYBRID_LOSS_RTOL, (losses, one_chip_losses)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes and no platform assert: the same "
                         "code on the CPU (JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    if not __debug__:
        raise SystemExit("chip_smoke.py checks with assert statements: run "
                         "it without -O / PYTHONOPTIMIZE")
    t_start = time.perf_counter()
    sizes = Sizes(args.dry_run)
    device = device_phase(args.dry_run)

    import jax
    from paddle_tpu.utils import cache_stats

    model, cfg, losses = train_phase(sizes, args.dry_run)
    serve_phase(model, cfg, sizes, args.dry_run)
    paged_kernel_phase(cfg, sizes)
    del model
    gc.collect()
    sparse_phase(args.dry_run)
    latent_phase(args.dry_run)
    latent_moe_phase(args.dry_run)
    if jax.device_count() >= 4:
        hybrid_phase(cfg, sizes, losses, args.dry_run)
    else:
        say(f"four-chip phase not run: {jax.device_count()} device(s)")

    t2 = cache_stats()["tier2"]
    say(f"cache.tier2 hits={t2['hits']} misses={t2['misses']} "
        f"dir={t2['dir']}")
    say(f"total wall {time.perf_counter() - t_start:.0f}s "
        "(information, not a measurement)")
    sys.stdout.flush()
    result = {"ok": True, "device": device}
    if args.dry_run:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
