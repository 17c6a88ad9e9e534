"""Every kernel that turns itself on by platform compiles for the chip.

No chip is needed: the installed libtpu describes a v5e 2x2 topology
(``jax.experimental.topologies.get_topology_desc``) whose devices can be
compiled against — real Mosaic, no run.  Each Pallas kernel is lowered
and compiled for it at the shapes GPT-2 124M, GPT-3 1.3B widths and
Llama use, alone and inside a program partitioned over a 2x2 mesh; a
kernel that cannot be hosted somewhere must be deselected there by a
rule in the code, which is asserted too.  The interpreter
(``PADDLE_TPU_PALLAS_INTERPRET``) is off throughout.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.pallas import flash_attention as fa
from paddle_tpu.pallas import fused

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def v5e():
    """The four compile-only ``TPU v5 lite`` devices of a v5e 2x2."""
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — the one admitted skip
        pytest.skip("jax.experimental.topologies.get_topology_desc cannot "
                    f"describe a v5e topology here, so nothing can be "
                    f"compiled for the chip: {type(e).__name__}: {e}")
    finally:
        mp.undo()
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """The gates decide as they do on a TPU; no interpreter."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)


def _compile(fn, shapes, sharding, compiled=False, donate=()):
    """Lower + compile ``fn`` for the TPU, its arguments ``donate``
    donated; returns the StableHLO text, or with ``compiled`` the
    compiled program's."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding)
            for s in shapes]
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    program = lowered.compile()
    return program.as_text() if compiled else lowered.as_text()


def _n_kernels(text):
    return text.count("tpu_custom_call")


# ---------------------------------------------------------------- flash

#           b  s     h   h_kv d    head_major  features
_FLASH = {
    "gpt2-124m": (2, 1024, 12, 12, 64, True, ()),
    "gpt3-1.3b": (1, 2048, 16, 16, 128, True, ()),
    "llama-gqa-segments": (1, 2048, 32, 8, 128, False, ("seg",)),
    "d64-additive-mask": (1, 1024, 12, 12, 64, False, ("mask",)),
    "d64-dropout": (1, 1024, 12, 12, 64, True, ("dropout",)),
}


@pytest.mark.parametrize("case", sorted(_FLASH))
def test_flash_fwd_bwd_compiles(v5e, case):
    b, s, h, h_kv, d, head_major, feats = _FLASH[case]
    dropout = 0.1 if "dropout" in feats else 0.0
    # the path and the blocks the public op would give the call
    walk = bool(fa._walk_vmem_bytes(s, d, 2, h // h_kv, "mask" in feats,
                                    "seg" in feats))
    assert walk == ("mask" not in feats)
    bq, bk = fa._pick_blocks(s, d, "fwd", walk)
    bqb, bkb = fa._pick_blocks(s, d, "bwd", walk)

    def f(q, k, v, mask, qseg, kseg, seed):
        def loss(q, k, v):
            out = fa._flash_core(q, k, v, mask, qseg, kseg, seed, True,
                                 1.0 / math.sqrt(d), dropout, bq, bk,
                                 bqb, bkb, head_major)
            return jnp.sum(out.astype(F32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    def qkv(heads):
        return ((b, heads, s, d) if head_major else (b, s, heads, d), BF16)

    shapes = [qkv(h), qkv(h_kv), qkv(h_kv),
              ((1, 1, s, s), F32) if "mask" in feats else None,
              ((b, s, 1), jnp.int32) if "seg" in feats else None,
              ((b, 1, s), jnp.int32) if "seg" in feats else None,
              ((1, 1), jnp.uint32) if dropout else None]
    text = _compile(f, shapes, SingleDeviceSharding(v5e[0]))
    assert _n_kernels(text) == 3          # fwd, dK/dV, dQ


def test_flash_every_autotune_candidate_compiles(v5e):
    """``autotune_blocks`` sweeps these block pairs on the chip and no
    longer skips one that fails: each must compile."""
    s, d = 1024, 64
    cands = fa._block_candidates(s)
    assert len(cands) == 15 and (1024, 512) in cands \
        and (1024, 1024) not in cands
    for bq, bk in cands:
        def f(q, k, v, bq=bq, bk=bk):
            return fa._flash_core(q, k, v, None, None, None, None,
                                  True, 0.125, 0.0, bq, bk, None,
                                  None, False)
        text = _compile(f, [((1, s, 2, d), BF16)] * 3,
                        SingleDeviceSharding(v5e[0]))
        assert _n_kernels(text) == 1, (bq, bk)


def _flash_vjp_program(v5e, b, h, h_kv, s, d, fwd, bwd):
    """The forward and both backward kernels of a causal head-major
    bfloat16 call at the given blocks, compiled for the chip."""
    def f(q, k, v):
        def loss(q, k, v):
            out = fa._flash_core(q, k, v, None, None, None, None, True,
                                 1.0 / math.sqrt(d), 0.0, *fwd, *bwd, True)
            return jnp.sum(out.astype(F32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    return _compile(f, [((b, h, s, d), BF16), ((b, h_kv, s, d), BF16),
                        ((b, h_kv, s, d), BF16)],
                    SingleDeviceSharding(v5e[0]))


def _flash_path_traces():
    from paddle_tpu.utils import monitor
    stats = monitor.all_stats()
    return [stats.get("pallas.flash.resident", 0),
            stats.get("pallas.flash.streamed", 0)]


def test_flash_walk_compiles_at_the_training_cells_shape(v5e):
    """``gpt3-1.3b-1chip.train-2k``'s call — B 4, H 16, S 2,048, d 128,
    bfloat16, causal, head-major — takes the walk by the rule, and its
    three kernels compile for the chip with a head resident."""
    b, h, s, d = 4, 16, 2048, 128
    assert fa._walk_vmem_bytes(s, d, 2, 1) > 0
    before = _flash_path_traces()
    text = _flash_vjp_program(v5e, b, h, h, s, d,
                              fa._pick_blocks(s, d, "fwd", True),
                              fa._pick_blocks(s, d, "bwd", True))
    assert _n_kernels(text) == 3
    after = _flash_path_traces()
    assert [a - b_ for a, b_ in zip(after, before)] == [3, 0]


def test_flash_walk_every_pair_of_the_rule_compiles(v5e):
    """Every (q block, key tile) pair ``_pick_blocks`` can hand a walk,
    at a sequence length that draws it; and the longest head the
    budget admits, with four q-heads a kv head resident in dKV."""
    drawn = {}
    for s in range(128, 4096 + 1, 128):
        pairs = (fa._pick_blocks(s, 128, "fwd", True),
                 fa._pick_blocks(s, 128, "bwd", True))
        drawn.setdefault(pairs, s)
    assert len(drawn) >= 3, drawn
    for (fwd, bwd), s in drawn.items():
        text = _flash_vjp_program(v5e, 1, 2, 2, s, 128, fwd, bwd)
        assert _n_kernels(text) == 3, (s, fwd, bwd)
    s = 8192
    assert fa._walk_vmem_bytes(s, 128, 2, 4) > 0
    assert fa._walk_vmem_bytes(2 * s, 128, 2, 4) == 0
    text = _flash_vjp_program(v5e, 1, 8, 2, s, 128,
                              fa._pick_blocks(s, 128, "fwd", True),
                              fa._pick_blocks(s, 128, "bwd", True))
    assert _n_kernels(text) == 3


# ------------------------------------------------- rms norm, rope, adam

@pytest.mark.parametrize("rows,n", [(2048, 2048), (2048, 4096)])
def test_rms_norm_fwd_bwd_compiles(v5e, rows, n):
    assert fused.rms_norm_supported(
        jax.ShapeDtypeStruct((rows, n), BF16),
        jax.ShapeDtypeStruct((n,), BF16))

    def f(x, w):
        return jax.value_and_grad(
            lambda x, w: jnp.sum(fused.rms_norm_pallas(x, w, 1e-6)
                                 .astype(F32)), argnums=(0, 1))(x, w)

    text = _compile(f, [((rows, n), BF16), ((n,), BF16)],
                    SingleDeviceSharding(v5e[0]))
    assert _n_kernels(text) == 2


def test_rope_neox_compiles_and_interleaved_is_deselected(v5e):
    shape, d = (1, 2048, 32, 128), 128
    assert fused.rope_supported(shape, d, neox=True)
    # no interleaved kernel compiles for the chip (its pair reshape is a
    # gather Mosaic refuses): the XLA rope is that style's one path
    assert not fused.rope_supported(shape, d, neox=False)

    def f(t, cos, sin):
        return jax.value_and_grad(
            lambda t: jnp.sum(fused.rope_pallas(t, cos, sin)
                              .astype(F32)))(t)

    text = _compile(f, [(shape, BF16), ((2048, d), F32), ((2048, d), F32)],
                    SingleDeviceSharding(v5e[0]))
    assert _n_kernels(text) == 2


def _entry_moves(text, elements):
    """The instructions of a compiled program's entry computation that
    copy, reshape or transpose an array of ``elements`` elements."""
    body = text[text.index("\nENTRY "):]
    body = body[:body.index("\n}")]
    found = []
    for line in body.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if m and m.group(2) in ("copy", "copy-start", "copy-done",
                                "reshape", "transpose") and any(
                math.prod(int(d) for d in dims.split(",") if d) == elements
                for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))):
            found.append(line.strip()[:160])
    return found


# the leaves of gpt3-1.3b-1chip (embedding, a layer's four matrices, its
# norms' and biases' vectors), a rank-3 leaf, an unpadded vocabulary's
# rows (no multiple of 8), GPT-2 124M's embedding and MLP
_ADAM_LEAVES = [(50304, 2048), (2048, 2048), (2048, 6144), (2048, 8192),
                (8192, 2048), (2048,), (6144,), (8192,), (16, 2048, 128),
                (50257, 2048), (50304, 768), (768, 3072)]


@pytest.mark.parametrize("shape", _ADAM_LEAVES, ids=str)
def test_fused_adam_compiles(v5e, shape):
    """The optimizer's update of one leaf, its parameter and moments
    donated as the compiled step donates them: a leaf of rank 2 and up
    is one kernel that reads and writes the leaf where it lies, and the
    entry copies, reshapes or transposes nothing of the leaf's size; a
    1-D leaf takes the jnp lane, which copies nothing either."""
    assert fused.optimizer_kernels_enabled()
    assert fused.adam_update_supported(
        jax.ShapeDtypeStruct(shape, F32)) == (len(shape) >= 2)
    text = _compile(_adamw_update(), [(shape, F32), (shape, BF16),
                                      (shape, F32), (shape, F32)],
                    SingleDeviceSharding(v5e[0]), compiled=True,
                    donate=(0, 2, 3))
    assert _n_kernels(text) == (1 if len(shape) >= 2 else 0)
    assert _entry_moves(text, math.prod(shape)) == []


# --------------------------------------------------------- paged decode

#         slots h   h_kv d    page pages/row pool dtype
_PAGED = {
    "gpt2-f32": (4, 12, 12, 64, 16, 64, F32),
    "gpt2-int8": (4, 12, 12, 64, 32, 32, I8),
    "gpt3-1.3b-bf16": (4, 16, 16, 128, 16, 128, BF16),
    "llama-gqa-int8": (4, 32, 8, 128, 32, 64, I8),
    "llama-gqa-fp8": (4, 32, 8, 128, 32, 64, jnp.float8_e4m3fn),
    # the benchmark's two serving cells, as their ticks call the kernel
    "mistral-7b-d16": (32, 32, 8, 128, 16, 72, BF16),
    "granite-4.0-h-micro": (64, 32, 8, 64, 16, 72, BF16),
}


@pytest.mark.parametrize("case", sorted(_PAGED))
def test_paged_decode_compiles(v5e, case):
    """One kernel, and in the compiled program what the benchmark's
    patterns of it need (``chipbench/kernels/paged_decode_attention*``):
    a ``tpu_custom_call`` named ``paged_decode`` with ONE array result,
    whose first two operands are the int32 page table ``[slots, pages]``
    and the int32 offsets ``[slots]``."""
    import re
    b, h, h_kv, d, psz, n, pool_dt = _PAGED[case]
    pool = (1 + b * n, psz, h_kv, d)
    quant = jnp.dtype(pool_dt).itemsize == 1
    assert fa.paged_decode_pages_per_step(
        psz, h_kv, d, jnp.dtype(pool_dt).itemsize) > 0
    shapes = [((b, h, d), F32 if pool_dt == F32 else BF16),
              (pool, pool_dt), (pool, pool_dt),
              ((b, n), jnp.int32), ((b,), jnp.int32)]
    if quant:
        shapes += [(pool[:2], F32), (pool[:2], F32)]

        def f(q, k, v, pt, off, ks, vs):
            return fa.paged_decode_attention(q, k, v, pt, off,
                                             k_scale=ks, v_scale=vs)
    else:
        f = fa.paged_decode_attention
    text = _compile(f, shapes, SingleDeviceSharding(v5e[0]), compiled=True)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    # as chipbench/kernels/paged_decode_attention_hybrid/*.json
    assert re.match(r"^(ROOT )?%paged_decode[\w.]* = ", calls[0]), calls[0]
    # one array result, not a tuple
    assert re.match(r"^(ROOT )?%[\w.-]+ = \w+\[[\d,]+\]\S* custom-call\(",
                    calls[0]), calls[0]
    # as chipbench/kernels/paged_decode_attention/pallas_paged.json: the
    # first two operands, in the constraint list the call carries
    assert re.search(
        r"operand_layout_constraints=\{s32\[%d,%d\]\S*, s32\[%d\]" % (b, n, b),
        calls[0]), calls[0]


#          rows new h   h_kv d   page pages pool
_STORED = {
    "granite-tick": (64, 1, 32, 8, 64, 16, 72, BF16),
    "granite-prefill-row": (1, 64, 32, 8, 64, 16, 72 * 64, BF16),
    "gpt2-f32-tick": (8, 1, 12, 12, 64, 16, 64, F32),
    "gpt2-int8-tick": (8, 1, 12, 12, 64, 32, 32, I8),
    "mistral-tick": (32, 1, 32, 8, 128, 16, 72, BF16),
}


@pytest.mark.parametrize("case", sorted(_STORED))
def test_paged_op_on_the_stored_pool_copies_no_pool(v5e, case):
    """The page write and the read of one attention layer, over donated
    pools in the shape ``PagedKVCache`` stores them, compiled for the
    chip: the optimized program holds no ``copy`` of an array as large
    as a pool.  (At head size 64 a 4-D pool cost three a pool: XLA's
    layout for it, the row-major write, the kernel's lane-dense view.)"""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    b, s, h, h_kv, d, psz, n, pool_dt = _STORED[case]
    item = jnp.dtype(pool_dt).itemsize
    page = fa.paged_pool_page_shape(psz, h_kv, d, item)
    assert page == ((psz * h_kv * d // 128, 128) if d < 128
                    else (psz, h_kv, d))
    pool = (1 + b * n, *page)
    quant = item == 1
    act = F32 if pool_dt == F32 else BF16

    def f(q, k, v, pools, pt, off):
        kw = dict(zip(("k_scale", "v_scale"), map(Tensor, pools[2:])))
        with no_grad():
            out = IF.paged_masked_multihead_attention(
                Tensor(q), Tensor(k), Tensor(v), Tensor(pools[0]),
                Tensor(pools[1]), Tensor(pt), Tensor(off), psz, **kw)
        return tuple(t._data_ for t in out)

    one = SingleDeviceSharding(v5e[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)
    pools = (sds(pool, pool_dt),) * 2
    if quant:
        pools += (sds((pool[0], psz), F32),) * 2
    text = jax.jit(f, donate_argnums=(3,)).lower(
        sds((b, s, h, d), act), sds((b, s, h_kv, d), act),
        sds((b, s, h_kv, d), act), pools, sds((b, n), jnp.int32),
        sds((b,), jnp.int32)).compile().as_text()
    assert _n_kernels(text) == (1 if s == 1 else 0)
    # the reading chip_smoke.py's serve leg takes of its tick on the chip
    from chip_smoke import pool_sized_copies
    assert not pool_sized_copies(text, {int(np.prod(pool))})


#          slots h    h_kv d    page entries window
_WINDOWED = {
    # the sparse-expert cell's tick: 128 query heads on 8 kv heads; a
    # window layer's ring of (4096 + 512) / 16 + 1 pages, the full
    # layer's table of 8192 / 16
    "window-ring": (32, 128, 8, 128, 16, 289, 4096),
    "full-table-of-the-same-model": (32, 128, 8, 128, 16, 512, None),
    "window-not-a-page-multiple": (4, 32, 8, 128, 16, 40, 500),
    # Mellum2's tick: 32 query heads on 4 kv heads, 64 slots; a window
    # layer's ring of (1024 + 512) / 16 + 1 pages, the full layer's
    # table of 13824 / 16
    "window-ring-of-4-kv-heads": (64, 32, 4, 128, 16, 97, 1024),
    "full-table-of-4-kv-heads": (64, 32, 4, 128, 16, 864, None),
}


@pytest.mark.parametrize("case", sorted(_WINDOWED))
def test_paged_decode_with_a_window_compiles(v5e, case):
    """The lower bound of the row's page loop and the ring indexing are
    scalar arithmetic on the prefetched offsets: still one kernel named
    ``paged_decode``."""
    import re
    b, h, h_kv, d, psz, n, window = _WINDOWED[case]
    pool = (1 + b * n, psz, h_kv, d)

    def f(q, k, v, pt, off):
        return fa.paged_decode_attention(q, k, v, pt, off, window=window)

    text = _compile(f, [((b, h, d), BF16), (pool, BF16), (pool, BF16),
                        ((b, n), jnp.int32), ((b,), jnp.int32)],
                    SingleDeviceSharding(v5e[0]), compiled=True)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert re.match(r"^(ROOT )?%paged_decode[\w.]* = ", calls[0]), calls[0]


# ------------------------------------------------------------ expert gmm

#        tokens hidden width held k
_GMM = {
    "tick-32-sessions": (32, 4096, 4096, 16, 8),
    "prefill-chunk-512": (512, 4096, 4096, 16, 8),
    "prefill-4-rows": (2048, 4096, 4096, 16, 8),
    # Mellum2: all 64 experts of width 896 held, hidden 2304
    "tick-64-sessions-all-64-held": (64, 2304, 896, 64, 8),
    "prefill-4-rows-all-64-held": (2048, 2304, 896, 64, 8),
}


@pytest.mark.parametrize("case", sorted(_GMM))
def test_expert_gmm_compiles(v5e, case):
    """The routed experts' part at the sparse-expert cell's widths: two
    kernels named ``expert_gmm`` (gate and up fused, then down) whose
    tile -> expert map and tiles in use are scalar-prefetched."""
    import re
    from paddle_tpu.pallas import moe
    t, h, f, held, k = _GMM[case]

    def fn(x, experts, gates, wg, wu, wd):
        return moe.routed_experts(x, experts, gates, wg, wu, wd, (0, held))

    text = _compile(fn, [((t, h), BF16), ((t, k), jnp.int32), ((t, k), F32),
                         ((held, h, f), BF16), ((held, h, f), BF16),
                         ((held, f, h), BF16)],
                    SingleDeviceSharding(v5e[0]), compiled=True)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2, calls
    for call in calls:
        # as chipbench/kernels/expert_ffn/pallas_expert_gmm.json
        assert re.match(r"^(ROOT )?%expert_gmm[\w.]* = ", call), call


#        tokens latent width held k
_GMM_RELU2 = {
    "tick-128-sessions": (128, 1024, 2688, 128, 22),
    "prefill-4-rows": (2048, 1024, 2688, 128, 22),
}


@pytest.mark.parametrize("case", sorted(_GMM_RELU2))
def test_expert_gmm_relu2_compiles(v5e, case):
    """The latent relu² experts at the LatentMoE cell's widths (up K 1,024
    / N 2,688, down K 2,688 / N 1,024): the up product's kernel is named
    ``expert_gmm_relu2`` and takes one weight matrix, the down product's
    ``expert_gmm``; both are matched by the latent reader's pattern."""
    import re
    from paddle_tpu.pallas import moe
    t, lat, f, held, k = _GMM_RELU2[case]

    def fn(x, experts, gates, wu, wd):
        return moe.routed_experts(x, experts, gates, None, wu, wd,
                                  (0, held), act="relu2")

    text = _compile(fn, [((t, lat), BF16), ((t, k), jnp.int32),
                         ((t, k), F32), ((held, lat, f), BF16),
                         ((held, f, lat), BF16)],
                    SingleDeviceSharding(v5e[0]), compiled=True)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2, calls
    names = sorted(re.match(r"^(?:ROOT )?%([a-z_0-9]+?)(?:\.\d+)? = ",
                            c).group(1) for c in calls)
    assert names == ["expert_gmm", "expert_gmm_relu2"], names
    for call in calls:
        # as chipbench/kernels/latent_expert_ffn/pallas_expert_gmm.json
        assert re.match(r"^(ROOT )?%expert_gmm[\w.]* = ", call), call


# --------------------------------------------------------- latent decode

#         rows heads row lanes page pages-a-slot
_MLA = {
    "longdoc-reason-56-tick": (56, 64, 640, 16, 1280),
    "prefill-bucket-of-1": (1, 64, 640, 16, 1280),
    "short-table": (4, 64, 640, 16, 8),
}


@pytest.mark.parametrize("case", sorted(_MLA))
def test_mla_decode_compiles(v5e, case):
    """The latent decode kernel at the latent cell's widths (64 heads
    against rows of 576 values living in 640 lanes, pages of 16): one
    kernel named ``mla_decode`` over the pool left in HBM."""
    import re
    from paddle_tpu.pallas import mla
    b, h, lanes, psz, n = _MLA[case]
    assert mla.latent_row_lanes(576) == lanes

    def f(q, pool, pt, off):
        return mla.mla_decode_attention(q, pool, pt, off, 512, 0.1352)

    text = _compile(f, [((b, h, lanes), BF16),
                        ((1 + b * n, psz, lanes), BF16),
                        ((b, n), jnp.int32), ((b,), jnp.int32)],
                    SingleDeviceSharding(v5e[0]), compiled=True)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    # as chipbench/kernels/mla_decode_attention/pallas_mla_decode.json
    assert re.match(r"^(ROOT )?%mla_decode[\w.]* = ", calls[0]), calls[0]


@pytest.mark.parametrize("s", [1, 512], ids=["tick", "chunk"])
def test_latent_attention_through_the_op_compiles(v5e, s):
    """The framework op over a latent pool at the cell's widths: the
    single-token step (row write, absorb products, the kernel) and a
    512-token chunk (row write, blocked up-projected read: no kernel);
    neither copies the pool."""
    from chip_smoke import pool_sized_copies
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    b, h, n, psz = (56, 64, 1280, 16) if s == 1 else (1, 64, 1280, 16)
    pool = (1 + 56 * n, psz, 640)

    def f(q, row, w, pl_, pt, off):
        cache = {"latent_pool": Tensor(pl_), "page_table": Tensor(pt),
                 "offset": Tensor(off), "page_size": psz,
                 "latent_width": 576}
        with no_grad():
            out = IF.paged_latent_attention(
                Tensor(q), Tensor(row), Tensor(w), cache, nope_dim=128,
                scale=0.1352)
        return out._data_, cache["latent_pool"]._data_

    args = [jax.ShapeDtypeStruct(sh, dt,
                                 sharding=SingleDeviceSharding(v5e[0]))
            for sh, dt in [((b, s, h, 192), BF16), ((b, s, 576), BF16),
                           ((512, h * 256), BF16), (pool, BF16),
                           ((b, n), jnp.int32), ((b,), jnp.int32)]]
    program = jax.jit(f, donate_argnums=(3,)).lower(*args).compile()
    text = program.as_text()
    assert _n_kernels(text) == (1 if s == 1 else 0)
    assert not pool_sized_copies(text, {math.prod(pool)})


def _kernels_through_the_op(device, b, h, h_kv, d, psz, n, pool_dt):
    """Kernels in the framework op's single-token step (page write +
    read) over a pool of these shapes."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF

    pool = (1 + b * n, psz, h_kv, d)

    def f(q, k, v, kp, vp, pt, off):
        with no_grad():
            out, kp2, vp2 = IF.paged_masked_multihead_attention(
                Tensor(q), Tensor(k), Tensor(v), Tensor(kp), Tensor(vp),
                Tensor(pt), Tensor(off), psz)
        return out._data_, kp2._data_, vp2._data_

    return _n_kernels(_compile(
        f, [((b, 1, h, d), BF16), ((b, 1, h_kv, d), BF16),
            ((b, 1, h_kv, d), BF16), (pool, pool_dt), (pool, pool_dt),
            ((b, n), jnp.int32), ((b,), jnp.int32)],
        SingleDeviceSharding(device)))


def test_paged_decode_unhostable_pool_is_deselected_by_the_rule(v5e):
    """Pages the kernel cannot view as whole 128-lane rows (2 kv heads
    of 16) go to the XLA gather lane by the shape rule: the op compiles
    with no kernel in it, and the kernel itself refuses the pool."""
    b, h, h_kv, d, psz, n = 4, 4, 2, 16, 8, 16
    pool = (1 + b * n, psz, h_kv, d)
    assert fa.paged_decode_pages_per_step(psz, h_kv, d, 2) == 0
    assert _kernels_through_the_op(v5e[0], b, h, h_kv, d, psz, n, BF16) == 0
    with pytest.raises(ValueError, match="XLA gather lane"):
        fa.paged_decode_attention(
            jnp.zeros((b, h, d), BF16), jnp.zeros(pool, BF16),
            jnp.zeros(pool, BF16), jnp.zeros((b, n), jnp.int32),
            jnp.zeros((b,), jnp.int32))


def test_paged_decode_through_the_op_compiles(v5e):
    """The serving decode step reaches the kernel through the framework
    op (page write + kernel read) at the GPT-2 124M tick's shapes."""
    assert _kernels_through_the_op(v5e[0], 4, 12, 12, 64, 16, 64, F32) == 1


# ----------------------------------------------------------- ssm update

#         rows slots+1 heads head state groups
_SSM = {
    "granite-4.0-h-micro-64-slots": (64, 65, 64, 64, 128, 1),
    "nemotron-3-super-128-slots": (128, 129, 128, 64, 128, 8),
    "prefill-bucket-of-2": (2, 65, 64, 64, 128, 1),
    "two-groups": (8, 9, 16, 64, 128, 2),
    "head-of-128-lanes": (8, 9, 8, 128, 128, 8),
}


@pytest.mark.parametrize("case", sorted(_SSM))
def test_ssm_update_compiles(v5e, case):
    """The decode step's in-place state update at both serving cells'
    widths (Granite 4.0-H Micro, Nemotron-3-Super) and a head that fills
    the lanes alone: one kernel over the lane-packed state, aliased in
    and out, and no copy of the state in the compiled program."""
    from paddle_tpu.pallas import ssm
    b, r, h, p, n, g = _SSM[case]
    row = ssm.state_row_shape(h, p, n, g)
    assert row[-1] == min(128, p * (h // g))
    text = _compile(ssm.ssm_step, [
        ((r, *row), F32), ((b,), jnp.int32), ((b, h, p), F32),
        ((b, h), F32), ((h,), F32), ((b, g, n), F32), ((b, g, n), F32)],
        SingleDeviceSharding(v5e[0]), compiled=True, donate=(0,))
    assert _n_kernels(text) == 1
    assert "ssm_update" in text
    state = f"f32[{','.join(str(d) for d in (r, *row))}]"
    assert not re.search(r"= " + re.escape(state) + r"\S* copy", text)


# ----------------------------------------------------------- lora delta

def test_lora_delta_compiles(v5e):
    """``FLAGS_pallas_lora`` is off by default and off the main path;
    its kernel lowers all the same (verdict recorded in PERF.md)."""
    from paddle_tpu.serving.adapters import _pallas_delta

    ns, din, rp, dout, pool = 4, 768, 8, 2304, 5
    text = _compile(_pallas_delta,
                    [((ns, 1, din), BF16), ((pool, din, rp), BF16),
                     ((pool, rp, dout), BF16), ((pool,), F32),
                     ((ns,), jnp.int32)], SingleDeviceSharding(v5e[0]))
    assert _n_kernels(text) == 1


# ------------------------------------------------- under a 2x2 dp×mp mesh

@pytest.fixture
def mesh2x2(v5e):
    from paddle_tpu.distributed.mesh import ProcessMesh
    mesh = ProcessMesh(np.array(v5e, dtype=object).reshape(2, 2),
                       ["dp", "mp"])
    with mesh:
        yield mesh


def test_flash_under_mesh_runs_in_shard_map(v5e, mesh2x2):
    """One GSPMD program over dp2×mp2 (the hybrid lane of
    ``CompiledTrainStep``): Mosaic kernels cannot be partitioned
    automatically, so the public op wraps them in ``shard_map`` — batch
    over dp, heads over mp — and the program compiles with flash on."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F

    def f(q, k, v):
        def loss(q, k, v):
            out = F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True)
            return jnp.sum(out._data_.astype(F32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    sharding = NamedSharding(mesh2x2.jax_mesh, P("dp", None, "mp", None))
    text = _compile(f, [((8, 1024, 12, 64), BF16)] * 3, sharding)
    assert _n_kernels(text) == 3 and "sdy.manual_computation" in text


def test_bare_kernel_under_mesh_is_refused(v5e, mesh2x2):
    """Why the rule exists: without ``shard_map`` Mosaic refuses the
    very same call, replicated operands or not."""
    def f(q, k, v):
        return fa._flash_core(q, k, v, None, None, None, None, True,
                              0.125, 0.0, 256, 512, None, None, False)

    sharding = NamedSharding(mesh2x2.jax_mesh, P())
    with pytest.raises(Exception, match="shard_map"):
        _compile(f, [((8, 1024, 12, 64), BF16)] * 3, sharding)


def _adamw_update():
    """The optimizer's own fused update over one parameter, as the
    compiled step's tail calls it."""
    import paddle_tpu as paddle

    opt = paddle.optimizer.AdamW(
        1e-4, parameters=[paddle.Parameter(np.zeros((8, 8), np.float32))])

    def f(p, g, m1, m2):
        new_p, st = opt._fused_update(
            jnp.float32(1e-4), jnp.float32(1.0), [p], [g],
            {"moment1": [m1], "moment2": [m2], "master": [None]},
            lr_scales=(1.0,), wd_mask=(True,))
        return new_p, st["moment1"], st["moment2"]
    return f


def test_unsharded_kernels_yield_to_xla_under_mesh(v5e, mesh2x2):
    """Adam, RMS norm, rope and paged decode carry no ``shard_map``:
    under a multi-device mesh their gates say no (a static rule), and
    the AdamW update of mp-sharded parameters compiles as plain XLA."""
    assert not fused.optimizer_kernels_enabled()
    assert not fused.rms_norm_supported(
        jax.ShapeDtypeStruct((2048, 4096), BF16),
        jax.ShapeDtypeStruct((4096,), BF16))
    assert not fused.rope_supported((1, 2048, 32, 128), 128, neox=True)
    assert not fa._unsharded_kernels_on()

    sharding = NamedSharding(mesh2x2.jax_mesh, P(None, "mp"))
    text = _compile(_adamw_update(), [((768, 3072), F32)] * 4, sharding)
    assert _n_kernels(text) == 0


def test_same_update_is_the_pallas_kernel_without_a_mesh(v5e):
    text = _compile(_adamw_update(), [((768, 3072), F32)] * 4,
                    SingleDeviceSharding(v5e[0]))
    assert _n_kernels(text) == 1


def test_interpreter_on_a_tpu_is_an_error(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="PADDLE_TPU_PALLAS_INTERPRET"):
        fa._interpret()
