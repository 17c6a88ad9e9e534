"""The latent decode read alone, on the chip: one call at the latent
cell's shapes (56 rows, 64 heads, rows of 576 values in 640 lanes, pages
of 16), every row at a context of 1 k / 8 k / 16 k.

For each context a line of the XLA lane (``mla_decode_xla``) and the two
floors of the call (the live rows' 576 values once at the chip's HBM
rate; the absorbed form's FLOPs at the chip's peak), then a line for each
step size (``mla._STEP_BYTES``; the one the tree's rule picks is
starred), in microseconds a call:

- ``parent``: the kernel of the checkout ``--parent`` names;
- ``kernel``: this tree's ``mla_decode_attention``, and beside it its
  nanoseconds a live page;
- ``copy old`` / ``copy new``: a kernel with the same grid, scratch and
  copies whose body only moves the pages — ``old`` starts and waits every
  page in a loop of the live count (the choreography before the one-wait
  steps), ``new`` is this tree's ``mla._step_copies``;
- ``products``: the kernel's products and online softmax
  (``mla._online_step``) over a buffer filled once, with no copies;
- the largest gap of the kernel (and the parent) to the XLA lane.

A call is timed inside ONE program that makes it ``--calls`` times in
sequence (each call's query depends on the one before), so the host's
dispatch is not in the number.

    python tools/ubench_mla_decode.py --parent <parent checkout> \\
        --out ubench_mla_decode.log        # one chip
"""
from __future__ import annotations

import argparse
import functools
import pathlib
import sys
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.pallas import mla  # noqa: E402
from paddle_tpu.pallas.flash_attention import NEG_INF, _interpret  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e, chipbench/peaks.json
BF16_FLOPS_PER_S = 197e12

#        rows heads width v   page pages-a-slot dtype
SHAPES = {"longdoc-reason-56": (56, 64, 576, 512, 16, 1280, jnp.bfloat16)}
CONTEXTS = (1024, 8192, 16384)
STEP_BYTES = (1 << 20, 2 << 20, 4 << 20, 8 << 20)      # 32 .. 256 pages
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = {"tiny": (4, 4, 40, 32, 8, 8, jnp.float32)}
REHEARSAL_STEPS = (8 << 10, 16 << 10)                  # 2 and 4 pages


def parent_module(checkout):
    """Another checkout's ``pallas/mla.py``, loaded beside this tree's
    package."""
    path = pathlib.Path(checkout) / "paddle_tpu/pallas/mla.py"
    src = path.read_text().replace("from ..", "from paddle_tpu.") \
        .replace("from .", "from paddle_tpu.pallas.")
    mod = types.ModuleType("parent_mla")
    exec(compile(src, str(path), "exec"), mod.__dict__)
    return mod


def old_copies(pt_ref, off_ref, pool_hbm, buf, sem, *, page_size, group):
    """The choreography before the one-wait steps: every step's pages
    started, and then waited on, a page at a time in a loop of the live
    count (``(start, wait)`` as ``mla._step_copies``)."""
    from jax.experimental.pallas import tpu as pltpu

    def choreography(act):
        def run(row, g, slot):
            first = g * group

            def one(j, carry):
                act(pltpu.make_async_copy(
                    pool_hbm.at[pt_ref[row, first + j]], buf.at[slot, j],
                    sem.at[slot]))
                return carry
            jax.lax.fori_loop(0, jnp.minimum(
                off_ref[row] // page_size + 1 - first, group), one, 0)
        return run
    return (choreography(lambda c: c.start()),
            choreography(lambda c: c.wait()))


def _walk_kernel(pt_ref, off_ref, q_ref, pool_hbm, o_ref, buf, sem,
                 slot_ref, *, scale, page_size, group, v_width, copies,
                 products):
    """``mla._mla_decode_kernel``'s walk with a part left out: ``copies``
    None fills both buffers once and copies nothing more; ``products``
    False leaves the products out (a row of each step's buffer is summed,
    so the copies have a reader)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    rows = pl.num_programs(0)
    h = q_ref.shape[0]
    cols = group * page_size
    if copies is not None:
        start, wait = copies(pt_ref, off_ref, pool_hbm, buf, sem,
                             page_size=page_size, group=group)

    @pl.when(b == 0)
    def _first_row():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        if copies is not None:
            start(0, 0, 0)
            return
        for s in range(2):
            for j in range(group):
                c = pltpu.make_async_copy(pool_hbm.at[pt_ref[0, j]],
                                          buf.at[s, j], sem.at[s])
                c.start()
                c.wait()

    off = off_ref[b]
    n_groups = (off // page_size + group) // group
    slot0 = slot_ref[0]
    q = q_ref[...]
    tok = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)

    def step(g, carry):
        slot = (slot0 + g) % 2
        if copies is not None:
            last = g == n_groups - 1
            nxt_row = jnp.where(last, b + 1, b)

            @pl.when(nxt_row < rows)
            def _prefetch():
                start(jnp.minimum(nxt_row, rows - 1),
                      jnp.where(last, 0, g + 1), 1 - slot)

            wait(b, g, slot)
        if products:
            kv = buf[slot].reshape(cols, buf.shape[-1])
            return mla._online_step(q, kv, g * cols + tok <= off, carry,
                                    scale=scale, v_width=v_width)
        m, l, acc = carry
        return m, l, acc + buf[slot, 0, 0:1, :v_width].astype(jnp.float32)

    _, l, acc = jax.lax.fori_loop(
        0, n_groups, step,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, v_width), jnp.float32)))
    slot_ref[0] = (slot0 + n_groups) % 2
    o_ref[...] = (acc / l if products else acc).astype(o_ref.dtype)


def walk(q, pool, page_table, offsets, v_width, scale, *, copies,
         products):
    """``_walk_kernel`` called as ``mla._mla_decode_call`` calls the
    kernel, at the step the tree's rule picks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lanes = q.shape
    psz = pool.shape[1]
    group = mla.mla_decode_pages_per_step(psz, lanes, pool.dtype.itemsize)
    while group > page_table.shape[1]:
        group //= 2
    h_pad = -(-h // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, h_pad - h), (0, 0)))
    kernel = functools.partial(_walk_kernel, scale=scale, page_size=psz,
                               group=group, v_width=v_width, copies=copies,
                               products=products)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, h_pad, lanes),
                                   lambda bi, pt, off: (bi, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, h_pad, v_width),
                                   lambda bi, pt, off: (bi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, group, psz, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, h_pad, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if _interpret() else False,
        name="mla_decode_walk",
    )(page_table.astype(jnp.int32), offsets.astype(jnp.int32), q, pool)
    return out[:, :h]


def timed(fn, q, calls, *operands):
    """Microseconds a call of ``fn(q, *operands) -> o~`` made ``calls``
    times in one program, each call's query nudged by the one before.
    The operands are the program's ARGUMENTS: a pool closed over would be
    compiled into it as a 3.4 GB constant (PR 34's second run of this
    tool spent its 900 s compiling)."""
    def chain(q, *operands):
        def body(_, q):
            out = fn(q, *operands)
            return q + (jnp.mean(out) * 1e-9).astype(q.dtype)
        return jax.lax.fori_loop(0, calls, body, q)

    prog = jax.jit(chain)
    jax.block_until_ready(prog(q, *operands))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(prog(q, *operands))
        best = min(best, time.perf_counter() - t)
    return best / calls * 1e6


def gap(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--parent", default=None)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    shapes = REHEARSAL if a.rehearse else SHAPES
    contexts = (9, 40) if a.rehearse else CONTEXTS
    steps = REHEARSAL_STEPS if a.rehearse else STEP_BYTES
    calls = 2 if a.rehearse else a.calls
    old = parent_module(a.parent) if a.parent else None
    lines = []

    def say(text):
        print(text, flush=True)
        lines.append(text)
        if a.out:               # line by line: a lost call keeps its rows
            path = pathlib.Path(a.out)
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text("\n".join(lines) + "\n")

    say(f"device {jax.devices()[0].device_kind}; us a call, {calls} calls "
        "a program (best of 3); parent = --parent's kernel; copy old / new "
        "= the walk moving pages only, per-page loops / this tree's "
        "one-wait steps; products = the walk's products over a buffer "
        "filled once")
    lanes_of = {
        "parent": lambda x, *o: old.mla_decode_attention(x, *o, v, 0.1),
        "kernel": lambda x, *o: mla.mla_decode_attention(x, *o, v, 0.1),
        "copy old": lambda x, *o: walk(x, *o, v, 0.1, copies=old_copies,
                                       products=False),
        "copy new": lambda x, *o: walk(x, *o, v, 0.1,
                                       copies=mla._step_copies,
                                       products=False),
        "products": lambda x, *o: walk(x, *o, v, 0.1, copies=None,
                                       products=True),
    }
    if old is None:
        del lanes_of["parent"]
    picked = mla._STEP_BYTES
    for name, (b, h, width, v, psz, n, dt) in shapes.items():
        lanes = mla.latent_row_lanes(width)
        el = jnp.dtype(dt).itemsize
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        pool = (jax.random.normal(keys[0], (1 + b * n, psz, lanes),
                                  jnp.float32) * 0.5).astype(dt)
        q = (jax.random.normal(keys[1], (b, h, lanes), jnp.float32)
             * 0.5).astype(dt)
        pt = jnp.asarray(np.arange(b * n).reshape(b, n) + 1, jnp.int32)
        for ctx in contexts:
            off = jnp.full((b,), ctx - 1, jnp.int32)
            live = b * ((ctx - 1) // psz + 1)
            ref = mla.mla_decode_xla(q, pool, pt, off, v_width=v, scale=0.1)
            xla = timed(lambda x, *o: mla.mla_decode_xla(
                x, *o, v_width=v, scale=0.1), q, calls, pool, pt, off)
            by_bytes = b * ctx * width * el / HBM_BYTES_PER_S * 1e6
            by_flops = 2.0 * h * (width + v) * b * ctx \
                / BF16_FLOPS_PER_S * 1e6
            say(f"{name} context {ctx} ({live} live pages): xla lane "
                f"{xla:.1f} | floors: bytes {by_bytes:.1f}, flops "
                f"{by_flops:.1f}")
            for step in steps:
                mla._STEP_BYTES = step
                if old is not None:
                    old._STEP_BYTES = step
                group = mla.mla_decode_pages_per_step(psz, lanes, el)
                cols, gaps = [], []
                for label, fn in lanes_of.items():
                    us = timed(fn, q, calls, pool, pt, off)
                    extra = (f" ({us * 1e3 / live:.1f} ns/page)"
                             if label == "kernel" else "")
                    cols.append(f"{label} {us:.1f}{extra}")
                    if label in ("parent", "kernel"):
                        worst = gap(fn(q, pool, pt, off), ref)
                        gaps.append(f"{label} {worst:.2e}")
                mark = "*" if step == picked else ""
                say(f"  {mark}{step >> 10}K/{group}p: " + " | ".join(cols)
                    + " | max abs vs xla: " + ", ".join(gaps))
            mla._STEP_BYTES = picked
            if old is not None:
                old._STEP_BYTES = picked


if __name__ == "__main__":
    main()
