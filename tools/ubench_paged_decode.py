"""The paged decode kernel alone, on the chip: one call at the serving
cells' shapes and GPT-2's, contexts 128 / 512 / the slot's capacity,
every row live and a quarter of the rows empty.

Columns, in microseconds a call: the kernel as the tree has it at each
candidate step size (``_PAGED_STEP_BYTES``; the one the tree's rule
picks is starred), the XLA gather lane (``_cache_attend`` over the
gathered view), the parent commit's kernel where ``--parent`` names a
checkout of it, and the bytes floor (the live contexts' K and V once at
the chip's HBM rate).  The kernel's worst gap to the gather lane at
"highest" matmul precision is printed beside each row.

Each shape's pools are built as ``PagedKVCache`` would store them
(``paged_pool_page_shape``: ``[pages, rows, 128]`` for heads narrower
than the lanes the kernel hosts, else ``[pages, page_size, H_kv, D]``),
so the timed program holds no relayout of a pool; the parent's kernel
gets the 4-D pools its own cache stored.

A call is timed inside ONE program that makes it ``--calls`` times in
sequence (each call's query depends on the one before), so the host's
dispatch is not in the number.

    chiprun --chips 1 -- python tools/ubench_paged_decode.py \
        --parent .bench_checkout/parent --out chiprun_out/ubench_paged_decode.log
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.incubate.nn.functional import _cache_attend  # noqa: E402
from paddle_tpu.pallas import flash_attention as fa  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e, chipbench/peaks.json

#        rows heads kv d   page pages pool dtype   q dtype      scale
# (the two cells' ticks hand the op a float32 query: rope, the norm)
SHAPES = {
    "mistral-7b": (32, 32, 8, 128, 16, 72, jnp.bfloat16, jnp.float32, None),
    "granite-4.0-h-micro": (64, 32, 8, 64, 16, 72, jnp.bfloat16,
                            jnp.float32, 1.0 / 64),
    "gpt2-124m-f32": (8, 12, 12, 64, 16, 64, jnp.float32, jnp.float32,
                      None),
}
STEP_BYTES = (64 << 10, 128 << 10, 256 << 10, 512 << 10, 1 << 20)
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = {"tiny": (4, 16, 8, 16, 8, 6, jnp.float32, jnp.float32, None)}


def parent_kernel(checkout):
    """``paged_decode_attention`` of another checkout's
    ``flash_attention.py``, loaded beside this tree's package."""
    path = pathlib.Path(checkout) / "paddle_tpu/pallas/flash_attention.py"
    src = path.read_text().replace("from ..", "from paddle_tpu.")
    mod = types.ModuleType("parent_flash_attention")
    exec(compile(src, str(path), "exec"), mod.__dict__)
    return mod.paged_decode_attention


def gather_lane(q, kp, vp, pt, off, scale, page):
    """The XLA read over the gathered pages; ``page`` = (page_size,
    H_kv, D), which a lane-dense pool's shape no longer says."""
    b, n = pt.shape
    psz, h_kv, d = page
    return _cache_attend(q[:, None], kp[pt].reshape(b, n * psz, h_kv, d),
                         vp[pt].reshape(b, n * psz, h_kv, d), off,
                         scale)[:, 0]


def timed(call, calls, q, kp, vp, pt, off, zero):
    """Microseconds a call of ``call(q, kp, vp, pt, off)``."""
    def chain(q, kp, vp, pt, off, zero):
        def body(_, carry):
            # as in a tick, every call reads pools that were just
            # written (here: the scratch page's first token, in place),
            # so no part of the read can be hoisted out of the loop
            q, kp, vp = carry
            kp, vp = (p.at[0, 0].add(zero.astype(p.dtype)) for p in (kp, vp))
            out = call(q, kp, vp, pt, off)
            return q + (out * zero).astype(q.dtype), kp, vp
        return jax.lax.fori_loop(0, calls, body, (q, kp, vp))[0]
    f = jax.jit(chain)
    f(q, kp, vp, pt, off, zero).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        f(q, kp, vp, pt, off, zero).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best / calls * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    shapes = REHEARSAL if args.rehearse else SHAPES
    steps = (1 << 10, 4 << 10) if args.rehearse else STEP_BYTES
    lines = []

    def say(text):
        print(text, flush=True)
        lines.append(text)

    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind}; {args.calls} calls a "
        "program, best of 3 programs; microseconds a call")
    old = parent_kernel(args.parent) if args.parent else None
    zero = jnp.zeros((), jnp.float32)
    rule_bytes = fa._PAGED_STEP_BYTES
    for name in args.shapes or sorted(shapes):
        b, h, h_kv, d, psz, n, pool_dt, q_dt, scale = shapes[name]
        rng = np.random.default_rng(29)
        pool = (1 + b * n, psz, h_kv, d)
        item = jnp.dtype(pool_dt).itemsize
        stored = (pool[0], *fa.paged_pool_page_shape(psz, h_kv, d, item))
        kp4 = jnp.asarray(rng.standard_normal(pool), pool_dt)
        vp4 = jnp.asarray(rng.standard_normal(pool), pool_dt)
        kp, vp = kp4.reshape(stored), vp4.reshape(stored)
        q = jnp.asarray(rng.standard_normal((b, h, d)), q_dt)
        table = rng.permutation(np.arange(1, pool[0])).reshape(b, n) \
            .astype(np.int32)
        say(f"\n{name}: {b} rows, {h}/{h_kv} heads of {d}, pages of {psz}, "
            f"{n} a row, {jnp.dtype(pool_dt).name} pool stored "
            f"{list(stored)}; pages a step by the rule: "
            f"{fa.paged_decode_pages_per_step(psz, h_kv, d, item)}")
        head = "  ".join(f"{'*' if s == rule_bytes else ''}{s >> 10}K"
                         .rjust(7) for s in steps)
        say(f"{'context':>8} {'empty':>5}  {head}  {'xla':>8} "
            f"{'parent':>8} {'floor':>7}  max|kernel-xla|")
        for ctx in sorted({min(c, n * psz) for c in (128, 512, n * psz)}):
            for empty in (0, b // 4):
                pt, off = table.copy(), np.full((b,), ctx - 1, np.int32)
                pt[:, -(-ctx // psz):] = 0
                if empty:
                    pt[::4], off[::4] = 0, 0
                floor = float((off + 1).sum()) * 2 * h_kv * d * item \
                    / HBM_BYTES_PER_S * 1e6
                ops = (q, kp, vp, jnp.asarray(pt), jnp.asarray(off))

                def kernel(*a):
                    return fa.paged_decode_attention(*a, scale=scale,
                                                     h_kv=h_kv)

                def lane(*a):
                    return gather_lane(*a, scale, pool[1:])
                cells = []
                for step in steps:
                    fa._PAGED_STEP_BYTES = step
                    cells.append(timed(kernel, args.calls, *ops, zero))
                fa._PAGED_STEP_BYTES = rule_bytes
                xla = timed(lane, args.calls, *ops, zero)
                par = timed(lambda *a: old(*a, scale=scale), args.calls,
                            q, kp4, vp4, *ops[3:], zero) \
                    if old else float("nan")
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(lane)(*ops)
                got = jax.jit(kernel)(*ops)
                gap = float(jnp.max(jnp.abs(
                    got.astype(jnp.float32) - ref.astype(jnp.float32))))
                say(f"{ctx:>8} {empty:>5}  "
                    + "  ".join(f"{c:7.1f}" for c in cells)
                    + f"  {xla:8.1f} {par:8.1f} {floor:7.1f}  {gap:.2e}")
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
