"""The three training flash kernels alone, on the chip: the training
cell's call (B 4, H 16, S 2,048, d 128, bfloat16, causal, head-major)
and the same tokens at S 4,096 and 8,192.

Rows: the forward, the dKV kernel, the dQ kernel (each alone: the other
backward kernel is dead code in that program) and the whole VJP
(forward, then both).  Columns, in microseconds a call:

- ``parent``: the kernels of the checkout ``--parent`` names, at its
  own (256, 512) blocks — float32 tiles, a key block a grid step;
- ``typed``: this tree's streamed grid at (256, 512): the parent's
  kernels with nothing changed but the operands' type;
- the walk at each (q block, key tile) pair of ``PAIRS`` (the VJP row
  gives the pair to forward and backward alike), and ``rule``: the walk
  at the blocks ``_pick_blocks`` returns (forward and backward apart);
- ``xla``: the XLA lane (``_xla_attention``; its dKV / dQ rows are a
  gradient with respect to (k, v) / q alone, its own forward included);
- ``floor``: the row's FLOPs at the chip's peak — causal half, 4·S²/2·d
  a head forward, twice that backward (dKV and dQ half of it each; the
  backward's recomputation of the scores does not count).

Under the table of a shape: the worst gap of out, dq, dk, dv to the
XLA lane at "highest" matmul precision on float32 copies of the same
operands, for ``parent``, ``typed`` and ``rule``.

A call is timed inside ONE program that makes it ``--calls`` times in
sequence (each call's first operand depends on the call before), so the
host's dispatch is not in the number.

    chiprun --chips 1 -- python tools/ubench_flash_train.py \
        --parent .bench_checkout/parent --out chiprun_out/ubench_flash_train.log
"""
from __future__ import annotations

import argparse
import math
import pathlib
import sys
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.pallas import flash_attention as fa  # noqa: E402

PEAK_FLOPS = 197e12              # TPU v5e bf16, chipbench/peaks.json

#          batch heads seq   d
SHAPES = {"train-2k": (4, 16, 2048, 128),
          "same-tokens-4k": (2, 16, 4096, 128),
          "same-tokens-8k": (1, 16, 8192, 128)}
PAIRS = ((256, 512), (512, 512), (512, 1024), (1024, 512), (1024, 1024),
         (2048, 1024))
PARENT_BLOCKS = (256, 512)
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = {"tiny": (1, 2, 256, 64)}
REHEARSAL_PAIRS = ((128, 128), (128, 256))
ROWS = ("fwd", "dkv", "dq", "vjp")


def parent_module(checkout):
    """Another checkout's ``flash_attention.py``, loaded beside this
    tree's package."""
    path = pathlib.Path(checkout) / "paddle_tpu/pallas/flash_attention.py"
    src = path.read_text().replace("from ..", "from paddle_tpu.")
    mod = types.ModuleType("parent_flash_attention")
    exec(compile(src, str(path), "exec"), mod.__dict__)
    return mod


def kernel_calls(mod, scale, fwd_blocks, bwd_blocks):
    """{row: fn(q, k, v, out, lse, dout) -> outputs} over ``mod``'s
    kernels at the given blocks, head-major and causal."""
    kw = dict(causal=True, scale=scale, head_major=True)

    def fwd(q, k, v, out, lse, dout):
        return mod._pallas_flash_fwd(q, k, v, block_q=fwd_blocks[0],
                                     block_k=fwd_blocks[1], **kw)[:1]

    def bwd(q, k, v, out, lse, dout):
        return mod._pallas_flash_bwd(q, k, v, out, lse, dout,
                                     block_q=bwd_blocks[0],
                                     block_k=bwd_blocks[1], **kw)

    def vjp(q, k, v, out, lse, dout):
        def loss(q, k, v):
            o = mod._flash_core(q, k, v, None, None, None, None, True,
                                scale, 0.0, *fwd_blocks, *bwd_blocks, True)
            return jnp.sum(o.astype(jnp.float32) * dout.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return {"fwd": fwd,
            "dkv": lambda *a: bwd(*a)[1:],
            "dq": lambda *a: bwd(*a)[:1],
            "vjp": vjp}


def xla_calls(scale):
    def attn(q, k, v):
        return fa._xla_attention(q, k, v, causal=True, scale=scale,
                                 head_major=True)

    def grads(argnums):
        def call(q, k, v, out, lse, dout):
            def loss(q, k, v):
                return jnp.sum(attn(q, k, v).astype(jnp.float32)
                               * dout.astype(jnp.float32))
            return jax.grad(loss, argnums=argnums)(q, k, v)
        return call
    return {"fwd": lambda q, k, v, out, lse, dout: (attn(q, k, v),),
            "dkv": grads((1, 2)), "dq": grads((0,)),
            "vjp": grads((0, 1, 2))}


def timed(call, calls, ops, whole_outputs=False):
    """Microseconds a call of ``call(*ops)``.  Call n+1's q holds one
    element of call n's outputs (times zero): in place, so the chain
    adds no pass of its own.  The XLA lane is held to all of every
    output (``whole_outputs``), or XLA would prune the products."""
    def chain(zero, *ops):
        def body(_, q):
            outs = call(q, *ops[1:])
            if whole_outputs:
                dep = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
            else:
                dep = sum(o[(0,) * o.ndim].astype(jnp.float32)
                          for o in outs)
            return q.at[(0,) * q.ndim].add((dep * zero).astype(q.dtype))
        return jax.lax.fori_loop(0, calls, body, ops[0])
    f = jax.jit(chain)
    zero = jnp.zeros((), jnp.float32)
    f(zero, *ops).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        f(zero, *ops).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best / calls * 1e6


def fits(measure):
    """``measure()``, or NaN where the XLA lane's ``[B, H, S, S]``
    float32 scores do not fit the device beside the operands."""
    try:
        return measure()
    except Exception as e:  # noqa: BLE001 — XlaRuntimeError, by its text
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return float("nan")


def streamed(fn):
    """``fn`` traced with the rule answering "streamed grid"."""
    def call(*a):
        budget, fa._WALK_VMEM_BUDGET = fa._WALK_VMEM_BUDGET, 0
        try:
            return fn(*a)
        finally:
            fa._WALK_VMEM_BUDGET = budget
    return call


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--pairs", nargs="*", default=None,
                    help="walk pairs to time instead of PAIRS: 512x1024 …")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    shapes = REHEARSAL if args.rehearse else SHAPES
    pairs = REHEARSAL_PAIRS if args.rehearse else PAIRS
    if args.pairs is not None:
        pairs = [tuple(int(x) for x in p.split("x")) for p in args.pairs]
    parent_blocks = (128, 128) if args.rehearse else PARENT_BLOCKS
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    lines = []

    def say(text):
        print(text, flush=True)
        lines.append(text)
        if args.out:            # line by line: a lost call keeps its rows
            path = pathlib.Path(args.out)
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text("\n".join(lines) + "\n")

    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind}; {args.calls} calls a "
        "program, best of 3 programs; microseconds a call")
    old = parent_module(args.parent) if args.parent else None
    for name in args.shapes or list(shapes):
        b, h, s, d = shapes[name]
        scale = 1.0 / math.sqrt(d)
        rng = np.random.default_rng(35)
        q, k, v, dout = (jnp.asarray(rng.standard_normal((b, h, s, d)),
                                     dtype) for _ in range(4))
        out, lse = jax.jit(lambda q, k, v: fa._pallas_flash_fwd(
            q, k, v, causal=True, scale=scale, block_q=128, block_k=128,
            head_major=True))(q, k, v)
        ops = (q, k, v, out, lse, dout)
        item = q.dtype.itemsize
        walks = bool(fa._walk_vmem_bytes(s, d, item, 1))
        rule = (fa._pick_blocks(s, d, "fwd", walks),
                fa._pick_blocks(s, d, "bwd", walks))
        fwd_flops = 4.0 * b * h * s * (s + 1) / 2.0 * d
        floor = {"fwd": fwd_flops, "dkv": fwd_flops, "dq": fwd_flops,
                 "vjp": 3.0 * fwd_flops}
        say(f"\n{name}: B {b}, H {h}, S {s}, d {d}, {q.dtype.name}, causal, "
            f"head-major; the rule: {'walk' if walks else 'streamed grid'} "
            f"at fwd {rule[0]}, bwd {rule[1]}")
        fit = [p for p in pairs if p[0] <= s and p[1] <= s]
        columns = {}
        if old:
            columns["parent"] = kernel_calls(old, scale, parent_blocks,
                                             parent_blocks)
        typed = kernel_calls(fa, scale, parent_blocks, parent_blocks)
        columns["typed"] = {r: streamed(f) for r, f in typed.items()}
        for p in fit:
            columns[f"{p[0]}x{p[1]}"] = kernel_calls(fa, scale, p, p)
        columns["rule"] = kernel_calls(fa, scale, *rule)
        say(f"{'':>4} " + " ".join(f"{c:>9}" for c in columns)
            + f" {'xla':>9} {'floor':>7}")
        lane = xla_calls(scale)
        for row in ROWS:
            cells = [timed(calls[row], args.calls, ops)
                     for calls in columns.values()]
            xla = fits(lambda: timed(lane[row], args.calls, ops,
                                     whole_outputs=True))
            say(f"{row:>4} " + " ".join(f"{c:9.1f}" for c in cells)
                + f" {xla:9.1f} {floor[row] / PEAK_FLOPS * 1e6:7.1f}")
        def reference():
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            with jax.default_matmul_precision("highest"):
                return jax.block_until_ready(jax.jit(
                    lambda q, k, v, dout: (
                        lane["fwd"](q, k, v, None, None, dout)
                        + lane["vjp"](q, k, v, None, None, dout)))(
                            *f32, dout))
        ref = fits(reference)
        for col in ("parent", "typed", "rule"):
            if col not in columns or not isinstance(ref, tuple):
                continue
            got = jax.jit(lambda *a, c=columns[col]: c["fwd"](*a)
                          + c["vjp"](*a))(*ops)
            gaps = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)))
                    for g, r in zip(got, ref)]
            say(f"  max |{col} - xla at highest| out, dq, dk, dv: "
                + ", ".join(f"{g:.3e}" for g in gaps))


if __name__ == "__main__":
    main()
