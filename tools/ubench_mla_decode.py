"""The latent decode read alone, on the chip: one call at the latent
cell's shapes (56 rows, 64 heads, rows of 576 values in 640 lanes, pages
of 16), every row at a context of 1 k / 8 k / 16 k.

Columns, in microseconds a call: the Pallas kernel at each candidate
step size (``mla._STEP_BYTES``; the one the tree's rule picks is
starred), the XLA lane (``mla_decode_xla``), and the two floors of the
call: the live rows' 576 values once at the chip's HBM rate, and the
absorbed form's FLOPs at the chip's peak.  The kernel's worst gap to the
XLA lane is printed beside each row.

A call is timed inside ONE program that makes it ``--calls`` times in
sequence (each call's query depends on the one before), so the host's
dispatch is not in the number.

    chiprun --chips 1 -- python tools/ubench_mla_decode.py \
        --out chiprun_out/ubench_mla_decode.log
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.pallas import mla  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e, chipbench/peaks.json
BF16_FLOPS_PER_S = 197e12

#        rows heads width v   page pages-a-slot dtype
SHAPES = {"longdoc-reason-56": (56, 64, 576, 512, 16, 1280, jnp.bfloat16)}
CONTEXTS = (1024, 8192, 16384)
STEP_BYTES = (512 << 10, 1 << 20, 2 << 20, 4 << 20)
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = {"tiny": (4, 4, 40, 32, 8, 8, jnp.float32)}


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


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    shapes = REHEARSAL if a.rehearse else SHAPES
    contexts = (9, 40) if a.rehearse else CONTEXTS
    calls = 2 if a.rehearse else a.calls
    lines = [f"device {jax.devices()[0].device_kind}; us a call, "
             f"{calls} calls a program"]
    for name, (b, h, width, v, psz, n, dt) in shapes.items():
        lanes = mla.latent_row_lanes(width)
        el = jnp.dtype(dt).itemsize
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        pool = (jax.random.normal(keys[0], (1 + b * n, psz, lanes),
                                  jnp.float32) * 0.5).astype(dt)
        q = (jax.random.normal(keys[1], (b, h, lanes), jnp.float32)
             * 0.5).astype(dt)
        pt = jnp.asarray(np.arange(b * n).reshape(b, n) + 1, jnp.int32)
        picked = mla._STEP_BYTES
        for ctx in contexts:
            off = jnp.full((b,), ctx - 1, jnp.int32)
            cols = []
            ref = mla.mla_decode_xla(q, pool, pt, off, v_width=v, scale=0.1)
            worst = 0.0
            for step in STEP_BYTES:
                mla._STEP_BYTES = step
                group = mla.mla_decode_pages_per_step(psz, lanes, el)
                us = timed(lambda x, *o: mla.mla_decode_attention(
                    x, *o, v, 0.1), q, calls, pool, pt, off)
                got = mla.mla_decode_attention(q, pool, pt, off, v, 0.1)
                worst = max(worst, float(jnp.max(jnp.abs(
                    got.astype(jnp.float32) - ref.astype(jnp.float32)))))
                cols.append(f"{'*' if step == picked else ''}"
                            f"{step >> 10}K/{group}p {us:.1f}")
            mla._STEP_BYTES = picked
            xla = timed(lambda x, *o: mla.mla_decode_xla(
                x, *o, v_width=v, scale=0.1), q, calls, pool, pt, off)
            by_bytes = b * ctx * width * el / HBM_BYTES_PER_S * 1e6
            by_flops = 2.0 * h * (width + v) * b * ctx \
                / BF16_FLOPS_PER_S * 1e6
            lines.append(
                f"{name} context {ctx}: kernel " + " | ".join(cols)
                + f" | xla lane {xla:.1f} | floors: bytes {by_bytes:.1f}, "
                f"flops {by_flops:.1f} | kernel vs xla max abs {worst:.2e}")
            print(lines[-1], flush=True)
    if a.out:
        pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(a.out).write_text("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
