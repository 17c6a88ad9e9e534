"""The latent relu² experts' routed part alone, on the chip: one call of
``pallas.moe.routed_experts`` (``act="relu2"``) at the LatentMoE cell's
shapes — 128 held experts of 1,024 → 2,688 → 1,024, 22 chosen a token of
512 — for a decode tick's 128 tokens and a prefill member's 4 × 512, at
each candidate rows-a-tile (the one ``moe.row_tile`` picks is starred).

Columns, in microseconds a call: the tile's time, and the floor of the
call, the hit experts' two matrices once at the chip's HBM rate.  A
call is timed inside ONE program that makes it ``--calls`` times in
sequence (each call's input nudged by the one before), so the host's
dispatch is not in the number.

    chiprun --chips 1 -- python tools/ubench_relu2_gmm.py \
        --out chiprun_out/ubench_relu2_gmm.log
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

from paddle_tpu.pallas import moe  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e, chipbench/peaks.json

#         latent width held published k
SHAPE = (1024, 2688, 128, 512, 22)
#: tokens a call -> candidate rows a tile
CASES = {128: (16, 32, 64, 128), 2048: (64, 128, 256)}
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = ((128, 256, 8, 32, 6), {64: (16, 64)})


def timed(fn, x, calls, *operands):
    """Microseconds a call of ``fn(x, *operands) -> y`` made ``calls``
    times in one program (the operands are its arguments, not
    constants)."""
    def chain(x, *operands):
        def body(_, x):
            y = fn(x, *operands)
            return x + (jnp.mean(y.astype(jnp.float32)) * 1e-9) \
                .astype(x.dtype)
        return jax.lax.fori_loop(0, calls, body, x)

    prog = jax.jit(chain)
    jax.block_until_ready(prog(x, *operands))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(prog(x, *operands))
        best = min(best, time.perf_counter() - t)
    return 1e6 * best / calls


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    (lat, f, held, pub, k), cases = (REHEARSAL if a.rehearse
                                     else (SHAPE, CASES))
    dtype = jnp.float32 if a.rehearse else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    w_up = (jax.random.normal(keys[0], (held, lat, f)) * 0.02).astype(dtype)
    w_dn = (jax.random.normal(keys[1], (held, f, lat)) * 0.02).astype(dtype)
    bias = jax.random.normal(keys[2], (pub,)) * 0.02
    lines = [f"device {jax.devices()[0].device_kind}; held {held} of {pub}, "
             f"{k} a token, {lat} -> {f} -> {lat} relu2, {dtype.__name__}"]
    rule = moe.row_tile
    for tokens, tiles in cases.items():
        x = jax.random.normal(keys[3], (tokens, lat)).astype(dtype)
        logits = jax.random.normal(keys[4], (tokens, pub))
        experts, gates = moe.route_sigmoid_topk(logits, k, bias=bias,
                                                scale=5.0)
        hit = np.unique(np.asarray(experts))
        hit = int(np.sum(hit < held))
        floor = 1e6 * hit * 2 * lat * f * jnp.dtype(dtype).itemsize \
            / HBM_BYTES_PER_S
        picked = rule(tokens)
        for tm in tiles:
            moe.row_tile = lambda t, tm=tm: tm
            try:
                us = timed(lambda x, e, g, wu, wd: moe.routed_experts(
                    x, e, g, None, wu, wd, (0, held), act="relu2")[0],
                    x, a.calls, experts, gates, w_up, w_dn)
            finally:
                moe.row_tile = rule
            star = "*" if tm == picked else " "
            lines.append(
                f"tokens {tokens:5d} tile {tm:4d}{star}"
                f" {us:10.1f} us a call; floor {floor:8.1f} us "
                f"({hit} held experts hit): {100 * floor / us:5.1f} %")
            print(lines[-1], flush=True)
    if a.out:
        pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(a.out).write_text("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
