"""The fused AdamW update alone, on the chip, at every leaf shape of the
training cell (``gpt3-1.3b-1chip``: the tied embedding, a layer's four
matrices, its norms' and biases' vectors), float32 parameter and
moments, bfloat16 gradient.

Columns, in microseconds a call:

- ``parent``: the update of the checkout ``--parent`` names (PR 36's
  kernel over a ``[n/128, 128]`` view of every leaf, a reshape in and
  out);
- ``donated``: this tree's update, the leaf's own layout, results
  aliased to parameter and moments, which the program hands back in
  place (as the compiled step, which donates them);
- ``kept``: the same, with the old parameter and moments kept alive
  past the update (as a caller that does not donate), so XLA copies
  each aliased input once;
- ``jnp``: the jnp lane (``Adam._fused_update`` with the kernel off);
- ``floor``: 26 B a parameter at the chip's HBM rate (read w, m1, m2 in
  float32 and g in bfloat16, write w, m1, m2).

A 1-D leaf takes the jnp lane by the rule (``adam_update_blocks``), so
its ``donated`` and ``kept`` are the lane the optimizer gives it.
Beside each row: the number of leaf-sized ``copy`` / ``reshape`` /
``transpose`` instructions in each column's compiled program, and
whether ``donated`` is bitwise equal to ``jnp`` after the calls.

A call is timed inside ONE program that makes it ``--calls`` times in
sequence (parameter and moments are the loop's carry), so the host's
dispatch is not in the number.

    chiprun --chips 1 -- python tools/ubench_adamw.py \
        --parent .bench_checkout/parent --out chiprun_out/ubench_adamw.log
"""
from __future__ import annotations

import argparse
import math
import pathlib
import re
import sys
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.pallas import fused  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e, chipbench/peaks.json
SHAPES = [(50304, 2048), (2048, 2048), (2048, 6144), (2048, 8192),
          (8192, 2048), (2048,), (6144,), (8192,)]
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = [(40, 256), (3, 16, 40), (256,)]
HYPER = dict(b1=0.9, b2=0.95, eps=1e-8, wd=0.1, decoupled=True)


def parent_module(checkout):
    """Another checkout's ``pallas/fused.py``, loaded beside this tree's
    package."""
    path = pathlib.Path(checkout) / "paddle_tpu/pallas/fused.py"
    src = path.read_text().replace("from ..", "from paddle_tpu.") \
        .replace("from .", "from paddle_tpu.pallas.")
    mod = types.ModuleType("parent_fused")
    exec(compile(src, str(path), "exec"), mod.__dict__)
    return mod


def jnp_update(w, g, m1, m2, lr, bc1, bc2, *, b1, b2, eps, wd, decoupled):
    """The optimizer's jnp lane, op for op (``Adam._fused_update``)."""
    gf = g.astype(jnp.float32)
    m1 = b1 * m1 + (1 - b1) * gf
    m2 = b2 * m2 + (1 - b2) * jnp.square(gf)
    upd = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + eps)
    upd = upd + wd * w
    return w - lr * upd, m1, m2


def program(update, calls, keep):
    """``calls`` updates in sequence over the carry (w, m1, m2); with
    ``keep`` each call's inputs stay alive past it (an optimization
    barrier over the new and the old values)."""
    def run(w, g, m1, m2):
        def body(i, carry):
            bc1 = 1.0 - 0.9 ** (i + 1.0)
            bc2 = 1.0 - 0.95 ** (i + 1.0)
            new = update(*carry[:1], g, *carry[1:], 2e-4, bc1, bc2,
                         **HYPER)
            if keep:
                new, _ = jax.lax.optimization_barrier((new, carry))
            return tuple(new)
        return jax.lax.fori_loop(0, calls, body, (w, m1, m2))
    return jax.jit(run, donate_argnums=(0, 2, 3))


def moves(compiled, elements):
    """Leaf-sized ``copy`` / ``reshape`` / ``transpose`` instructions of
    a compiled program, its loop body included."""
    n = 0
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if m and m.group(2) in ("copy", "copy-start", "reshape",
                                "transpose") and any(
                math.prod(int(d) for d in dims.split(",") if d) == elements
                for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))):
            n += 1
    return n


def timed(prog, make, calls):
    """(us a call, the compiled program, its outputs): best of 3."""
    compiled = prog.lower(*make()).compile()
    out = jax.block_until_ready(compiled(*make()))
    best = float("inf")
    for _ in range(3):
        args = make()
        jax.block_until_ready(args)
        t = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        best = min(best, time.perf_counter() - t)
    return best / calls * 1e6, compiled, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--parent", default=None)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    shapes = REHEARSAL if a.rehearse else SHAPES
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

    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind}; {calls} calls a "
        "program, best of 3 programs; us a call; [n] = leaf-sized copy / "
        "reshape / transpose instructions in the program")
    columns = {}
    if old is not None:
        columns["parent"] = (old.adam_update_pallas, False)
    say(f"{'leaf':>14} " + " ".join(
        f"{c:>14}" for c in [*columns, "donated", "kept", "jnp"])
        + f" {'floor':>8}  donated == jnp")
    for shape in shapes:
        n = math.prod(shape)
        rng = np.random.default_rng(37)
        host = [rng.standard_normal(shape).astype(np.float32) * s
                for s in (0.02, 1e-3, 1e-3, 1e-6)]
        host[3] = np.abs(host[3])

        def make():
            return (jnp.asarray(host[0]), jnp.asarray(host[1], jnp.bfloat16),
                    jnp.asarray(host[2]), jnp.asarray(host[3]))

        probe = jax.ShapeDtypeStruct(shape, jnp.float32)
        kernel = fused.adam_update_pallas \
            if fused.adam_update_supported(probe) else jnp_update
        lanes = dict(columns)
        lanes["donated"] = (kernel, False)
        lanes["kept"] = (kernel, True)
        lanes["jnp"] = (jnp_update, False)
        cells, outs = [], {}
        for name, (update, keep) in lanes.items():
            if name == "parent" and not old.adam_update_supported(probe):
                update = jnp_update
            us, compiled, out = timed(program(update, calls, keep), make,
                                      calls)
            outs[name] = out
            cells.append(f"{us:9.1f} [{moves(compiled, n)}]")
        same = all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(outs["donated"], outs["jnp"]))
        gap = max(float(jnp.max(jnp.abs(x - y)))
                  for x, y in zip(outs["donated"], outs["jnp"]))
        floor = n * 26 / HBM_BYTES_PER_S * 1e6
        say(f"{str(shape):>14} " + " ".join(f"{c:>14}" for c in cells)
            + f" {floor:8.1f}  {'yes' if same else f'no, {gap:.2e}'}")


if __name__ == "__main__":
    main()
