"""The decode step's SSM state update alone, on the chip, at both serving
cells' shapes: ``granite-4.0-h-micro`` (64 rows, 64 heads, 1 group) and
``nemotron-3-super-ep4-d11`` (128 rows, 128 heads, 8 groups), P 64, N
128, float32, every row live, the state aliased in and out.

Lines, in microseconds a call and as a share of the bytes floor (the
rows' state read and written once at the chip's HBM rate):

- ``parent``: the kernel of the checkout ``--parent`` names, over the
  state as that tree stores it (``[H, P, N]`` before the packing);
- ``copy-only (ceiling)``: a kernel with the same grid, blocks and
  aliasing as this tree's that copies each slot's state block through
  VMEM and computes nothing — what the DMA alone allows; ``/8`` the same
  with eight head tiles a grid step instead of a whole slot;
- ``packed``: this tree's ``ssm_update`` over the lane-packed state;
- ``xla``: this tree's XLA lane (``ssm_step_xla``) over the same state.

A call is timed inside ONE program that makes it ``--calls`` times in
sequence (the state and the input are the loop's carry, so each call
reads what the one before wrote), so the host's dispatch is not in the
number.  ``wall`` is the host clock round the program, best of 3;
``device`` the program's time on the device's ``XLA Modules`` line of a
profiler trace of one more run (on a CPU: not measured).  Beside each
line, the largest gap of its state and ``y`` to the XLA lane's.

    python tools/ubench_ssm_update.py --parent <parent checkout> \
        --out ubench_ssm_update.log        # one chip
"""
from __future__ import annotations

import argparse
import functools
import glob
import os
import pathlib
import sys
import tempfile
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.pallas import ssm  # noqa: E402
from paddle_tpu.pallas.flash_attention import _interpret  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e, chipbench/peaks.json
#        rows heads head state groups
SHAPES = {"granite-4.0-h-micro": (64, 64, 64, 128, 1),
          "nemotron-3-super-ep4-d11": (128, 128, 64, 128, 8)}
# --rehearse: the same script end to end on a CPU, in the interpreter
REHEARSAL = {"tiny": (4, 8, 16, 16, 2)}


def parent_module(checkout):
    """Another checkout's ``pallas/ssm.py``, loaded beside this tree's
    package."""
    path = pathlib.Path(checkout) / "paddle_tpu/pallas/ssm.py"
    src = path.read_text().replace("from ..", "from paddle_tpu.") \
        .replace("from .", "from paddle_tpu.pallas.")
    mod = types.ModuleType("parent_ssm")
    exec(compile(src, str(path), "exec"), mod.__dict__)
    return mod


def _copy_kernel(rows_ref, s_ref, s_out, y_out):
    del rows_ref
    s_out[...] = s_ref[...]
    y_out[...] = jnp.zeros(y_out.shape, y_out.dtype)


def copy_only(state, rows, x, dt, a, bm, cm, *, tiles_a_step=None):
    """The ceiling: ``ssm_update``'s grid, state blocks and aliasing, the
    body a copy (``tiles_a_step``: head tiles a grid step; None: the
    slot's whole row, as the kernel)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    del dt, a, bm, cm
    b, h, p = x.shape
    tiles, n, lanes = state.shape[1:]
    tb = tiles_a_step or tiles
    spec = pl.BlockSpec((None, tb, n, lanes),
                        lambda i, j, rows: (rows[i], j, 0, 0))
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, tiles // tb), in_specs=[spec],
            out_specs=[spec, pl.BlockSpec((None, tb, lanes),
                                          lambda i, j, rows: (i, j, 0))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, tiles, lanes), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 6 * tb * n * lanes * 4)),
        interpret=_interpret(),
        name="ssm_copy",
    )(rows, state)
    return out[0], out[1].reshape(b, h, p)


def program(step, calls):
    """``calls`` steps in sequence; the state and ``x`` are the carry
    (``y`` feeds the next call's input, so no lane can drop it)."""
    def run(state, rows, x, dt, a, bm, cm):
        def body(_, carry):
            state, x = carry
            state, y = step(state, rows, x, dt, a, bm, cm)
            return state, x + y * 0.0
        return jax.lax.fori_loop(0, calls, body, (state, x))
    return jax.jit(run, donate_argnums=(0,))


def device_seconds(trace_dir):
    """Summed ``XLA Modules`` time of the traced run, or None (no chip)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    total = None
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                total = sum(e.duration_ns for e in line.events) * 1e-9
    return total


def timed(prog, make, calls):
    """(wall us a call, device us a call or None, the outputs)."""
    compiled = prog.lower(*make()).compile()
    out = jax.block_until_ready(compiled(*make()))
    best = float("inf")
    for _ in range(3):
        args = make()
        jax.block_until_ready(args)
        t = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        best = min(best, time.perf_counter() - t)
    args = make()
    jax.block_until_ready(args)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(compiled(*args))
        dev = device_seconds(d)
    return best / calls * 1e6, None if dev is None else dev / calls * 1e6, \
        out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--parent", default=None)
    ap.add_argument("--calls", type=int, default=32)
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
        "program; wall = best of 3 programs, device = the program's "
        "XLA Modules time in a trace of one more; us a call; share = "
        "floor / device (wall where no device time)")
    for name, (b, h, p, n, g) in shapes.items():
        k = ssm.heads_per_tile(h, p, g)
        row = ssm.state_row_shape(h, p, n, g)
        floor = b * 2 * h * p * n * 4 / HBM_BYTES_PER_S * 1e6
        rng = np.random.default_rng(39)
        host = rng.standard_normal((b + 1, h, p, n)).astype(np.float32)
        rows = jnp.arange(b, dtype=jnp.int32)
        x = jnp.asarray(rng.standard_normal((b, h, p)), jnp.float32)
        dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((b, h)))),
                         jnp.float32)
        decay = jnp.asarray(-np.exp(rng.standard_normal(h)), jnp.float32)
        bm, cm = (jnp.asarray(rng.standard_normal((b, g, n)), jnp.float32)
                  for _ in range(2))

        def make(packed):
            s = jnp.asarray(host)
            return (ssm.pack_state(s, k) if packed else s, rows, x, dt,
                    decay, bm, cm)

        say(f"\n{name}: {b} rows, H {h}, P {p}, N {n}, G {g}; stored row "
            f"{list(row)} (k = {k}); floor {floor:.1f} us "
            f"({b * 2 * h * p * n * 4 / 1e6:.1f} MB read and written)")
        say(f"{'line':>26} {'wall':>9} {'device':>9} {'share':>7}  "
            "max|state - xla|, max|y - xla|")
        lanes = [("xla", ssm.ssm_step_xla, True),
                 ("packed", ssm.ssm_update, True),
                 ("copy-only (ceiling)", copy_only, True),
                 ("copy-only (ceiling) /8",
                  functools.partial(copy_only, tiles_a_step=min(
                      8, row[0])), True)]
        if old is not None:
            lanes.append(("parent", old.ssm_update, False))
        want = None
        for label, step, packed in lanes:
            wall, devus, _ = timed(program(step, calls),
                                   functools.partial(make, packed), calls)
            s_out, y = jax.jit(step)(*make(packed))
            s_out = np.asarray(s_out if packed
                               else ssm.pack_state(s_out, k))
            y = np.asarray(y)
            if want is None:
                want = (s_out, y)
            gaps = "-" if label.startswith("copy") else (
                f"{np.abs(s_out - want[0]).max():.2e}, "
                f"{np.abs(y - want[1]).max():.2e}")
            share = floor / (devus or wall) * 100
            say(f"{label:>26} {wall:9.1f} "
                f"{'-' if devus is None else f'{devus:.1f}':>9} "
                f"{share:6.1f}%  {gaps}")


if __name__ == "__main__":
    main()
