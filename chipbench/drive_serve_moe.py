"""The serving cells of a model whose feed-forward part is sparse:
``drive_serve``'s window and its ``served_logit_gap``, and one number
more, ``routed_gap``.

A router is a discontinuity: bfloat16's rounding of a layer's input
sends a few tokens in a hundred to another expert than the float32
reference chooses, and each such flip moves every later logit.
``served_logit_gap`` reads those flips beside the program's roundings,
so its limit cannot be tight enough to see the routed experts' own
precision, or one expert of sixteen left out (PERF.md section 2).  The
routed part itself tells.

After the close the longest finished request goes through the plain
reference once more, layer by layer.  Each layer's normed input, as the
reference has it, is handed to the PROGRAM's expert layer
(``CohereSparseMLP.routed``: its router, its sorted groups, its grouped
product in the lane serving ran, its weights as they were served) in
pieces of one prefill chunk, and the result is compared with the
reference's routed part for the same input (``routed_part``) at the
positions where both chose the same experts.

``routed_gap`` is the widest, over the layers, of the Frobenius norm of
program minus reference over the reference's, over those positions.  The
positions where the chosen sets differ are counted and said; were they
more than half, the gap would be taken over every position (a router
that chooses otherwise is then a gap of the order of 1, not an empty
comparison).  The control (``tests/control_moe.py``) is the reference's
routed part with the experts' products alone in a lower precision, the
router left in float32 so that the sets are the reference's."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import drive_serve
from reference import common as refc
from reference import run as refrun


class MoeProgram(drive_serve.ServeProgram):
    """``ServeProgram`` that remembers what it was sent and, when it
    shuts the engine down, keeps the model's expert layers (their
    weights are the benchmark's own arrays, which stay anyway)."""

    def __init__(self, run):
        super().__init__(run)
        self.sent, self.mlps = [], []

    def submit(self, prompt, max_new):
        fut, live = super().submit(prompt, max_new)
        self.sent.append((np.asarray(prompt, np.int32), fut))
        return fut, live

    def shutdown(self):
        if self.model is not None:
            self.mlps = [blk.mlp for blk in self.model.model.layers]
        super().shutdown()

    def longest_finished(self):
        """(prompt, served tokens) of the longest request that finished."""
        done = [(p, np.asarray(f.result().output_ids, np.int32))
                for p, f in self.sent
                if f.done() and not f.cancelled() and f.exception() is None]
        if not done:
            raise RuntimeError("no request finished: there is no routed "
                               "part to compare")
        return max(done, key=lambda t: t[0].size + t[1].size)

    def finish(self):
        """After the last comparison (a planted fault lifts its patches
        here, tests/test_faults_moe.py)."""
        self.mlps = []


def program_routed(mlp, n, piece):
    """The program's routed part for ``n`` [S, h] and the experts its
    router chose ([S, k]), ``piece`` positions a call."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.pallas import moe
    k = mlp.config.num_experts_per_tok
    outs, sets = [], []
    for a in range(0, n.shape[0], piece):
        x = n[a:a + piece]
        with no_grad():
            y, _ = mlp.routed(Tensor(x[None]))
        outs.append(y._data_[0].astype(jnp.float32))
        idx, _ = moe.route_sigmoid_topk(
            moe.router_logits(x, mlp.gate.weight._data_), k)
        sets.append(idx)
    return jnp.concatenate(outs), jnp.concatenate(sets)


@jax.jit
def _gap(got, want, sets_got, sets_want, n):
    """(relative Frobenius gap over the first ``n`` positions where the
    chosen sets agree — over all ``n`` if fewer than half do —, positions
    that disagree)."""
    real = jnp.arange(want.shape[0]) < n
    same = jnp.all(jnp.sort(sets_got, -1) == jnp.sort(sets_want, -1), -1)
    flips = jnp.sum(real & ~same)
    use = jnp.where(2 * flips > n, real, real & same)[:, None]
    num = jnp.sum(jnp.where(use, jnp.square(got - want), 0.0))
    den = jnp.sum(jnp.where(use, jnp.square(want), 0.0))
    return jnp.sqrt(num / den), flips


def routed_gap(run, weights, mlps, prompt, output):
    from paddle_tpu.utils import monitor
    arch = refrun.arch_module(run.config["reference"])
    cfg = run.model_cfg
    pad = run.cell["engine"]["max_seq_len"]
    piece = run.cell["engine"]["prefill_chunk_tokens"]
    dtype = jnp.dtype(run.cell["weights_dtype"])
    control = getattr(run, "control_routed", None)
    embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
    layer = jax.jit(lambda x, w: arch.layer(x, w, cfg, refc.mm_f32))
    routed = jax.jit(lambda x, w: arch.routed_part(x, w, cfg, refc.mm_f32))
    low = jax.jit(lambda x, w: arch.routed_part(
        x, w, cfg, refc.mm_f32, refc.MATMULS[control])) if control else None
    n = prompt.size + output.size
    ids = np.zeros(pad, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    x = embed({k: weights[k] for k in arch.EMBED_NAMES},
              jnp.asarray(ids)[None])
    gaps, c_gaps, flips = [], [], []
    lanes0 = monitor.all_stats()
    for i, mlp in enumerate(mlps):
        w = refrun._layer_weights(arch, cfg, weights, i)
        y, want, sets = routed(x, w)
        got, sets_got = program_routed(mlp, y[0].astype(dtype), piece)
        g, f = _gap(got, want[0], sets_got, sets[0], n)
        gaps.append(float(g))
        flips.append(int(f))
        if low is not None:
            _, c_out, c_sets = low(x, w)
            c_gaps.append(float(_gap(c_out[0], want[0], c_sets[0], sets[0],
                                     n)[0]))
        x = layer(x, w)
    lanes = {k: monitor.all_stats().get(k, 0) - lanes0.get(k, 0)
             for k in ("pallas.expert_gmm.kernel",
                       "pallas.expert_gmm.xla_lane")}
    said = (f"routed part over {n} positions, gap by layer: "
            + " ".join(f"{g:.4g}" for g in gaps)
            + "; positions that chose other experts: "
            + " ".join(str(f) for f in flips)
            + "; grouped products traced: "
            + ", ".join(f"{v} {k.rsplit('.', 1)[1]}"
                        for k, v in lanes.items()))
    if low is not None:
        said += f"; control {control} experts: " + " ".join(
            f"{g:.4g}" for g in c_gaps)
        run.records["control_routed_gap"] = max(c_gaps)
    run.say(said)
    run.records["routed_flips"] = flips
    return max(gaps)


def measure(run, prog_factory=MoeProgram):
    progs = []

    def factory(run):
        progs.append(prog_factory(run))
        return progs[0]

    try:
        drive_serve.measure(run, prog_factory=factory)
        prog = progs[0]
        numbers = {name: value for name, (value, _) in run.compared.items()}
        t = time.perf_counter()
        numbers["routed_gap"] = routed_gap(run, prog.weights, prog.mlps,
                                           *prog.longest_finished())
        run.say(f"routed part: one request through {len(prog.mlps)} expert "
                f"layers in {time.perf_counter() - t:.1f}s")
        run.judge(numbers)
    finally:
        for prog in progs:
            prog.finish()
