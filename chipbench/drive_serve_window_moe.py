"""The serving cells of a decoder whose blocks are window or full
attention, then an expert layer (``models/mellum.py``):
``drive_serve``'s window and its ``served_logit_gap``, a ``routed_gap``
of the expert layers and an ``attention_gap`` of the attention layers of
both kinds.

Both are read one layer at a time, as ``drive_serve_mla``'s are: after
the close the longest finished request goes through the plain reference
once more, layer by layer (``attention_part``, ``attend``,
``routed_part``, ``feed_forward``), and each part's normed input, as the
reference has it, is handed to the PROGRAM's part.

- ``routed_gap`` (``drive_serve_moe``'s): an expert layer's normed
  input goes through ``SparseExpertMLP.routed`` (its router, its sorted
  groups and products in the lane serving ran, its weights as they were
  served) in pieces of one prefill chunk, and once more in one piece of
  ``num_slots`` positions spread over the request (the tick's rows, so
  the tick's tile is compared too), against the reference's routed part
  at the positions where both chose the same experts.  Its control is
  the reference's routed part with the experts' own products in float8.
- ``attention_gap``: an attention layer's normed input goes through the
  program's attention over a page store of its own in the pages' type,
  built as the engine builds it — a window layer's a RING of pages with
  a prefill chunk's slack — the whole sequence in prefill-chunk pieces
  (the chunk read), and after each piece that holds some of them, one
  single-token call of ``num_slots`` rows at positions of the served part
  that the piece computed (the decode read over the same pages, each row
  at its own offset; a ring holds every key a position of the piece just
  written sees).  Against the reference's attention output (relative
  Frobenius gap; the wider path, the widest layer).  Served logits
  cannot hold these layers: one mixer in eleven moved them less than the
  router's flips do in ``reason-128`` (a planted rotation read 0.16
  there, PERF.md section 2), and here a wrong window or rotation
  changes two to six layers of eight by a few per cent.  Its control,
  read beside every run, is the reference's attention with its keys and
  values rounded to float8 (e4m3, a scale a row): the nearest type below
  the pages' bfloat16, read as the program's gap is, at the widest
  layer.  (Past the second layer the program's gap comes from its
  bfloat16 projections more than from its pages, and the control, which
  rounds the pages alone, reads below it there.)
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import drive_serve
import drive_serve_moe
from drive_serve_hybrid_moe import program_routed
from drive_serve_mla import _rel, _round_e4m3
from drive_serve_moe import _gap
from reference import common as refc
from reference import run as refrun


class WindowMoeProgram(drive_serve_moe.MoeProgram):
    """``MoeProgram`` that, when it shuts the engine down, also keeps the
    model's attention layers, and moves the weights to the host: the
    reference's float32 logits over a whole slot (5.4 GB at 13,824 x
    98,304, and as much again while ``reference/run.py`` takes them out
    of their batch) do not fit beside 7.6 GB of weights on a 16 GB chip.
    The reference's jitted layers take a layer's leaves to the device as
    they reach it; ``layer_on_device`` hands the kept layers theirs."""

    def __init__(self, run):
        super().__init__(run)
        self.attns = []

    def shutdown(self):
        if self.model is not None:
            self.attns = [blk.self_attn for blk in self.model.model.layers]
        super().shutdown()
        if self.weights and not isinstance(
                next(iter(self.weights.values())), np.ndarray):
            host = {k: np.asarray(v) for k, v in self.weights.items()}
            for v in self.weights.values():
                v.delete()
            self.weights = host

    @contextlib.contextmanager
    def layer_on_device(self, arch, cfg, i):
        """Layer ``i``'s leaves on the device, by the reference's names
        (``reference/run.py`` ``_layer_weights``), and in the kept
        attention and expert layers of the program; gone again after."""
        w = jax.device_put(refrun._layer_weights(arch, cfg, self.weights, i))
        for prefix, part in (("self_attn.", self.attns[i]),
                             ("mlp.", self.mlps[i])):
            for name, p in part.named_parameters():
                p._data_ = w[prefix + name]
        try:
            yield w
        finally:
            for a in w.values():
                a.delete()

    def finish(self):
        """After the last comparison (a planted fault lifts its patches
        here, tests/test_faults_mellum.py)."""
        self.attns = []
        super().finish()


def program_attention(run, attn, y, n, prompt_len):
    """The program's attention layer for the normed inputs ``y`` [pad, h]
    over a page store of its own: (the chunk path's outputs [pad, h],
    zero past the last chunk that holds one of the ``n`` real positions;
    the positions of the single-token calls; their outputs there)."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving import PagedKVCache
    eng, cfg = run.cell["engine"], attn.config
    pad, piece, rows = y.shape[0], eng["prefill_chunk_tokens"], \
        eng["num_slots"]
    cache = PagedKVCache(1, 1, pad, cfg.num_kv_heads, cfg.head_dim,
                         page_size=eng["page_size"],
                         dtype=eng["cache_dtype"],
                         layer_windows=[attn.window],
                         window_slack=min(piece, pad))
    slot = cache.allocate(cache.pages_per_slot)
    pieces = -(-n // piece)
    cache.ensure_capacity(slot, min(pieces * piece, pad) - 1)
    served = np.arange(prompt_len, n)
    pos = np.unique(served[np.linspace(0, served.size - 1, rows).round()
                           .astype(int)]).astype(np.int32)
    table = jnp.asarray(np.repeat(cache.table[slot][None], rows, axis=0))
    ring = jnp.asarray(np.repeat(cache.table_w[slot][None], rows, axis=0)) \
        if cache.ring_pages else None
    chunks, steps = [], []
    with no_grad():
        for a in range(0, pieces * piece, piece):
            cache.set_offset(slot, a)
            chunks.append(attn(Tensor(y[None, a:a + piece]),
                               cache=cache.layer_caches()[0])._data_[0])
            at = pos[(pos >= a) & (pos < a + piece)]
            if not at.size:
                continue
            full = np.concatenate([at, np.full(rows - at.size, at[-1],
                                               np.int32)])
            view = cache.views_over(cache.flat_pools(), table,
                                    jnp.asarray(full),
                                    window_table=ring)[0]
            # finished before the next eager op starts: the Pallas
            # interpreter's callbacks (a CPU rehearsal) cannot run
            # beside one
            steps.append(jax.block_until_ready(
                attn(Tensor(y[full][:, None]), cache=view)
                ._data_[:at.size, 0]))
    chunks = jnp.concatenate(chunks).astype(jnp.float32)
    chunks = jnp.pad(chunks, ((0, pad - chunks.shape[0]), (0, 0)))
    return chunks, pos, jnp.concatenate(steps).astype(jnp.float32)


def layer_gaps(run, prog, prompt, output):
    """(routed_gap, attention_gap) of the program's layers on the
    reference's inputs for one request."""
    from paddle_tpu.utils import monitor
    arch = refrun.arch_module(run.config["reference"])
    cfg = run.model_cfg
    pad = run.cell["engine"]["max_seq_len"]
    piece = run.cell["engine"]["prefill_chunk_tokens"]
    rows = run.cell["engine"]["num_slots"]
    dtype = jnp.dtype(run.cell["weights_dtype"])
    control = getattr(run, "control_routed", None)
    mm = refc.mm_f32
    embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
    attention = jax.jit(lambda x, w: arch.attention_part(x, w, cfg, mm))
    attention_fp8 = jax.jit(lambda x, w: arch.attention_part(
        x, w, cfg, mm, _round_e4m3)[1])
    routed = jax.jit(lambda x, w: arch.routed_part(x, w, cfg, mm))
    low = jax.jit(lambda x, w: arch.routed_part(
        x, w, cfg, mm, refc.MATMULS[control])) if control else None
    n = prompt.size + output.size
    tick = np.linspace(0, n - 1, rows).round().astype(int)
    ids = np.zeros(pad, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    x = embed({k: prog.weights[k] for k in arch.EMBED_NAMES},
              jnp.asarray(ids)[None])
    real = jnp.arange(pad) < n
    gaps, tick_gaps, c_gaps, flips, a_gaps, a_ctl = [], [], [], [], [], []
    lanes0 = monitor.all_stats()
    for i, (attn, mlp) in enumerate(zip(prog.attns, prog.mlps)):
        with prog.layer_on_device(arch, cfg, i) as w:
            y, want = attention(x, w)
            chunks, pos, step = program_attention(
                run, attn, y[0].astype(dtype), n, prompt.size)
            a_gaps.append((float(_rel(chunks, want[0], real)),
                           float(_rel(step, want[0][pos],
                                      jnp.ones(pos.size, bool)))))
            a_ctl.append(float(_rel(attention_fp8(x, w)[0], want[0],
                                    real)))
            mid = (x[0] + want, x[1])
            y, want, sets = routed(mid, w)
            got, sets_got = program_routed(mlp, y[0].astype(dtype), piece)
            g, f = _gap(got, want[0], sets_got, sets[0], n)
            got_t, sets_t = program_routed(mlp, y[0, tick].astype(dtype),
                                           rows)
            gaps.append(float(g))
            tick_gaps.append(float(_gap(got_t, want[0, tick], sets_t,
                                        sets[0, tick], rows)[0]))
            flips.append(int(f))
            if low is not None:
                _, c_out, c_sets = low(mid, w)
                c_gaps.append(float(_gap(c_out[0], want[0], c_sets[0],
                                         sets[0], n)[0]))
            # ``feed_forward`` of the reference: the stream plus the
            # routed part just computed (the layer has no shared expert)
            x = (mid[0] + want, mid[1] + 1)
    keys = ("pallas.expert_gmm.kernel", "pallas.expert_gmm.xla_lane",
            "pallas.paged_decode.kernel", "pallas.paged_decode.xla_lane")
    lanes = {k: monitor.all_stats().get(k, 0) - lanes0.get(k, 0)
             for k in keys}
    fmt = lambda gs: " ".join(f"{g:.4g}" for g in gs)   # noqa: E731
    kinds = "".join("w" if attn.window else "f" for attn in prog.attns)
    said = (f"over {n} positions ({prompt.size} of the prompt); routed "
            f"part, gap by layer: {fmt(gaps)}; in one piece of {rows} "
            f"positions (the tick's tile): {fmt(tick_gaps)}; positions "
            "that chose other experts: " + " ".join(str(f) for f in flips)
            + f"; attention over its own pages by layer ({kinds}), chunk "
            "path / single-token path: "
            + " ".join(f"{c:.4g} / {t:.4g}" for c, t in a_gaps)
            + f"; control, keys and values in float8: {fmt(a_ctl)}; traced: "
            + ", ".join(f"{v} {k.split('.', 1)[1]}"
                        for k, v in lanes.items()))
    if low is not None:
        said += f"; control {control} experts: {fmt(c_gaps)}"
        run.records["control_routed_gap"] = max(c_gaps)
    run.say(said)
    run.records["routed_flips"] = flips
    run.records["control_attention_gap"] = max(a_ctl)
    return max(gaps + tick_gaps), max(max(g) for g in a_gaps)


def measure(run, prog_factory=WindowMoeProgram):
    progs = []

    def factory(run):
        progs.append(prog_factory(run))
        return progs[0]

    try:
        drive_serve.measure(run, prog_factory=factory)
        prog = progs[0]
        numbers = {name: value for name, (value, _) in run.compared.items()}
        t = time.perf_counter()
        numbers["routed_gap"], numbers["attention_gap"] = layer_gaps(
            run, prog, *prog.longest_finished())
        run.say(f"layer gaps: one request through {len(prog.attns)} "
                f"layers in {time.perf_counter() - t:.1f}s")
        run.judge(numbers)
    finally:
        for prog in progs:
            prog.finish()
