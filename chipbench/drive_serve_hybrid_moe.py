"""The serving cells of a hybrid decoder whose expert layers are latent
(``models/nemotron_h.py``): ``drive_serve``'s window and its
``served_logit_gap``, ``drive_serve_state``'s ``state_gap`` (the
recurrent state of the Mamba-2 layers, read from the rows of requests
that finished after the close), a ``routed_gap`` of the LATENT expert
layers and an ``attention_gap`` of the attention layers.

Both are read one layer at a time, as ``drive_serve_mla``'s
``routed_gap`` and ``latent_gap`` are: after the close the longest
finished request goes through the plain reference once more, layer by
layer, and each layer's normed input, as the reference has it, is
handed to the PROGRAM's layer.

- ``routed_gap`` (``drive_serve_moe``'s, for a stack whose layers are
  one kind each): an EXPERT layer's input goes through
  ``SparseExpertMLP.routed`` (its router, the latent down projection,
  its sorted groups and relu² products in the lane serving ran, the
  latent up projection, its weights as they were served) in pieces of
  one prefill chunk, and once more in one piece of ``num_slots``
  positions spread over the request (the tick's rows, so the tick's
  tile is compared too), and is compared with the reference's routed part
  (``routed_part``, through ``W_lat_up`` as well) at the positions where
  both chose the same experts.  Its control
  (``tests/control_nemotron.py``) is the reference's routed part with
  the experts' own products in float8.
- ``attention_gap``: an ATTENTION layer's input goes through the
  program's attention over a K/V page store of its own in the pages'
  type — the whole sequence in prefill-chunk pieces (the chunk read),
  then one single-token call of ``num_slots`` rows at positions of the
  served part spread evenly (the decode kernel, each row at its own
  offset over the same pages) — and is compared with the reference's
  attention output (relative Frobenius gap; the wider of the two
  paths).  Served logits cannot hold this layer: one attention layer in
  eleven moves them less than the router's flips do (a planted
  rotation read 0.16, PERF.md section 2).  Its control, read beside
  every run, is the reference's attention with its keys and values
  rounded to float8 (e4m3, a scale a row): the nearest type below the
  pages' bfloat16.

"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import drive_serve
import drive_serve_state
from drive_serve_mla import _rel, _round_e4m3
from drive_serve_moe import _gap
from reference import common as refc
from reference import run as refrun


class HybridMoeProgram(drive_serve_state.StateProgram):
    """``StateProgram`` that, when it shuts the engine down, also keeps
    the model's expert and attention layers, by layer index."""

    def __init__(self, run):
        super().__init__(run)
        self.mlps, self.attns = [], []

    def shutdown(self):
        if self.model is not None:
            blocks = list(enumerate(self.model.backbone.layers))
            self.mlps = [(i, b.mixer) for i, b in blocks if b.kind == "moe"]
            self.attns = [(i, b.mixer) for i, b in blocks
                          if b.kind == "attention"]
        super().shutdown()

    def longest_finished(self):
        """(prompt, served tokens) of the longest request that finished."""
        done = [(p, np.asarray(f.result().output_ids, np.int32))
                for p, f, _ in self.sent
                if f.done() and not f.cancelled() and f.exception() is None]
        if not done:
            raise RuntimeError("no request finished: there is no routed "
                               "part to compare")
        return max(done, key=lambda t: t[0].size + t[1].size)

    def finish(self):
        """After the last comparison (a planted fault lifts its patches
        here, tests/test_faults_nemotron.py)."""
        self.mlps, self.attns = [], []


def program_routed(mlp, n, piece):
    """The program's routed part for ``n`` [S, h] and the experts its
    router chose ([S, k]), ``piece`` positions a call."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    router = tuple(t._data_ for t in mlp.router_leaves())
    outs, sets = [], []
    for a in range(0, n.shape[0], piece):
        x = n[a:a + piece]
        with no_grad():
            y, _ = mlp.routed(Tensor(x[None]))
        outs.append(y._data_[0].astype(jnp.float32))
        sets.append(mlp.route(x, *router)[0])
    return jnp.concatenate(outs), jnp.concatenate(sets)


def program_attention(run, attn, y, n, prompt_len):
    """The program's attention layer for the normed inputs ``y`` [pad, h]
    over a K/V page store of its own: (the chunk path's outputs [pad, h],
    zero past the last chunk that holds one of the ``n`` real positions;
    the positions of the single-token call; its outputs there)."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving import PagedKVCache
    eng, cfg = run.cell["engine"], attn.config
    pad, piece, rows = y.shape[0], eng["prefill_chunk_tokens"], \
        eng["num_slots"]
    cache = PagedKVCache(1, 1, pad, cfg.num_kv_heads, cfg.head_dim,
                         page_size=eng["page_size"],
                         dtype=eng["cache_dtype"])
    slot = cache.allocate(cache.pages_per_slot)
    pieces = -(-n // piece)
    cache.ensure_capacity(slot, min(pieces * piece, pad) - 1)
    chunks = []
    with no_grad():
        for a in range(0, pieces * piece, piece):
            cache.set_offset(slot, a)
            chunks.append(attn(Tensor(y[None, a:a + piece]),
                               cache=cache.layer_caches()[0])._data_[0])
        served = np.arange(prompt_len, n)
        pos = served[np.linspace(0, served.size - 1, rows).round()
                     .astype(int)].astype(np.int32)
        table = np.repeat(cache.table[slot][None], rows, axis=0)
        view = cache.views_over(cache.flat_pools(), jnp.asarray(table),
                                jnp.asarray(pos))[0]
        # finished before the next eager op starts: the Pallas
        # interpreter's callbacks (a CPU rehearsal) cannot run beside one
        step = jax.block_until_ready(
            attn(Tensor(y[pos][:, None]), cache=view)._data_[:, 0])
    chunks = jnp.concatenate(chunks).astype(jnp.float32)
    chunks = jnp.pad(chunks, ((0, pad - chunks.shape[0]), (0, 0)))
    return chunks, pos, step.astype(jnp.float32)


def layer_gaps(run, weights, mlps, attns, prompt, output):
    """(routed_gap, attention_gap) of the program's layers on the
    reference's layer inputs for one request."""
    from paddle_tpu.utils import monitor
    arch = refrun.arch_module(run.config["reference"])
    cfg = run.model_cfg
    pad = run.cell["engine"]["max_seq_len"]
    piece = run.cell["engine"]["prefill_chunk_tokens"]
    dtype = jnp.dtype(run.cell["weights_dtype"])
    control = getattr(run, "control_routed", None)
    mm = refc.mm_f32
    embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
    layer = jax.jit(lambda x, w: arch.layer(x, w, cfg, mm))
    routed = jax.jit(lambda x, w: arch.routed_part(x, w, cfg, mm))
    low = jax.jit(lambda x, w: arch.routed_part(
        x, w, cfg, mm, refc.MATMULS[control])) if control else None
    attention = jax.jit(lambda x, w: arch.attention_part(x, w, cfg, mm))
    attention_fp8 = jax.jit(lambda x, w: arch.attention_part(
        x, w, cfg, mm, _round_e4m3)[1])
    n = prompt.size + output.size
    # the tick's own size: as many positions as it has rows, spread over
    # the request (a stretch of served tokens can be one token repeated,
    # and one near-tie in its routing then flips the whole stretch)
    rows = run.cell["engine"]["num_slots"]
    tick = np.linspace(0, n - 1, rows).round().astype(int)
    ids = np.zeros(pad, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    x = embed({k: weights[k] for k in arch.EMBED_NAMES},
              jnp.asarray(ids)[None])
    mlp_of, attn_of = dict(mlps), dict(attns)
    real = jnp.arange(pad) < n
    gaps, tick_gaps, c_gaps, flips, a_gaps, a_ctl = [], [], [], [], [], []
    lanes0 = monitor.all_stats()
    for i in range(max([*mlp_of, *attn_of]) + 1):
        w = refrun._layer_weights(arch, cfg, weights, i)
        if i in mlp_of:
            y, want, sets = routed(x, w)
            got, sets_got = program_routed(mlp_of[i], y[0].astype(dtype),
                                           piece)
            g, f = _gap(got, want[0], sets_got, sets[0], n)
            got_t, sets_t = program_routed(
                mlp_of[i], y[0, tick].astype(dtype), rows)
            g_t = _gap(got_t, want[0, tick], sets_t, sets[0, tick],
                       rows)[0]
            gaps.append(float(g))
            tick_gaps.append(float(g_t))
            flips.append(int(f))
            if low is not None:
                _, c_out, c_sets = low(x, w)
                c_gaps.append(float(_gap(c_out[0], want[0], c_sets[0],
                                         sets[0], n)[0]))
        if i in attn_of:
            y, want = attention(x, w)
            chunks, pos, step = program_attention(
                run, attn_of[i], y[0].astype(dtype), n, prompt.size)
            a_gaps.append((float(_rel(chunks, want[0], real)),
                           float(_rel(step, want[0][pos],
                                      jnp.ones(pos.size, bool)))))
            a_ctl.append(float(_rel(attention_fp8(x, w)[0], want[0],
                                    real)))
        x = layer(x, w)
    keys = ("pallas.expert_gmm.relu2.kernel",
            "pallas.expert_gmm.relu2.xla_lane",
            "pallas.paged_decode.kernel", "pallas.paged_decode.xla_lane")
    lanes = {k: monitor.all_stats().get(k, 0) - lanes0.get(k, 0)
             for k in keys}
    said = (f"latent routed part over {n} positions, gap by expert layer: "
            + " ".join(f"{g:.4g}" for g in gaps)
            + f"; in one piece of {rows} positions (the tick's tile): "
            + " ".join(f"{g:.4g}" for g in tick_gaps)
            + "; positions that chose other experts: "
            + " ".join(str(f) for f in flips)
            + "; relu2 products traced: "
            + ", ".join(f"{lanes[k]} {k.rsplit('.', 1)[1]}"
                        for k in keys[:2]))
    if low is not None:
        said += f"; control {control} experts: " + " ".join(
            f"{g:.4g}" for g in c_gaps)
        run.records["control_routed_gap"] = max(c_gaps)
    run.say(said)
    run.records["routed_flips"] = flips
    routed = max(gaps + tick_gaps)
    if not a_gaps:
        return routed, None
    run.say("attention over its own pages, gap by attention layer (chunk "
            "path / single-token path): "
            + " ".join(f"{c:.4g} / {t:.4g}" for c, t in a_gaps)
            + "; control, keys and values in float8: "
            + " ".join(f"{g:.4g}" for g in a_ctl)
            + "; single-token reads traced: "
            + ", ".join(f"{lanes[k]} {k.rsplit('.', 1)[1]}"
                        for k in keys[2:]))
    run.records["control_attention_gap"] = min(a_ctl)
    return routed, max(max(g) for g in a_gaps)


def measure(run, prog_factory=HybridMoeProgram):
    progs = []

    def factory(run):
        progs.append(prog_factory(run))
        return progs[0]

    try:
        drive_serve.measure(run, prog_factory=factory)
        prog = progs[0]
        if not prog.kept:
            raise RuntimeError("no finished request's slot stayed empty: "
                               "there is no state to compare")
        numbers = {name: value for name, (value, _) in run.compared.items()}
        t = time.perf_counter()
        numbers["state_gap"] = drive_serve_state.state_gap(
            run, prog.weights, prog.kept)
        run.say(f"state: {len(prog.kept)} requests against the reference "
                f"in {time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        numbers["routed_gap"], attention_gap = layer_gaps(
            run, prog.weights, prog.mlps, prog.attns,
            *prog.longest_finished())
        if attention_gap is not None:
            numbers["attention_gap"] = attention_gap
        run.say(f"one request through {len(prog.mlps)} expert and "
                f"{len(prog.attns)} attention layers in "
                f"{time.perf_counter() - t:.1f}s")
        run.judge(numbers)
    finally:
        for prog in progs:
            prog.finish()
