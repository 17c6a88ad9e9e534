"""The serving cells of a model with LATENT attention and sparse experts:
``drive_serve``'s window and its ``served_logit_gap``, and three numbers
more.

``served_logit_gap`` reads a bfloat16 router's flips beside every
rounding of the program (PERF.md section 2), so it cannot be tight
enough to hold the program's precision.  What the SERVED run left in the
engine's own page store can.

**``served_latent_gap``: the timed path itself.**  Nothing is sent after
the close, and when the engine stops the sessions still live keep their
slots: the latent pools hold, a layer each, the row ``[c, rope(k_r)]`` of
every position they had consumed — the prompt's rows as the
``serving_prefill_r<rows>`` members wrote them (each chunk reading the
pages before it in the up-projected form), the answer's rows as the
compiled tick wrote them (the absorbed read on the lane serving ran),
all of it while the other slots were live.  A layer's row is a norm and
one product away from that layer's input, so layer ``i``'s rows carry
what attention and the feed-forward parts of the layers before ``i``
computed, in the precision they computed it.  ``PagedKVCache.read_latent``
hands back the rows of ``served_latent_gap.requests`` live sessions (the
longest, the others drawn from the seed); the plain reference computes
the same rows after the same tokens.  For every position the gap is the
norm of program minus reference over the reference's; a router is a
discontinuity (a few positions in a hundred choose another expert in
bfloat16 and read a gap of the order of 1), so a layer's number is the
MEDIAN over positions, taken apart over the prompt's rows (the prefill
members') and the answer's (the tick's).  ``served_latent_gap`` is the
widest, over the sessions, the layers and the two parts.  The controls
(``tests/control_mla.py``) put the rows of the reference computed in
float8 — whole, with float8 latent rows alone, with float8 products in
the experts alone — in the engine's place.

**``routed_gap`` and ``latent_gap``: one layer at a time.**  After the
close the longest finished request goes through the plain reference once
more, layer by layer.  ``routed_gap`` is ``drive_serve_moe``'s, through
the layer's own routing (a selection bias and a scaling factor are part
of it).  For ``latent_gap`` each layer's normed attention input, as the
reference has it, is handed to the PROGRAM's attention layer over a
latent page store of its own (one slot of the cell's page size and cache
type):

- the whole sequence in prefill chunks of the cell's length: the rows
  are written through the page table and every chunk reads the pages
  before it in the up-projected form;
- then ``DECODE_CALLS`` single-token calls of ``num_slots`` rows each,
  every row another position of the served part (evenly spaced, the last
  among them) over the SAME pages at its own offset: the absorbed form
  through the lane serving ran (on the chip the Pallas kernel).

Both are compared with the reference's attention output (after ``W_o``)
at the same positions: ``latent_gap`` is the widest, over the layers and
the two paths, of the Frobenius norm of program minus reference over the
reference's.  These two say WHICH layer a fault sits in, on inputs no
flip has touched; they run the layers' code, not the engine's programs,
and do not stand in for the number above.  Their controls are the
reference's own attention with the latent rows rounded to float8 as a
float8 cache would hold them, or with ``W_kvb``'s products (the absorb
products' counterpart in the up-projected form) in float8, and the
routed part with float8 products in the experts."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import drive_serve
import drive_serve_moe
import traffic
from reference import common as refc
from reference import run as refrun

DECODE_CALLS = 4
#: tokens a session has to have to go to count as running when the
#: engine stops: the stop must not race the session's own end
MIN_TO_GO = 64


class MlaProgram(drive_serve_moe.MoeProgram):
    """``MoeProgram`` that keeps the model's attention layers too and,
    when it stops the engine, the latent rows of live sessions."""

    def __init__(self, run):
        super().__init__(run)
        self.run, self.attns, self.live, self.kept = run, [], [], []

    def submit(self, prompt, max_new):
        fut, live = super().submit(prompt, max_new)
        self.live.append((np.asarray(prompt, np.int32), fut, live))
        return fut, live

    def shutdown(self):
        """``ServeProgram.shutdown`` that first keeps the rows
        ``served_latent_gap`` reads and then gives the page pools'
        memory back AT ONCE: whatever still names the engine, the
        reference's float32 layers at the slot's length need the room
        the pools held (7.3 of the chip's 16 GB in longdoc-reason-56)."""
        if self.model is not None:
            layers = list(self.model.model.layers)
            self.attns = [blk.self_attn for blk in layers]
            self.mlps = [blk.mlp for blk in layers]
        eng = self.engine
        if eng is not None:
            checked = self.run.setup_s is not None and not self.kept
            if checked:
                self.hold_sessions()
            eng.shutdown()          # no tick runs while the rows are read
            if checked:
                self.keep_rows()
            if eng.cache is not None:
                for pool in eng.cache.flat_pools():
                    if not pool.is_deleted():
                        pool.delete()
        drive_serve.ServeProgram.shutdown(self)
        used = [d.memory_stats() or {} for d in self.run.devices]
        self.run.say("after shutdown: " + ", ".join(
            f"{u.get('bytes_in_use', 0) / 1e9:.2f} GB in use"
            for u in used))

    def hold_sessions(self, wait_s=60.0):
        """Nothing is sent after the close, and the engine is about to
        stop under the sessions still running.  Where fewer than
        ``served_latent_gap.requests`` have ``MIN_TO_GO`` tokens to go —
        sessions shorter than the stop of a trace: the tiny presets —
        as many of the first prompts are sent again, each for as long an
        answer as its slot holds, and held until it has two tokens."""
        want = self.run.cell["served_latent_gap"]["requests"]
        going = sum(1 for _, fut, req in self.live
                    if req is not None and not fut.done()
                    and req.max_new_tokens - len(req.tokens) >= MIN_TO_GO)
        if going >= want:
            return
        room = self.run.cell["engine"]["max_seq_len"]
        held = [self.submit(prompt, room - prompt.size)
                for prompt, _, _ in self.live[:want - going]]
        deadline = time.perf_counter() + wait_s
        while any(len(req.tokens) < 2 and not fut.done()
                  for fut, req in held) and time.perf_counter() < deadline:
            time.sleep(self.run.cell["poll_ms"] * 1e-3)
        self.run.say(f"latent rows: {going} sessions had {MIN_TO_GO} tokens "
                     f"to go at the close; {len(held)} more sent and held")

    def keep_rows(self):
        """The rows of sessions that were live when the engine stopped
        (their futures fail with the shutdown's error and they keep
        their slots): ``(prompt length, the tokens the rows are of, {layer:
        rows})``, the longest session and others drawn from the seed.  A
        slot holds a row for every token it has consumed: the prompt (as
        far as its prefill came) and every served token but the last."""
        want = self.run.cell["served_latent_gap"]["requests"]
        cache = self.engine.cache
        live = []
        for prompt, fut, req in self.live:
            if req is None or req.slot is None or not fut.done() \
                    or type(fut.exception()).__name__ != \
                    "EngineShutdownError":
                continue
            ids = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
            n = min(int(cache.offsets[req.slot]), ids.size - 1)
            if n >= 2:
                live.append((req.slot, prompt.size, ids[:n]))
        live.sort(key=lambda t: -t[2].size)
        rest = live[1:]
        take = traffic.rng_for(self.run.seed, 10).permutation(
            len(rest))[:max(0, want - 1)]
        for slot, plen, ids in live[:1] + [rest[j] for j in take]:
            rows = {i: r[:ids.size] for i, r in
                    cache.read_latent(slot).items()}
            self.kept.append((plen, ids, rows))
        self.run.say(f"latent rows: {len(live)} sessions live at the stop, "
                     "kept the rows of " + ", ".join(
                         f"{ids.size} positions ({plen} of the prompt)"
                         for plen, ids, _ in self.kept))

    def finish(self):
        self.attns, self.kept = [], []
        super().finish()


def program_routed(mlp, n, piece):
    """The program's routed part for ``n`` [S, h] and the experts its
    OWN routing chose ([S, k]), ``piece`` positions a call."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    outs, sets = [], []
    for a in range(0, n.shape[0], piece):
        x = n[a:a + piece]
        with no_grad():
            y, _ = mlp.routed(Tensor(x[None]))
        outs.append(y._data_[0].astype(jnp.float32))
        idx, _ = mlp.route(x, mlp.gate.weight._data_,
                           mlp.gate.expert_bias._data_)
        sets.append(idx)
    return jnp.concatenate(outs), jnp.concatenate(sets)


def program_attention(run, attn, y, prompt_len, n):
    """The program's attention for the normed inputs ``y`` [pad, h]:
    (outputs of the chunk path [pad, h], zero past the last chunk that
    holds one of the ``n`` real positions; positions of the single-token
    path; its outputs there)."""
    from paddle_tpu.core.state import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving import PagedKVCache
    eng = run.cell["engine"]
    pad, piece = y.shape[0], eng["prefill_chunk_tokens"]
    rows = eng["num_slots"]
    cache = PagedKVCache(
        1, 1, pad, page_size=eng["page_size"], dtype=eng["cache_dtype"],
        layer_latents=attn.config.layer_latents()[:1])
    slot = cache.allocate(cache.pages_per_slot)
    pieces = -(-n // piece)
    cache.ensure_capacity(slot, min(pieces * piece, pad) - 1)
    chunks = []
    with no_grad():
        for a in range(0, pieces * piece, piece):
            cache.set_offset(slot, a)
            view = cache.layer_caches()[0]
            chunks.append(attn(Tensor(y[None, a:a + piece]),
                               cache=view)._data_[0])
        # the served part's positions, the last among them, ``rows`` a
        # call: every row reads the one slot's pages at its own offset
        served = np.arange(prompt_len, n)
        want = min(served.size, DECODE_CALLS * rows)
        pos = np.unique(served[np.linspace(0, served.size - 1, want)
                               .round().astype(int)])
        pos = np.concatenate([pos, np.full(-pos.size % rows, n - 1)])
        steps = []
        for a in range(0, pos.size, rows):
            at = pos[a:a + rows]
            table = np.repeat(cache.table[slot][None], rows, axis=0)
            view = cache.views_over(
                cache.flat_pools(), jnp.asarray(table),
                jnp.asarray(at.astype(np.int32)))[0]
            # finished before the next eager op starts: the Pallas
            # interpreter's callbacks (a CPU rehearsal) cannot run
            # beside one
            steps.append(jax.block_until_ready(
                attn(Tensor(y[at][:, None]), cache=view)._data_[:, 0]))
    chunks = jnp.concatenate(chunks).astype(jnp.float32)
    chunks = jnp.pad(chunks, ((0, pad - chunks.shape[0]), (0, 0)))
    return chunks, pos, jnp.concatenate(steps).astype(jnp.float32)


def _round_e4m3(a):
    """``a`` as a float8 (e4m3) cache with one scale a row would hold
    it.  ``reduce_precision``, not a pair of converts: on the chip XLA
    drops a widening convert of a narrowing one (call 2 of PR 34 read
    3e-7 for ``refc._fake_quant_fp8`` here)."""
    scale = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 240.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jax.lax.reduce_precision(a / scale, 4, 3) * scale


#: share of positions that may choose other experts than the reference
#: before the routed gap is taken over every position
FLIP_SHARE = 0.1


def _routed_gap(got, want, sets_got, sets_want, n):
    """``drive_serve_moe._gap`` with a tighter rule for what an empty
    comparison is: the gap over ALL ``n`` positions once more than
    ``FLIP_SHARE`` of them chose other experts (rounding flips a few in
    a hundred, a selection bias left out many more)."""
    gap, flips = drive_serve_moe._gap(got, want, sets_got, sets_want, n)
    if int(flips) > FLIP_SHARE * n:
        gap, _ = drive_serve_moe._gap(got, want, sets_want, sets_want, n)
    return gap, flips


@jax.jit
def _rel(got, want, use):
    num = jnp.sum(jnp.where(use[:, None], jnp.square(got - want), 0.0))
    den = jnp.sum(jnp.where(use[:, None], jnp.square(want), 0.0))
    return jnp.sqrt(num / den)


def layer_gaps(run, weights, attns, mlps, prompt, output):
    """(routed_gap, latent_gap) of the program's layers on the
    reference's layer inputs for one request."""
    from paddle_tpu.utils import monitor
    arch = refrun.arch_module(run.config["reference"])
    cfg = run.model_cfg
    pad = run.cell["engine"]["max_seq_len"]
    piece = run.cell["engine"]["prefill_chunk_tokens"]
    dtype = jnp.dtype(run.cell["weights_dtype"])
    c_routed = getattr(run, "control_routed", None)
    c_latent = getattr(run, "control_latent", None)
    mm = refc.mm_f32
    embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
    attention = jax.jit(lambda x, w: arch.attention_part(x, w, cfg, mm))
    routed = jax.jit(lambda x, w: arch.routed_part(x, w, cfg, mm))
    forward = jax.jit(lambda x, w: arch.feed_forward(x, w, cfg, mm))
    low_routed = jax.jit(lambda x, w: arch.routed_part(
        x, w, cfg, mm, refc.MATMULS[c_routed])) if c_routed else None
    low_latent = {}
    if c_latent:
        if c_latent != "fp8":
            raise ValueError(f"latent control {c_latent!r}: the rows are "
                             "rounded to float8 (e4m3) and nothing else")
        low = refc.MATMULS[c_latent]
        low_latent = {
            "rows": jax.jit(lambda x, w: arch.attention_part(
                x, w, cfg, mm, row_round=_round_e4m3)[1]),
            "kvb": jax.jit(lambda x, w: arch.attention_part(
                x, w, cfg, mm, kvb_mm=low)[1])}
    n = prompt.size + output.size
    ids = np.zeros(pad, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    x = embed({k: weights[k] for k in arch.EMBED_NAMES},
              jnp.asarray(ids)[None])
    real = jnp.arange(pad) < n
    r_gaps, flips, c_r = [], [], []
    l_chunk, l_step, c_l = [], [], {k: [] for k in low_latent}
    lanes0 = monitor.all_stats()
    for i, (attn, mlp) in enumerate(zip(attns, mlps)):
        w = refrun._layer_weights(arch, cfg, weights, i)
        y, want = attention(x, w)
        got, pos, got_step = program_attention(
            run, attn, y[0].astype(dtype), prompt.size, n)
        l_chunk.append(float(_rel(got, want[0], real)))
        l_step.append(float(_rel(got_step, want[0][pos],
                                 jnp.ones(pos.size, bool))))
        for name, fn in low_latent.items():
            c_l[name].append(float(_rel(fn(x, w)[0], want[0], real)))
        mid = (x[0] + want, x[1])
        if hasattr(mlp, "routed"):
            y, want, sets = routed(mid, w)
            got, sets_got = program_routed(mlp, y[0].astype(dtype), piece)
            g, f = _routed_gap(got, want[0], sets_got, sets[0], n)
            r_gaps.append(float(g))
            flips.append(int(f))
            if low_routed is not None:
                _, c_out, c_sets = low_routed(mid, w)
                c_r.append(float(_routed_gap(
                    c_out[0], want[0], c_sets[0], sets[0], n)[0]))
        x = forward(mid, w)
    names = ("pallas.expert_gmm.kernel", "pallas.expert_gmm.xla_lane",
             "pallas.mla_decode.kernel", "pallas.mla_decode.xla_lane")
    lanes = {k: monitor.all_stats().get(k, 0) - lanes0.get(k, 0)
             for k in names}
    fmt = lambda gs: " ".join(f"{g:.4g}" for g in gs)   # noqa: E731
    said = (f"over {n} positions; routed part, gap by expert layer: "
            f"{fmt(r_gaps)}; positions that chose other experts: "
            + " ".join(str(f) for f in flips)
            + f"; latent attention, gap by layer, chunk path: "
            f"{fmt(l_chunk)}; single-token path ({DECODE_CALLS} calls): "
            f"{fmt(l_step)}; traced: "
            + ", ".join(f"{v} {k.split('.', 1)[1]}"
                        for k, v in lanes.items()))
    if c_r:
        said += f"; control {c_routed} experts: {fmt(c_r)}"
        run.records["control_routed_gap"] = max(c_r)
    for name, gs in c_l.items():
        said += f"; control {c_latent} {name}: {fmt(gs)}"
        run.records["control_latent_gap_" + name] = max(gs)
    run.say(said)
    run.records["routed_flips"] = flips
    run.records["latent_gap_chunk"] = max(l_chunk)
    run.records["latent_gap_step"] = max(l_step)
    run.records["mla_decode_lanes"] = {
        k: v for k, v in lanes.items() if "mla" in k}
    return max(r_gaps), max(l_chunk + l_step)


class RowReference:
    """The plain forward, layer by layer as ``ServeReference`` runs it,
    handing back the latent rows each layer caches.  ``mm`` is every
    product's precision; ``row_round``, ``kvb_mm`` and ``expert_mm``
    lower one part alone (the controls)."""

    def __init__(self, arch_name, cfg, mm=refc.mm_f32, row_round=None,
                 kvb_mm=None, expert_mm=None):
        self.arch = arch = refrun.arch_module(arch_name)
        self.cfg = cfg
        self._embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
        self._rows = jax.jit(lambda x, w: arch.latent_rows(
            x, w, cfg, mm, row_round)[0])
        self._layer = jax.jit(lambda x, w: arch.feed_forward(
            arch.attend(x, w, cfg, mm, row_round, kvb_mm), w, cfg, mm,
            expert_mm))

    def rows(self, params, ids):
        """ids [S] int32 (already padded) -> each layer's [S, width]
        float32, one after another (the last layer's rows need none of
        its attention)."""
        arch, cfg = self.arch, self.cfg
        x = self._embed({n: params[n] for n in arch.EMBED_NAMES},
                        jnp.asarray(ids)[None])
        for i in range(cfg["num_layers"]):
            w = refrun._layer_weights(arch, cfg, params, i)
            yield self._rows(x, w)
            if i + 1 < cfg["num_layers"]:
                x = self._layer(x, w)


@jax.jit
def _position_gaps(got, want):
    """[S]: the norm of ``got`` minus ``want`` over ``want``'s, a row
    each."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(got - want), -1)
                    / jnp.sum(jnp.square(want), -1))


def part_medians(gaps, plen):
    """The median position's gap over the prompt's rows and over the
    answer's (None where a part has no row)."""
    parts = gaps[:plen], gaps[plen:]
    return [float(np.median(p)) if p.size else None for p in parts]


#: the controls of ``served_latent_gap``: which part of the reference is
#: computed in the lower precision
ROW_CONTROLS = {
    "whole": lambda low: {"mm": low},
    "rows": lambda low: {"row_round": _round_e4m3},
    "experts": lambda low: {"expert_mm": low}}


def served_latent_gap(run, weights, kept):
    """The widest median gap of the engine's own latent rows to the
    reference's, over ``kept`` (``MlaProgram.keep_rows``)."""
    name, cfg = run.config["reference"], run.model_cfg
    pad = run.cell["engine"]["max_seq_len"]
    control = getattr(run, "control_rows", None)
    if control not in (None, "fp8"):
        raise ValueError(f"row control {control!r}: float8 (e4m3) and "
                         "nothing else")
    ref = RowReference(name, cfg)
    lows = {k: RowReference(name, cfg, **kw(refc.MATMULS[control]))
            for k, kw in ROW_CONTROLS.items()} if control else {}
    worst, c_worst = 0.0, {k: 0.0 for k in lows}
    fmt = lambda ms: "/".join("-" if m is None else f"{m:.4g}"  # noqa: E731
                              for m in ms)
    for k, (plen, ids, got) in enumerate(kept):
        n = ids.size
        padded = np.zeros(pad, np.int32)
        padded[:n] = ids
        want = [np.asarray(r[:n]) for r in ref.rows(weights, padded)]
        by_layer, tails = [], []
        for i, w in enumerate(want):
            gaps = np.asarray(_position_gaps(jnp.asarray(got[i]), w))
            med = part_medians(gaps, plen)
            by_layer.append(med)
            tails.append(float(np.quantile(gaps, 0.9)))
            worst = max([worst] + [m for m in med if m is not None])
        said = (f"latent rows of {n} positions ({plen} of the prompt), "
                "median gap by layer, prompt/answer: "
                + " ".join(fmt(m) for m in by_layer)
                + "; the position nine tenths lie under: "
                + " ".join(f"{t:.4g}" for t in tails))
        # the controls read the longest session alone: a forward each
        for c, low in (lows.items() if k == 0 else ()):
            meds = [part_medians(np.asarray(_position_gaps(r[:n], w)), plen)
                    for r, w in zip(low.rows(weights, padded), want)]
            c_worst[c] = max(m for med in meds for m in med
                             if m is not None)
            said += f"; control {control} {c}: " + " ".join(
                fmt(m) for m in meds)
        run.say(said)
    for c, v in c_worst.items():
        run.records["control_served_latent_gap_" + c] = v
    return worst


def measure(run, prog_factory=MlaProgram):
    progs = []

    def factory(run):
        progs.append(prog_factory(run))
        return progs[0]

    try:
        drive_serve.measure(run, prog_factory=factory)
        prog = progs[0]
        if not prog.kept:
            raise RuntimeError("no session was live when the engine "
                               "stopped: there are no rows to compare")
        numbers = {name: value for name, (value, _) in run.compared.items()}
        t = time.perf_counter()
        numbers["served_latent_gap"] = served_latent_gap(
            run, prog.weights, prog.kept)
        run.say(f"latent rows: {len(prog.kept)} sessions against the "
                f"reference in {time.perf_counter() - t:.1f}s")
        prog.kept = []
        t = time.perf_counter()
        numbers["routed_gap"], numbers["latent_gap"] = layer_gaps(
            run, prog.weights, prog.attns, prog.mlps,
            *prog.longest_finished())
        run.say(f"layer gaps: one request through {len(prog.attns)} "
                f"layers in {time.perf_counter() - t:.1f}s")
        run.judge(numbers)
    finally:
        for prog in progs:
            prog.finish()
