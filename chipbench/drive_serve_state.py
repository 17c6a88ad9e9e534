"""The serving cells of a model that keeps a recurrent state beside its
pages: ``drive_serve``'s window and its ``served_logit_gap``, and one
number more, ``state_gap``.

A served token tells little of the state's precision: a state kept in
bfloat16 moves the argmax hardly more than bfloat16 weights do (PERF.md
section 2).  The state itself tells.  Nothing is sent after the close,
so a slot whose tenant finishes then stays empty, its state rows as the
tenant's last tick wrote them (``RequestOutput.slot``,
``PagedKVCache.read_state``).  ``check_requests`` such rows are compared
with the state the plain reference holds after the same tokens: the
prompt and every served token but the last, which no tick consumed.

``state_gap`` is the widest, over those requests and the first
``state_gap.layers`` recurrent layers, of the Frobenius norm of program
minus reference over the reference's, a layer's whole state at a time.
The first layers, because the activations that feed a state carry more
roundings of their own the deeper the layer lies (PERF.md section 2:
0.2 % in the first layer, 2 % in the last, where a bfloat16 state's own
2-3 % would drown)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import drive_serve
from reference import common as refc
from reference import run as refrun

#: the lower-precision controls of the state's type (tests/control_state.py)
KEEP = {"bfloat16": lambda s: jax.lax.reduce_precision(s, 8, 7)}


class StateProgram(drive_serve.ServeProgram):
    """``ServeProgram`` that, before it shuts the engine down, keeps the
    state rows of requests that finished and whose slots stayed empty."""

    def __init__(self, run):
        super().__init__(run)
        self.run, self.sent, self.kept = run, [], []

    def submit(self, prompt, max_new):
        fut, live = super().submit(prompt, max_new)
        self.sent.append((np.asarray(prompt, np.int32), fut, live))
        return fut, live

    def shutdown(self):
        if self.engine is not None and self.run.setup_s is not None \
                and not self.kept:
            self.keep_states()
        super().shutdown()

    def keep_states(self, wait_s=60.0):
        """Nothing is sent after the close, so a slot whose tenant
        finishes now stays empty.  Wait for ``check_requests`` more to
        finish, stop the engine, and keep the rows of the slots that no
        unfinished request holds, with their last tenant's tokens (the
        longest first; admission is first come, first served, so a
        slot's last tenant is the last sent)."""
        want = self.run.cell["check_requests"]
        waiting = [f for _, f, _ in self.sent if not f.done()]
        deadline = time.perf_counter() + wait_s
        while sum(f.done() for f in waiting) < min(want, len(waiting)) \
                and time.perf_counter() < deadline:
            time.sleep(0.005)
        self.engine.shutdown()          # no tick runs while the rows are read
        held, last = set(), {}
        for prompt, fut, req in self.sent:
            if fut.exception() is not None:
                held.add(getattr(req, "slot", None))
            elif fut.result().slot is not None:
                out = fut.result()
                last[out.slot] = (out.slot, prompt,
                                  np.asarray(out.output_ids, np.int32))
        free = sorted((t for t in last.values() if t[0] not in held),
                      key=lambda t: -(t[1].size + t[2].size))
        cache = self.engine.cache
        self.kept = [(prompt, out, cache.read_state(slot))
                     for slot, prompt, out in free[:want]]
        self.run.say(f"state: kept the rows of {len(self.kept)} finished "
                     f"requests whose slots stayed empty")


class StateReference:
    """The plain forward, layer by layer as ``ServeReference`` runs it,
    as far as the first ``layers`` recurrent layers, whose states after
    ``n`` tokens it hands back."""

    def __init__(self, arch_name, cfg, layers, keep=None):
        self.arch = arch = refrun.arch_module(arch_name)
        self.cfg, self.layers = cfg, layers
        self._embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
        self._layer = jax.jit(lambda x, w, n: arch.layer_and_state(
            x, w, cfg, refc.mm_f32, n, keep))

    def states(self, params, ids, n):
        """{layer: [H, P, N] float32} after the first ``n`` of ``ids``."""
        arch, cfg = self.arch, self.cfg
        x = self._embed({k: params[k] for k in arch.EMBED_NAMES},
                        jnp.asarray(ids)[None])
        out = {}
        for i in range(cfg["num_layers"]):
            x, state = self._layer(
                x, refrun._layer_weights(arch, cfg, params, i), jnp.int32(n))
            if state is not None:
                out[i] = np.asarray(state[0])
                if len(out) == self.layers:
                    break
        return out


def layer_gaps(got, want):
    """{layer: relative Frobenius gap of ``got`` to ``want``}."""
    norm = lambda a: float(np.sqrt(np.square(  # noqa: E731
        a.astype(np.float64)).sum()))
    return {i: norm(got[i] - want[i]) / norm(want[i]) for i in want}


def state_gap(run, weights, kept):
    arch_name, cfg = run.config["reference"], run.model_cfg
    layers = run.cell["state_gap"]["layers"]
    pad = run.cell["engine"]["max_seq_len"]
    control = getattr(run, "control_state", None)
    ref = StateReference(arch_name, cfg, layers)
    low = StateReference(arch_name, cfg, layers, KEEP[control]) \
        if control else None
    worst, c_worst = 0.0, 0.0
    for prompt, out, got in kept:
        ids = np.zeros(pad, np.int32)
        n = prompt.size + out.size - 1      # the last token fed no tick
        ids[:n] = np.concatenate([prompt, out[:-1]])
        want = ref.states(weights, ids, n)
        gaps = layer_gaps({i: got[i]["ssm_state"] for i in want}, want)
        worst = max(worst, max(gaps.values()))
        said = f"state after {n} tokens, gap by layer: " + " ".join(
            f"{g:.4g}" for g in gaps.values())
        if low is not None:
            c_gaps = layer_gaps(low.states(weights, ids, n), want)
            c_worst = max(c_worst, max(c_gaps.values()))
            said += f"; control {control}: " + " ".join(
                f"{g:.4g}" for g in c_gaps.values())
        run.say(said)
    if low is not None:
        run.say(f"control state {control}: state_gap {c_worst:.6g} "
                f"(program {worst:.6g})")
        run.records["control_state_gap"] = c_worst
    return worst


def measure(run):
    progs = []

    def factory(run):
        progs.append(StateProgram(run))
        return progs[0]

    drive_serve.measure(run, prog_factory=factory)
    prog = progs[0]
    if not prog.kept:
        raise RuntimeError("no finished request's slot stayed empty: "
                           "there is no state to compare")
    numbers = {name: value for name, (value, _) in run.compared.items()}
    t = time.perf_counter()
    numbers["state_gap"] = state_gap(run, prog.weights, prog.kept)
    run.say(f"state: {len(prog.kept)} requests against the reference in "
            f"{time.perf_counter() - t:.1f}s")
    run.judge(numbers)
