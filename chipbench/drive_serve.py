"""The serving cells: ``serving.Engine.submit`` -> tokens -> result.

One client thread offers the load (open loop: at the due times of the
plan, whatever the engine does; closed loop: each client's next request
when its last completes) and watches every request's tokens arrive.  The
engine has no streaming call, so the client reads the length of each
live request's token list every ``poll_ms`` on its own clock: that is
when a streaming client would have seen the token (PERF.md section 7
asks the program for a public stream)."""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

import traffic
from reference import common as refc
from reference import run as refrun


class ServeProgram:
    """The engine under test and the three calls the client makes."""

    def __init__(self, run):
        import jax.numpy as jnp
        from paddle_tpu.serving import Engine, ServingConfig
        cfg, cell = run.model_cfg, run.cell
        arch = importlib.import_module("program." + run.config["program"])
        common = importlib.import_module("program.common")
        dtype = cell["weights_dtype"]
        self.model = arch.build(cfg, dtype)
        common.drop_weights(self.model)
        spec = refrun.arch_module(run.config["reference"]).weight_spec(cfg)
        self.weights = refc.make_weights(spec, run.seed, jnp.dtype(dtype))
        common.install_weights(self.model, self.weights)
        self.engine = Engine(self.model, ServingConfig(**cell["engine"]))
        self.engine.start()

    def submit(self, prompt, max_new):
        """(future, the live request whose ``tokens`` list grows)."""
        fut = self.engine.submit(prompt, max_new_tokens=max_new)
        return fut, self.engine._pending.get(fut.request_id)

    @staticmethod
    def tokens_so_far(live):
        return len(live.tokens)

    def shutdown(self):
        """Stop the scheduler and free pages and model; the weights stay
        for the reference."""
        if self.engine is not None:
            self.engine.shutdown()
            self.engine.cache = None
            self.engine = None
        self.model = None
        gc.collect()


class _Req:
    __slots__ = ("plan", "fut", "live", "due", "sent", "seen", "first",
                 "last", "client", "done", "output", "error")

    def __init__(self, plan, due, client=None):
        self.plan, self.due, self.client = plan, due, client
        self.fut = self.live = self.first = self.last = None
        self.sent = None
        self.seen = 0
        self.done = False
        self.output = self.error = None


class Client:
    """Offers the plan and records, on the host's clock, when each token
    of each request was seen."""

    def __init__(self, run, prog, plan):
        self.run, self.prog, self.plan = run, prog, plan
        self.mix = run.mix
        self.poll_s = run.cell["poll_ms"] * 1e-3
        self.live = []
        self.all = []
        self.gaps = []              # (time seen, gap in s) of every token
        self.tokens_at = []         # (time seen, context length)
        self.late = []
        self.refused = 0

    def send(self, plan, due, max_new, client=None):
        r = _Req(plan, due, client)
        self.all.append(r)
        r.sent = time.perf_counter()
        try:
            with self.run.span("chipbench:submit"):
                r.fut, r.live = self.prog.submit(plan["prompt"], max_new)
        except Exception as e:      # refused: counts as the worst
            r.error, r.done = e, True
            self.refused += 1
            return r
        self.live.append(r)
        return r

    def poll(self, now):
        """One look at every live request; returns those just done."""
        finished = []
        for r in self.live:
            n = self.prog.tokens_so_far(r.live) if r.live is not None else 0
            if r.fut.done():
                try:
                    r.output = r.fut.result().output_ids
                    n = len(r.output)
                except Exception as e:
                    r.error = e
                r.done = True
                finished.append(r)
            if n > r.seen:
                if r.first is None:
                    r.first = now
                else:
                    self.gaps.append((now, now - r.last))
                    self.gaps.extend((now, 0.0)
                                     for _ in range(n - r.seen - 1))
                plen = len(r.plan["prompt"])
                self.tokens_at.extend((now, plen + j)
                                      for j in range(r.seen, n))
                r.seen, r.last = n, now
        if finished:
            self.live = [r for r in self.live if not r.done]
        return finished

    def fill(self):
        """Closed loop, set-up: every client's first request, cut to its
        staggered length, until each has its first token."""
        n = self.mix["clients"]
        for c in range(n):
            p = self.plan[c]
            self.send(p, None, p.get("first_new", p["max_new"]), client=c)
        self.next_plan = n
        deadline = time.perf_counter() + 600
        while any(r.first is None and not r.done for r in self.all):
            self._refill(self.poll(time.perf_counter()), None)
            if time.perf_counter() > deadline:
                raise RuntimeError("the slots did not fill in 600 s")
            time.sleep(self.poll_s)

    def _refill(self, finished, t_close):
        for r in finished:
            if r.client is None:
                continue
            if t_close is not None and time.perf_counter() >= t_close:
                continue
            p = self.plan[self.next_plan % len(self.plan)]
            self.next_plan += 1
            self.send(p, None, p["max_new"], client=r.client)

    def run_window(self, seconds):
        """Drive the window; returns (t0, t_close)."""
        open_loop = self.mix["loop"] == "open"
        t0 = time.perf_counter()
        t_close = t0 + seconds
        i = 0
        grace = t_close + 60.0
        while True:
            now = time.perf_counter()
            if now >= t_close:
                waiting = open_loop and any(
                    r.first is None and not r.done for r in self.all)
                if not waiting or now >= grace:
                    break
            if open_loop:
                while i < len(self.plan) and now < t_close and \
                        t0 + self.plan[i]["due_s"] <= now:
                    due = t0 + self.plan[i]["due_s"]
                    self.late.append(now - due)
                    self.send(self.plan[i], due, self.plan[i]["max_new"])
                    i += 1
            with self.run.span("chipbench:poll"):
                finished = self.poll(now)
            if not open_loop:
                self._refill(finished, t_close)
            time.sleep(self.poll_s)
        return t0, t_close


def _p95(xs):
    xs = np.sort(np.asarray(xs, float))
    return float(xs[min(len(xs) - 1, int(np.ceil(0.95 * len(xs))) - 1)])


def check_served(run, weights, sample, control=None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over ``sample``.  ``control`` names a lower
    precision (tests/control.py): the widest gap of the token that the
    reference computed in it puts first, at the same positions, is said
    on an earlier line."""
    pad = run.cell["engine"]["max_seq_len"]
    ref = refrun.ServeReference(run.config["reference"], run.model_cfg)
    low = refrun.ServeReference(run.config["reference"], run.model_cfg,
                                control) if control else None
    worst, n_tok, flips, c_worst = 0.0, 0, 0, 0.0
    for r in sample:
        got = refrun.served_gaps(ref, weights, r.plan["prompt"], r.output,
                                 pad, low)
        worst = max(worst, got["gap"])
        n_tok += got["tokens"]
        flips += got["flips"]
        c_worst = max(c_worst, got.get("control_gap", 0.0))
    if control:
        run.say(f"control {control}: served_logit_gap {c_worst:.6g} "
                f"(program {worst:.6g})")
        run.records["control_gap"] = c_worst
    return worst, n_tok, flips


def pick_sample(run, finished):
    """A sample of the finished requests drawn from the seed, with the
    longest in it."""
    k = run.cell["check_requests"]
    finished = sorted(finished, key=lambda r: -(len(r.plan["prompt"])
                                                + len(r.output)))
    rng = traffic.rng_for(run.seed, 9)
    rest = finished[1:]
    take = rng.permutation(len(rest))[:max(0, k - 1)]
    return finished[:1] + [rest[j] for j in take]


def measure(run, prog_factory=ServeProgram):
    from paddle_tpu.utils import monitor
    prog = prog_factory(run)
    mix, cfg = run.mix, run.model_cfg
    plan = traffic.serve_plan(mix, run.seed, run.seconds, cfg["vocab_size"])
    try:
        client = Client(run, prog, plan)
        # warm-up: a prompt longer than one prefill chunk and a few
        # decode ticks compile every program the window will run
        chunk = run.cell["engine"]["prefill_chunk_tokens"]
        warm = traffic.rng_for(run.seed, 8).integers(
            0, cfg["vocab_size"], chunk + chunk // 2 + 1, dtype=np.int32)
        fut, _ = prog.submit(warm, 4)
        fut.result(timeout=1500)
        if mix["loop"] == "closed":
            client.fill()
        run.end_of_setup()

        reg0 = monitor.all_stats()
        with run.window():
            t0, t_close = client.run_window(run.seconds)
        reg1 = monitor.all_stats()
        run.memory_peak()
    finally:
        prog.shutdown()

    in_window = [r for r in client.all
                 if not (r.done and (r.last or r.sent) < t0)]
    seconds = t_close - t0
    seen = [(t, c) for t, c in client.tokens_at if t0 <= t < t_close]
    tokens = len(seen)
    gaps = [g for t, g in client.gaps if t0 <= t < t_close]
    run.metrics["serve_tokens_per_s"] = tokens / seconds
    run.metrics["tpot_p95_ms"] = _p95(gaps) * 1e3
    failed = [r for r in client.all if r.error is not None
              and (r.due is not None or r.sent < t_close)]
    # a request cut off by the shutdown after the close did not fail
    failed = [r for r in failed
              if type(r.error).__name__ != "EngineShutdownError"]
    if mix["loop"] == "open":
        end = time.perf_counter()
        ttft = [(r.first if r.first is not None and r.error is None
                 else end) - r.due for r in client.all]
        run.metrics["ttft_p95_ms"] = _p95(ttft) * 1e3
        run.say(f"generator lateness: max {max(client.late) * 1e3:.2f} ms"
                f", p95 {_p95(client.late) * 1e3:.2f} ms over "
                f"{len(client.late)} requests")
    done = [r for r in client.all
            if r.output is not None and r.last is not None and r.last >= t0]
    run.say(f"window: {len(in_window)} requests live, {len(done)} finished,"
            f" {tokens} tokens in {seconds:.3f}s, {len(gaps)} gaps, "
            f"{client.refused} refused, {len(failed)} failed")

    # useful work in the window, for the whole step's share of the peak
    prefilled = [len(r.plan["prompt"]) for r in client.all
                 if r.first is not None and t0 <= r.first < t_close]
    prefill_tok = sum(prefilled)
    prefill_ctx = sum(n * n / 2 for n in prefilled)
    decode_ctx = float(sum(c for _, c in seen))
    run.records.update(
        seconds=seconds, tokens=tokens, prefill_tokens=prefill_tok,
        prefill_ctx=prefill_ctx, decode_ctx=decode_ctx,
        registry={k: reg1.get(k, 0) - reg0.get(k, 0) for k in reg1
                  if isinstance(reg1[k], (int, float))})

    if not done:
        raise RuntimeError("no request finished in the window: the cell "
                           "is too short for its longest requests")
    t_ref = time.perf_counter()
    sample = pick_sample(run, done)
    worst, n_tok, flips = check_served(run, prog.weights, sample,
                                       getattr(run, "control", None))
    run.say(f"reference: {len(sample)} requests, {n_tok} served tokens, "
            f"{flips} not the reference's first choice, in "
            f"{time.perf_counter() - t_ref:.1f}s")
    run.judge({"served_logit_gap": worst})
    run.attempted = len(in_window)
    run.failed = len(failed)
