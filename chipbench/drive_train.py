"""The training cells: ``CompiledTrainStep.__call__`` on a fresh seeded
batch every step.

Set-up builds ONE compiled step with its state, warms its shapes, puts
the state back to the seed's, drives it through its first three steps
with the window's own call and feed (their losses, the first gradient
read back from AdamW's first moment, and the parameters' change are
what ``correct`` compares), and hands the same object to the window."""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

import compare
import traffic
from reference import common as refc
from reference import run as refrun

CHECK_STEPS = 3


class TrainProgram:
    """The compiled step with its state, and the two calls the window
    makes: ``feed`` and ``step``."""

    def __init__(self, run):
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.framework.train_step import CompiledTrainStep
        self.paddle = paddle
        cfg, cell = run.model_cfg, run.cell
        arch = importlib.import_module("program." + run.config["program"])
        common = importlib.import_module("program.common")
        self.common = common
        self.spec = refrun.arch_module(run.config["reference"]) \
            .weight_spec(cfg)
        self.amp = cell["compute_dtype"] == "bfloat16"
        self.param_dtype = jnp.dtype(cell["parameter_dtype"])
        with paddle.amp.auto_cast(enable=self.amp, level="O2",
                                  dtype="bfloat16"):
            self.model = arch.build(cfg, cell["parameter_dtype"])
        common.drop_weights(self.model)
        common.install_weights(
            self.model, refc.make_weights(self.spec, run.seed,
                                          self.param_dtype))
        o = run.optimizer
        self.opt = paddle.optimizer.AdamW(
            o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
            epsilon=o["epsilon"], weight_decay=o["weight_decay"],
            parameters=self.model.parameters())
        self.cstep = CompiledTrainStep(
            self._forward, self.opt, network=self.model,
            eager_step=self._eager_step)
        self.run = run

    def _forward(self, x, y):
        with self.paddle.amp.auto_cast(enable=self.amp, level="O2",
                                       dtype="bfloat16"):
            _, loss = self.model(x, labels=y)
        return loss

    def _eager_step(self, x, y, update=True):
        loss = self._forward(x, y)
        loss.backward()
        self.opt.step()
        self.opt.clear_grad()
        return loss

    # -- the window's own call and feed --------------------------------
    def feed(self, batch):
        to = self.paddle.to_tensor
        return to(batch[:, :-1]), to(batch[:, 1:])

    def step(self, x, y):
        return self.cstep(x, y, update=True)

    # -- set-up ---------------------------------------------------------
    def warm_up(self, batches):
        """The eager call, the discovery and the first compile on one
        short row (the eager AdamW step holds the old and the new state
        side by side, so it gets no activations to speak of), then the
        full batch's compile."""
        import jax
        first = next(batches)
        short = first[:1, :self.run.cell["warm_seq"] + 1]
        for _ in range(2):
            loss = self.step(*self.feed(short))
        loss = self.step(*self.feed(first))
        jax.block_until_ready(loss._data_)
        if not self.cstep.compiled:
            raise RuntimeError("the train step fell back to eager: "
                               f"{self.cstep.fallback_reason}")

    def reset_to_seed(self):
        """Parameters back to the seed's, moments and step count to
        nought: the same object, as if no warm-up had run."""
        import jax.numpy as jnp
        self.common.install_weights(
            self.model, refc.make_weights(self.spec, self.run.seed,
                                          self.param_dtype))
        opt = self.opt
        for name in ("moment1", "moment2"):
            for t in opt._state[name]:
                if t is not None:
                    t._data_ = jnp.zeros_like(t._data_)
        if any(t is not None for t in opt._state.get("master", [])):
            raise RuntimeError("the optimizer keeps master weights; the "
                               "cell states float32 parameters")
        opt._step_tensor._data_ = jnp.zeros((), jnp.float32)
        opt._step_count = 0

    def grad1_norms(self):
        """Per leaf, the norm of the first gradient as AdamW got it:
        after one step from zero moments, moment1 = (1 - beta1) g."""
        names = {id(p): n for n, p in self.model.named_parameters()}
        m1 = {names[id(p)]: t._data_ for p, t in
              zip(self.opt._parameter_list, self.opt._state["moment1"])}
        k = 1.0 / (1.0 - self.run.optimizer["beta1"])
        return {n: float(v) * k for n, v in refc.leaf_norms(m1).items()}

    def change_norms(self):
        """(per-leaf norms, the vectors' changes whole)."""
        return refc.split_vectors(refc.change_norms(
            self.spec, self.run.seed, self.param_dtype,
            self.common.read_weights(self.model)))

    def free(self):
        self.cstep = self.opt = self.model = None
        gc.collect()


def first_steps(prog, batches):
    """Steps 1..3 through the window's own call and feed; returns what
    ``correct`` compares and the batches the reference has to follow."""
    fed, losses, g1 = [], [], None
    for k in range(1, CHECK_STEPS + 1):
        batch = next(batches)
        fed.append(batch)
        loss = prog.step(*prog.feed(batch))
        losses.append(float(loss))
        if k == 1:
            g1 = prog.grad1_norms()
    change, change_vec = prog.change_norms()
    return {"losses": losses, "grad1_norm": g1, "change_norm": change,
            "change_vec": change_vec}, fed


def measure(run, prog_factory=TrainProgram):
    import jax
    from paddle_tpu.utils import monitor
    prog = prog_factory(run)
    vocab = run.model_cfg["vocab_size"]
    prog.warm_up(traffic.train_batches(run.mix, run.seed ^ 0x5EED, vocab))
    prog.reset_to_seed()
    batches = traffic.train_batches(run.mix, run.seed, vocab)
    got, fed = first_steps(prog, batches)
    run.end_of_setup()

    mix = run.mix
    every = run.cell["loss_every"]
    tokens_per_step = mix["batch"] * mix["seq"]
    reg0 = monitor.all_stats()
    read = []
    steps = 0
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            with run.span("chipbench:feed"):
                x, y = prog.feed(next(batches))
            with run.span("chipbench:step"):
                loss = prog.step(x, y)
            steps += 1
            if steps % every == 0:
                with run.span("chipbench:read_loss"):
                    read.append(float(loss))
        with run.span("chipbench:last_step"):
            jax.block_until_ready(loss._data_)
        elapsed = time.perf_counter() - t0
    reg1 = monitor.all_stats()
    read.append(float(loss))
    run.memory_peak()
    failed = sum(1 for v in read if not np.isfinite(v))

    run.say(f"window: {steps} steps of {tokens_per_step} tokens in "
            f"{elapsed:.3f}s; losses read {len(read)}")
    run.metrics["train_tokens_per_s"] = \
        steps * tokens_per_step / elapsed / run.chips
    run.records.update(
        steps=steps, tokens=steps * tokens_per_step, elapsed_s=elapsed,
        registry={k: reg1.get(k, 0) - reg0.get(k, 0) for k in reg1
                  if isinstance(reg1[k], (int, float))})

    prog.free()
    t_ref = time.perf_counter()
    ref = refrun.TrainReference(
        run.config["reference"], run.model_cfg, run.optimizer
    ).follow(prog.spec, run.seed, prog.param_dtype, fed)
    run.say(f"reference: {CHECK_STEPS} steps in "
            f"{time.perf_counter() - t_ref:.1f}s")
    run.records["reference"], run.records["fed"] = ref, fed
    run.records["spec"], run.records["param_dtype"] = \
        prog.spec, prog.param_dtype
    numbers, notes = compare.train_numbers(got, ref)
    run.say(f"compared leaves: {notes}")
    run.say(f"losses program {got['losses']} reference {ref['losses']}")
    run.judge(numbers)
    run.attempted, run.failed = steps, failed
