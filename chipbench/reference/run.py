"""Drive a plain reference at the timed sizes, layer by layer so that it
fits beside nothing else on the chip.

Serving: one full forward over prompt + served tokens per sampled
request, read as logit gaps.  Training: loss, gradient and AdamW for the
first steps, row by row with each layer recomputed in the backward."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from .common import MATMULS, cross_entropy


def arch_module(name):
    return importlib.import_module(f"{__package__}.{name}")


def _layer_weights(arch, cfg, params, i):
    out = {}
    for name in arch.layer_names(cfg, i):
        # suffix after "<stack>.<i>."
        suffix = name.split(f".{i}.", 1)[1]
        out[suffix] = params[name]
    return out


class ServeReference:
    """Logits of the plain forward at every position of one padded
    sequence; jitted per layer, so one small program serves every layer
    and every request of a cell."""

    def __init__(self, arch_name, cfg, precision="float32"):
        self.arch = arch_module(arch_name)
        self.cfg = cfg
        mm = MATMULS[precision]
        arch = self.arch
        self._embed = jax.jit(lambda p, ids: arch.embed(p, ids, cfg))
        self._layer = jax.jit(lambda x, w: arch.layer(x, w, cfg, mm))
        self._head = jax.jit(lambda p, x: arch.head(p, x, cfg, mm))

    def logits(self, params, ids):
        """ids [S] int32 (already padded) -> [S, V] float32."""
        arch, cfg = self.arch, self.cfg
        x = self._embed({n: params[n] for n in arch.EMBED_NAMES},
                        jnp.asarray(ids)[None])
        for i in range(cfg["num_layers"]):
            x = self._layer(x, _layer_weights(arch, cfg, params, i))
        return self._head({n: params[n] for n in arch.HEAD_NAMES}, x)[0]


@jax.jit
def _gaps(logits, tokens):
    """How far each position's ``tokens`` entry lies below the best."""
    best = jnp.max(logits, axis=-1)
    pick = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - pick


def served_gaps(ref, params, prompt, output, pad_to, control=None):
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over this request's served tokens; with
    ``control`` (a lower-precision ServeReference) also the widest gap of
    the token that the control puts first at the same positions."""
    prompt = np.asarray(prompt, np.int32)
    output = np.asarray(output, np.int32)
    n = prompt.size + output.size
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    logits = ref.logits(params, ids)
    # position p predicts token p + 1
    lo, hi = prompt.size - 1, n - 1
    nxt = np.zeros(pad_to, np.int32)
    nxt[:pad_to - 1] = ids[1:]
    gaps = np.asarray(_gaps(logits, jnp.asarray(nxt)))[lo:hi]
    out = {"gap": float(gaps.max()), "tokens": int(output.size),
           "flips": int((gaps > 0).sum())}
    if control is not None:
        low = control.logits(params, ids)
        first = jnp.argmax(low, axis=-1).astype(jnp.int32)
        cg = np.asarray(_gaps(logits, first))[lo:hi]
        out["control_gap"] = float(cg.max())
    return out


class TrainReference:
    """Loss, gradient and AdamW of the plain model, float32 parameters,
    the batch's rows one at a time, each layer recomputed in the
    backward pass."""

    def __init__(self, arch_name, cfg, opt, precision="float32"):
        self.arch = arch = arch_module(arch_name)
        self.cfg = cfg
        self.opt = opt
        mm = MATMULS[precision]

        def row_loss(params, row):
            ids, labels = row[None, :-1], row[None, 1:]
            x = arch.embed(params, ids, cfg)
            for i in range(cfg["num_layers"]):
                w = _layer_weights(arch, cfg, params, i)
                x = jax.checkpoint(
                    lambda x, w: arch.layer(x, w, cfg, mm))(x, w)
            return cross_entropy(arch.head(params, x, cfg, mm), labels)

        def batch_grad(params, batch):
            zero = jax.tree.map(jnp.zeros_like, params)

            def body(carry, row):
                acc, tot = carry
                loss, g = jax.value_and_grad(row_loss)(params, row)
                return (jax.tree.map(jnp.add, acc, g), tot + loss), None

            (acc, tot), _ = jax.lax.scan(body, (zero, 0.0), batch)
            n = batch.shape[0]
            return tot / n, jax.tree.map(lambda a: a / n, acc)

        self.batch_grad = jax.jit(batch_grad)
        b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
        lr, wd = opt["learning_rate"], opt["weight_decay"]

        def adamw(params, grads, m, v, step):
            bc1 = 1.0 - b1 ** step
            bc2 = 1.0 - b2 ** step

            def leaf(p, g, m, v):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * jnp.square(g)
                upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
                return p - lr * upd, m, v

            out = jax.tree.map(leaf, params, grads, m, v)
            pick = lambda k: jax.tree.map(  # noqa: E731
                lambda t: t[k], out, is_leaf=lambda t: isinstance(t, tuple))
            return pick(0), pick(1), pick(2)

        self.adamw = jax.jit(adamw, donate_argnums=(0, 2, 3))

    def follow(self, spec, seed, dtype, batches):
        """Follow ``batches`` from the seed's weights.  Returns each
        step's loss, the per-leaf norm of the first gradient and the
        per-leaf norm of the parameters' change after all steps."""
        from .common import (change_norms, leaf_norms, make_weights,
                             split_vectors)
        params = jax.tree.map(lambda a: a.astype(jnp.float32),
                              make_weights(spec, seed, dtype))
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, g1, g1_vec = [], None, None
        for k, batch in enumerate(batches, 1):
            loss, grads = self.batch_grad(params, jnp.asarray(batch))
            losses.append(float(loss))
            if k == 1:
                g1, g1_vec = split_vectors(leaf_norms(grads, vectors=True))
            params, m, v = self.adamw(params, grads, m, v, float(k))
            del grads
        change, change_vec = split_vectors(
            change_norms(spec, seed, dtype, params))
        return {"losses": losses, "grad1_norm": g1, "grad1_vec": g1_vec,
                "change_norm": change, "change_vec": change_vec}
