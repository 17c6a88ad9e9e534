"""What the plain references share: seeded weights, the matmul whose
precision the lower-precision control swaps, losses.

The references keep weights in the type the configuration states and
compute every product in float32 at ``highest`` precision (a TPU would
otherwise run float32 matmuls in bfloat16 passes)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed):
    """A PRNG key from any whole ``--seed`` (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, i, entry, dtype):
    shape, kind, std = entry
    x = jax.random.normal(jax.random.fold_in(key, i), shape,
                          jnp.float32) * std
    if kind == "ones":
        x = x + 1.0
    return x.astype(dtype)


def make_weights(spec, seed, dtype):
    """Every leaf of ``spec`` ({name: (shape, kind, std)}) from ``seed``,
    on the device, in ONE jitted call, in ``dtype``.  Kinds: ``normal``
    (N(0, std)), ``ones`` (1 + N(0, std): norm scales), ``zeros`` +
    N(0, std) (biases) — every leaf carries noise so that no leaf is
    blind to a fault."""
    names = sorted(spec)

    def build(key):
        return {name: _leaf(key, i, spec[name], dtype)
                for i, name in enumerate(names)}

    return jax.jit(build)(seed_key(seed))


def mm_f32(x, w):
    """The reference product: float32 at ``highest``."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _straight_through(a, q):
    """``q`` in the forward pass, the identity's gradient backward: what
    a low-precision matmul with a full-precision backward formula does
    (rounding itself has no useful derivative)."""
    return a + jax.lax.stop_gradient(q - a)


def _fake_quant_int8(a, axis):
    a = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jax.lax.stop_gradient(jnp.where(scale == 0, 1.0, scale))
    return _straight_through(a, jnp.round(a / scale) * scale)


def mm_int8(x, w):
    """W8A8: activations per row, weights per output column, symmetric
    absmax int8; the product itself stays exact."""
    return mm_f32(_fake_quant_int8(x, -1), _fake_quant_int8(w, 0))


def _fake_quant_fp8(a, axis):
    a = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jax.lax.stop_gradient(jnp.where(scale == 0, 1.0, scale))
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return _straight_through(a, q)


def mm_fp8(x, w):
    """float8 e4m3 inputs (scaled per row / per output column), exact
    product: the nearest precision below bfloat16."""
    return mm_f32(_fake_quant_fp8(x, -1), _fake_quant_fp8(w, 0))


def mm_bf16(x, w):
    """bfloat16 inputs, float32 accumulation (control for float32)."""
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


MATMULS = {"float32": mm_f32, "bfloat16": mm_bf16, "int8": mm_int8,
           "fp8": mm_fp8}


def cross_entropy(logits, labels):
    """Mean over tokens of -log softmax(logits)[label], float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - pick)


def causal_attention(q, k, v):
    """q [B,S,H,D], k/v [B,S,Hkv,D] -> [B,S,H,D]; float32, dense mask."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    hi = jax.lax.Precision.HIGHEST
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi)


def change_norms(spec, seed, dtype, params):
    """Per leaf, the norm of ``params`` minus the seed's weights, with
    the seed's weights made again leaf by leaf inside one program so
    that no second copy of the model is ever held.  Vectors (biases,
    norm scales) come back whole as well, under ``vec:<name>``: part of
    a vector can be dead to the gradient (a key's bias under softmax)
    and is then left out element by element."""
    names = sorted(spec)

    def build(key, params):
        out = {}
        for i, name in enumerate(names):
            d = params[name].astype(jnp.float32) - \
                _leaf(key, i, spec[name], dtype).astype(jnp.float32)
            out[name] = jnp.sqrt(jnp.sum(jnp.square(d)))
            if d.ndim == 1:
                out["vec:" + name] = d
        return out

    return jax.jit(build)(seed_key(seed), params)


def split_vectors(tree):
    """({name: float}, {name: numpy vector}) of a ``change_norms`` /
    ``leaf_norms(vectors=True)`` result."""
    import numpy as np
    scal = {k: float(v) for k, v in tree.items() if not k.startswith("vec:")}
    vec = {k[4:]: np.asarray(v, np.float32) for k, v in tree.items()
           if k.startswith("vec:")}
    return scal, vec


def leaf_norms(tree, vectors=False):
    def norms(t):
        out = {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
               for k, v in t.items()}
        if vectors:
            out.update({"vec:" + k: v.astype(jnp.float32)
                        for k, v in t.items() if v.ndim == 1})
        return out
    return jax.jit(norms)(tree)
