"""Plain float32 jax.numpy pieces the model references share: matmuls at
a stated precision, the loss, and Adam. Imports nothing of the program.

Precisions:

- ``highest``: float32 products (what the configurations state);
- ``high``: the three-pass bfloat16 product (each operand split into a
  bfloat16 high part and a bfloat16 remainder, the low x low term
  dropped), written out so it reads the same on any platform. It is the
  control for a float32-at-highest configuration;
- ``bf16``: operands rounded to bfloat16, float32 accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def einsum(spec: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HI)
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        return (jnp.einsum(spec, ah, bh, precision=HI)
                + jnp.einsum(spec, ah, bl, precision=HI)
                + jnp.einsum(spec, al, bh, precision=HI))
    if precision == "bf16":
        r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return jnp.einsum(spec, r(a), r(b), precision=HI)
    raise ValueError(f"unknown precision {precision!r}")


def mm(a, b, precision: str):
    return einsum("...i,ij->...j", a, b, precision)


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy over all rows given."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"t": 0, "m": zeros, "v": zeros}


def adam_update(params, grads, state, lr, weight_decay, b1=0.9, b2=0.999,
                eps=1e-8):
    """Adam with classic L2 weight decay folded into the gradient. Returns
    (new params, new state, the gradient as the update saw it)."""
    tm = jax.tree_util.tree_map
    g = tm(lambda g_, p: g_ + weight_decay * p, grads, params)
    t = state["t"] + 1
    m = tm(lambda m_, g_: b1 * m_ + (1 - b1) * g_, state["m"], g)
    v = tm(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, state["v"], g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new = tm(lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
             params, m, v)
    return new, {"t": t, "m": m, "v": v}, g


def segment_softmax(logit, value, dst, n):
    """Per-destination softmax of ``logit`` (E, H) weighting ``value``
    (E, H, D); destinations without edges aggregate to 0."""
    mx = jax.ops.segment_max(logit, dst, n)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.exp(logit - mx[dst])
    den = jax.ops.segment_sum(ex, dst, n)
    num = jax.ops.segment_sum(ex[..., None] * value, dst, n)
    return num / jnp.maximum(den, 1e-30)[..., None]
