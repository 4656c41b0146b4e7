"""Plain float32 building blocks for the configurations' references.

Nothing here imports the program.  `exact` multiplies in float32 at
`highest` precision (on a TPU a float32 matmul otherwise runs in
bfloat16 passes).  The precision control, `fp8`, rounds both operands
to float8 e4m3, per row of the activations and per column of the
weights, then multiplies exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def exact(a, w):
    return jnp.matmul(a, w, precision=HIGHEST)


def _f8(x, axis):
    """Round to float8 e4m3 after scaling the largest magnitude along
    `axis` to the format's largest finite value, 448."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def fp8(a, w):
    return jnp.matmul(_f8(a, -1), _f8(w, 0), precision=HIGHEST)


def rms(x, scale, eps):
    """RMS norm with the program's parameterisation: the stored scale
    is an offset from 1."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def widest_gap(ref_rows, tokens):
    """Widest gap by which the reference's logit of `tokens` lies below
    its best, over the rows."""
    best = ref_rows.max(axis=-1)
    got = jnp.take_along_axis(ref_rows, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(best - got)


def gaps(logits_rows, conf, params, tokens, rows, served, control):
    """{"served": gap of the served tokens} and, with `control`, the gap
    of the token the fp8 reference puts first ("control")."""
    ref = logits_rows(conf, params, tokens, rows, exact)
    out = {"served": widest_gap(ref, served)}
    if control:
        pick = jnp.argmax(logits_rows(conf, params, tokens, rows, fp8),
                          axis=-1)
        out["control"] = widest_gap(ref, pick)
    return out
