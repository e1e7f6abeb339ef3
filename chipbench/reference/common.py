"""Pieces every family's reference uses."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def padded_vocab(v: int) -> int:
    """Rows of the served embedding table: the vocabulary rounded up to a
    multiple of 128."""
    return -(-v // 128) * 128


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def fan_in_normal(key, shape, dtype):
    """std 1/sqrt(fan_in), fan_in being the next-to-last axis."""
    return normal(key, shape, 1.0 / math.sqrt(shape[-2]), dtype)


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, (seed >> 32) % 2**32)


def fp8(x, axis):
    """``x`` rounded to float8 e4m3 after scaling its absolute maximum
    along ``axis`` to 448, the format's largest value: 3 mantissa bits,
    subnormal spacing 2**-9 below 2**-6."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    y = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    q = 2.0 ** (e - 3)
    return jnp.round(y / q) * q * s


def operands(a, a_axis, b, b_axis, lower: bool):
    """The two inputs of a matmul, each rounded along its contracted axis
    when the lower-precision control is asked for."""
    if not lower:
        return a, b
    return fp8(a, a_axis), fp8(b, b_axis)


def matmul(a, w, lower: bool):
    """(..., k) @ (k, n) in float32 at the highest precision."""
    a, w = operands(a, -1, w, 0, lower)
    return jnp.matmul(a, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)
