"""Pieces every plain reference shares: exact and rounded products,
RMSNorm, cross-entropy, per-leaf keys and norms."""
from __future__ import annotations

import zlib
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def exact(x):
    return x


def rounding(dtype):
    """Round to ``dtype`` in the forward pass; the gradient passes
    straight through (no rounding of cotangents). Bfloat16 goes through
    ``lax.reduce_precision``: the TPU compiler may fold away a float32 ->
    bfloat16 -> float32 round trip of converts, and did here on the chip
    (the control's readings came out bit for bit as without it). Float8
    e4m3fn keeps the converts, whose finite range (448) reduce_precision's
    IEEE model of 4 exponent bits (240) would not match."""
    dtype = jnp.dtype(dtype)

    if dtype == jnp.bfloat16:
        def rnd(x):
            return x + lax.stop_gradient(
                lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
                - x)
        return rnd

    def rnd(x):
        return x + lax.stop_gradient(
            x.astype(dtype).astype(jnp.float32) - x)

    return rnd


class Rounding(NamedTuple):
    """What a reference rounds: ``product`` the operands and result of
    every matrix product (the configurations' bfloat16 compute), ``f32``
    what a configuration states in float32 beyond its accumulation (the
    WKV recurrence of rwkv6)."""
    product: Callable = exact
    f32: Callable = exact


EXACT = Rounding()


def control() -> Rounding:
    """The control: one step below each precision a configuration states,
    float8 e4m3 for bfloat16 and bfloat16 for float32."""
    return Rounding(rounding(jnp.float8_e4m3fn), rounding(jnp.bfloat16))


def matmul(rnd):
    """Products whose operands and result pass through ``rnd``: as the
    trainer keeps its activations, every product's result among them, in
    its compute type."""
    def mm(spec, a, b):
        return rnd(jnp.einsum(spec, rnd(a.astype(jnp.float32)),
                              rnd(b.astype(jnp.float32)), precision=HIGHEST,
                              preferred_element_type=jnp.float32))
    return mm


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))


def seed_key(seed: int):
    """A key from any non-negative seed (two 32-bit words)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 32)),
                              seed // (2 ** 32))


def leaf_names(tree) -> list:
    return [path_str(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


def host_leaves(tree) -> list:
    """The leaves as float32 arrays on the host."""
    return [np.asarray(x, np.float32) for x in
            jax.device_get(jax.tree.leaves(tree))]


@jax.jit
def diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
