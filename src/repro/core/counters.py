"""Exact running totals: the spike and synaptic-event counters.

At the paper's column size the 24x24 grid delivers about 1.2M synaptic
events per step, so an f32 running total passes 2**24 within a few dozen
steps and from then on rounds every addition. How it rounds depends on
how the total was split — one shard's running sum, or four per-shard
sums added at the end — so a mesh run and the single-shard run of the
same network would disagree in the last bits of a count that is, in
exact arithmetic, the same integer.

A counter is therefore a pair of int32 words ``[hi, lo]`` worth
``hi * 2**LO_BITS + lo``. Each step adds an exact int32 increment, and
partial totals of any number of shards add word by word (``psum``, the
checkpoint reshard). :func:`value` turns a counter into the f32 that the
metrics report, rounded once from the exact integer, so equal counts give
bitwise-equal values however they were accumulated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LO_BITS = 20
_LO_MASK = (1 << LO_BITS) - 1


def zero() -> jax.Array:
    return jnp.zeros((2,), jnp.int32)


def _carry(hi, lo):
    return hi + (lo >> LO_BITS), lo & _LO_MASK


def add(count: jax.Array, inc: jax.Array) -> jax.Array:
    """``count + inc`` for a non-negative int32 ``inc`` below
    ``2**31 - 2**LO_BITS`` (one step's events on one shard)."""
    hi, lo = _carry(count[..., 0], count[..., 1] + inc.astype(jnp.int32))
    return jnp.stack([hi, lo], axis=-1)


def value(count: jax.Array) -> jax.Array:
    """f32 value of a counter, or of a stack of counters (last axis
    ``[hi, lo]``). ``lo`` may hold a sum of up to 2**11 shards' words;
    the carry brings it back below ``2**LO_BITS`` first, so both operands
    of the final addition are exact and only that addition rounds."""
    hi, lo = _carry(count[..., 0], count[..., 1])
    return (hi.astype(jnp.float32) * float(1 << LO_BITS)
            + lo.astype(jnp.float32))
