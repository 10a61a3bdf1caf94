"""Block-event-driven local synaptic delivery (Pallas TPU kernel).

Computes ``out[c, t] = sum_s spikes[c, s] * w[c, s, t]`` — a batched
vector-matrix product per column — with the paper's event-driven insight
adapted to block granularity (DESIGN.md §2): for every 128-wide source
block whose spike vector is all-zero (the common case at cortical firing
rates: a 1240-neuron column at 5 Hz emits ~6 spikes/ms, so ~94 % of
128-blocks are silent in any step), the MXU tile is **skipped** via
``pl.when``.

Tiling: grid (C, T_out, S_in) with S_in innermost (reduction). Per step
the kernel holds one (BLK_S, BLK_T) weight tile + one (1, BLK_S) spike
slice in VMEM and accumulates into the (1, BLK_T) output block in f32.
Spike and output vectors are laid out ``(C, 1, N_pad)`` so their blocks
meet the TPU (8, 128) rule; N is zero-padded to the 128 lane width,
because a contraction must not read past N.
VMEM footprint = BLK_S*BLK_T*2B (bf16 weights) + accumulator ≈ 33 KB at
128x128 — far under the ~16 MB/core budget, so the pipeline can
triple-buffer tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._padding import pad_to

BLK_S = 128   # source block (MXU contraction dim)
BLK_T = 128   # target block (MXU lane dim)


def _kernel(s_ref, w_ref, o_ref):
    i_s = pl.program_id(2)

    @pl.when(i_s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = s_ref[0]                         # (1, BLK_S)
    # block-event skip: silent source blocks contribute nothing
    any_spike = jnp.max(jnp.abs(s)) > 0

    @pl.when(any_spike)
    def _acc():
        w = w_ref[0]                     # (BLK_S, BLK_T)
        acc = jax.lax.dot_general(
            s.astype(w.dtype), w,
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                # (1, BLK_T)
        o_ref[0] += acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def synapse_matmul(spikes: jax.Array, w_local: jax.Array,
                   *, interpret: bool) -> jax.Array:
    """(C, N) x (C, N, N) -> (C, N). Zero-pads N to the 128 lane width."""
    c, n = spikes.shape
    sp = pad_to(spikes, 1, BLK_S)[:, None]
    w = pad_to(pad_to(w_local, 1, BLK_S), 2, BLK_T)
    n_s, n_t = w.shape[1], w.shape[2]

    out = pl.pallas_call(
        _kernel,
        grid=(c, n_t // BLK_T, n_s // BLK_S),
        in_specs=[
            pl.BlockSpec((1, 1, BLK_S), lambda ci, ti, si: (ci, 0, si)),
            pl.BlockSpec((1, BLK_S, BLK_T), lambda ci, ti, si: (ci, si, ti)),
        ],
        out_specs=pl.BlockSpec((1, 1, BLK_T), lambda ci, ti, si: (ci, 0, ti)),
        out_shape=jax.ShapeDtypeStruct((c, 1, n_t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(sp, w)
    return out[:, 0, :n].astype(spikes.dtype)
