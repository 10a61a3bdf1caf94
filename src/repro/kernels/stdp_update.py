"""Dense local STDP weight update (Pallas TPU kernel).

Computes, per column ``c`` and (src, tgt) pair::

    dw = lr * (a_plus  * x_pre_exc[c, s] * spikes[c, t]
               - a_minus * spk_exc[c, s] * x_post[c, t])
    w' = where(w > 0, clip(w + dw, 0, w_max), w)

— the pair-based STDP rule of core/plasticity.py as two rank-1 outer
products per (BLK_S, BLK_T) tile, with the block-event skip of
synapse_matmul.py (DESIGN.md §2/§Plasticity): the potentiation term is
zero wherever the *target* block has no spikes and the depression term is
zero wherever the *source* block has no spikes, so a tile whose source
AND target spike slices are all silent skips the outer products and only
re-applies the (elementwise) clip — keeping it exactly equal to the ref
rule, which clips unconditionally. At cortical rates (~5 Hz, ~6
spikes/ms in a 1240-neuron column) the vast majority of 128x128 tiles
take the skip path.

Inhibitory sources are handled upstream: ``x_pre_exc``/``spk_exc`` arrive
pre-masked to excitatory rows, and the ``w > 0`` guard keeps negative
(inhibitory) and absent (zero) weights exactly unchanged.

Grid (C, S/BLK_S, T/BLK_T); each instance owns one weight tile (read +
write, 64 KB f32 at 128x128) plus four (1, 1, 128) vector slices, passed
as ``(C, 1, N)`` so the TPU (8, 128) block rule holds. N is not padded:
a partial edge tile reads unspecified values past N, and they only reach
outputs past N, which are discarded. The outer products are f32-exact
(``Precision.HIGHEST``), like the reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK_S = 128   # source block (rows)
BLK_T = 128   # target block (lanes)


def _outer(col_ref, row_ref):
    """(1, 1, B) x (1, 1, B) slices -> (B, B) outer product on the MXU
    (contract the unit dim), exact in f32."""
    return jax.lax.dot_general(
        col_ref[0], row_ref[0], (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _fired(ref):
    """Whether a spike slice holds an event. Compares before reducing, so
    an unspecified value past N (NaN in interpret mode) reads as silent."""
    return jnp.max((ref[...] > 0).astype(jnp.float32)) > 0


def _kernel(par_ref, w_ref, xpre_ref, sspk_ref, tspk_ref, xpost_ref, o_ref):
    any_event = _fired(sspk_ref) | _fired(tspk_ref)
    a_plus, a_minus, lr, w_max = [par_ref[i] for i in range(4)]

    @pl.when(any_event)
    def _update():
        w = w_ref[0]                         # (BLK_S, BLK_T)
        pot = _outer(xpre_ref, tspk_ref)
        dep = _outer(sspk_ref, xpost_ref)
        dw = lr * (a_plus * pot - a_minus * dep)
        o_ref[0] = jnp.where(
            w > 0, jnp.clip(w + dw.astype(w.dtype), 0.0, w_max), w
        )

    @pl.when(~any_event)
    def _silent():
        # the ref rule clips unconditionally (dw == 0 still re-clips a
        # weight that starts above w_max); skip only the outer products,
        # not the clip, so pallas == ref for any input state
        w = w_ref[0]
        o_ref[0] = jnp.where(w > 0, jnp.clip(w, 0.0, w_max), w)


@functools.partial(jax.jit, static_argnames=(
    "a_plus", "a_minus", "lr", "w_max", "interpret"))
def stdp_dense_update(w_local: jax.Array, x_pre_exc: jax.Array,
                      spk_exc: jax.Array, spikes: jax.Array,
                      x_post: jax.Array, *, a_plus: float, a_minus: float,
                      lr: float, w_max: float,
                      interpret: bool) -> jax.Array:
    """(C, N, N) weights + four (C, N) vectors -> updated (C, N, N)."""
    c, n = spikes.shape
    params = jnp.array([a_plus, a_minus, lr, w_max], dtype=w_local.dtype)
    n_s, n_t = pl.cdiv(n, BLK_S), pl.cdiv(n, BLK_T)
    src = pl.BlockSpec((1, 1, BLK_S), lambda ci, si, ti: (ci, 0, si))
    tgt = pl.BlockSpec((1, 1, BLK_T), lambda ci, si, ti: (ci, 0, ti))
    tile = pl.BlockSpec((1, BLK_S, BLK_T), lambda ci, si, ti: (ci, si, ti))
    return pl.pallas_call(
        _kernel,
        grid=(c, n_s, n_t),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile, src, src, tgt, tgt],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((c, n, n), w_local.dtype),
        # each tile is read, then written at the same index: update the
        # weights in place rather than holding a second (C, N, N) copy
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(params, w_local, x_pre_exc[:, None], spk_exc[:, None],
      spikes[:, None], x_post[:, None])
