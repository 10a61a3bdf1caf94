"""Fused LIF+SFA neuron update (Pallas TPU kernel).

Elementwise over the (C, N) state but fusing the five HBM round-trips
(v, c, refrac, current -> v, c, refrac, spikes) into one pass. On TPU the
unfused jnp version materializes each intermediate through HBM when the
state exceeds VMEM; the fused kernel is bandwidth-bound at exactly
4 reads + 4 writes per neuron.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import NeuronConfig
from repro.kernels._padding import pad_to

BLK_C = 8
BLK_N = 128


def _kernel(v_ref, c_ref, r_ref, i_ref, params_ref,
            vo_ref, co_ref, ro_ref, so_ref):
    (decay_v, decay_c, gain, g_c, alpha_c, v_rest, v_reset,
     v_thr, arp) = [params_ref[i] for i in range(9)]
    v, c, refrac, cur = v_ref[...], c_ref[...], r_ref[...], i_ref[...]

    drive = cur - g_c * c
    v1 = v_rest + (v - v_rest) * decay_v + drive * gain
    refractory = refrac > 0
    v1 = jnp.where(refractory, v_reset, v1)
    spikes_b = (v1 >= v_thr) & (~refractory)
    spikes = spikes_b.astype(v.dtype)

    vo_ref[...] = jnp.where(spikes_b, v_reset, v1)
    co_ref[...] = c * decay_c + alpha_c * spikes
    ro_ref[...] = jnp.where(spikes_b, arp.astype(jnp.int32),
                            jnp.maximum(refrac - 1, 0))
    so_ref[...] = spikes


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def lif_step(cfg: NeuronConfig, v, c, refrac, current, *, interpret: bool):
    """Returns (v', c', refrac', spikes) — see kernels/ref.py oracle."""
    nc, nn = v.shape
    import math
    params = jnp.array(
        [math.exp(-cfg.dt_ms / cfg.tau_m_ms),
         math.exp(-cfg.dt_ms / cfg.tau_c_ms),
         (1.0 - math.exp(-cfg.dt_ms / cfg.tau_m_ms)) * cfg.tau_m_ms / cfg.dt_ms,
         cfg.g_c, cfg.alpha_c, cfg.v_rest, cfg.v_reset, cfg.v_threshold,
         round(cfg.tau_arp_ms / cfg.dt_ms)],
        dtype=v.dtype,
    )
    args = [pad_to(pad_to(x, 0, BLK_C), 1, BLK_N)
            for x in (v, c, refrac, current)]
    pc, pn = args[0].shape
    spec = pl.BlockSpec((BLK_C, BLK_N), lambda i, j: (i, j))
    out = pl.pallas_call(
        _kernel,
        grid=(pc // BLK_C, pn // BLK_N),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((pc, pn), v.dtype),
            jax.ShapeDtypeStruct((pc, pn), v.dtype),
            jax.ShapeDtypeStruct((pc, pn), jnp.int32),
            jax.ShapeDtypeStruct((pc, pn), v.dtype),
        ],
        interpret=interpret,
    )(*args, params)
    return tuple(o[:nc, :nn] for o in out)
