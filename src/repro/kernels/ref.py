"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness ground truth: tests/test_kernels.py sweeps
shapes/dtypes and asserts the kernels (interpret mode on CPU, compiled on
TPU) match these to tight tolerances. Their contractions and outer
products run at ``Precision.HIGHEST``, so they stay f32 on a TPU (which
multiplies f32 in one bf16 pass at default precision); on the CPU the
setting changes nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def synapse_matmul_ref(spikes: jax.Array, w_local: jax.Array) -> jax.Array:
    """Local synaptic delivery: (C,N) x (C,N,N)[src,tgt] -> (C,N)."""
    return jnp.einsum(
        "cs,cst->ct", spikes, w_local, precision=HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(spikes.dtype)


def stdp_dense_update_ref(w_local, x_pre_exc, spk_exc, spikes, x_post, *,
                          a_plus, a_minus, lr, w_max):
    """Dense local STDP update (mirrors core/plasticity.py local branch)."""
    pot = jnp.einsum("cs,ct->cst", x_pre_exc, spikes, precision=HIGHEST)
    dep = jnp.einsum("cs,ct->cst", spk_exc, x_post, precision=HIGHEST)
    dw = lr * (a_plus * pot - a_minus * dep)
    return jnp.where(
        w_local > 0, jnp.clip(w_local + dw, 0.0, w_max), w_local
    )


def lif_step_ref(v, c, refrac, current, *, decay_v, decay_c, gain,
                 g_c, alpha_c, v_rest, v_reset, v_threshold, arp_steps):
    """Fused LIF+SFA update (mirrors core/neuron.py lif_sfa_step)."""
    drive = current - g_c * c
    v1 = v_rest + (v - v_rest) * decay_v + drive * gain
    refractory = refrac > 0
    v1 = jnp.where(refractory, v_reset, v1)
    spikes_b = (v1 >= v_threshold) & (~refractory)
    spikes = spikes_b.astype(v.dtype)
    v2 = jnp.where(spikes_b, v_reset, v1)
    c2 = c * decay_c + alpha_c * spikes
    r2 = jnp.where(spikes_b, jnp.int32(arp_steps),
                   jnp.maximum(refrac - 1, 0))
    return v2, c2, r2, spikes
