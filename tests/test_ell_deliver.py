"""Remote ELL delivery over bit-packed spike words (kernels/ell_deliver.py),
interpreted on the CPU, against the reference gather
(``core/network.deliver_remote_ref``): bitwise equal currents for column
sizes of one word and several, on and off the 8- and 32-row tilings, the
Gaussian stencil and the many-offset ``gauss_exp`` one, silent to
saturated spike tables, excitatory and inhibitory (negative) weights,
stored in float32 or bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dpsnn
from repro.configs.base import DPSNNConfig
from repro.core import connectivity as conn
from repro.core import network as net
from repro.kernels import ops


def _synapses(n, family, n_cols, weight_dtype="float32"):
    cfg = dpsnn.with_family(
        DPSNNConfig(grid_h=4, grid_w=4, neurons_per_column=n, seed=5,
                    weight_dtype=weight_dtype), family)
    stencil = conn.build_stencil(cfg)
    _, idx, w = conn.generate_columns(cfg, jnp.arange(n_cols))
    return stencil, conn.flat_gather_index(stencil, idx, n), w


def _spike_table(n_cols, width, density, seed=0):
    draw = jax.random.uniform(jax.random.PRNGKey(seed), (n_cols, width))
    return (draw < density).astype(jnp.float32)


@pytest.mark.parametrize("n,family,density,weight_dtype", [
    pytest.param(n, family, density, wd,
                 id=f"{n}-{family}-{density}" + ("-bf16" if wd else ""))
    for n in (20, 48, 100, 136, 1240) for family in ("gauss", "gauss_exp")
    for density in (0.0, 0.05, 1.0) for wd in ("", "bfloat16")])
def test_ell_deliver_bitwise_vs_ref(n, family, density, weight_dtype):
    """N = 136 steps 8 rows at a time, off bfloat16's 16-row tile."""
    n_cols = 1 if n == 1240 else 4
    weight_dtype = weight_dtype or "float32"
    stencil, rem_flat, rem_w = _synapses(n, family, n_cols, weight_dtype)
    assert rem_w.dtype == jnp.dtype(weight_dtype)
    assert bool((rem_w < 0).any()) and bool((rem_w > 0).any())
    s_flat = _spike_table(n_cols, stencil.n_offsets * n, density)
    ref = net.deliver_remote_ref(s_flat, rem_flat, rem_w)
    got = net.deliver_remote_packed(s_flat, rem_flat, rem_w,
                                    stencil=stencil)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    assert bool(jnp.array_equal(got, ref))
    if density == 0.0:
        assert not bool(jnp.any(got))


@pytest.mark.parametrize("n", [48, 100, 1240])
def test_pack_spikes_round_trip(n):
    n_offsets, n_cols = 20, 3
    s_flat = _spike_table(n_cols, n_offsets * n, 0.3, seed=n)
    words = np.asarray(ops.pack_spikes(s_flat, n_offsets))
    assert words.shape == (n_cols, n_offsets, -(-n // 32))
    assert words.dtype == np.uint32
    bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    bits = bits.reshape(n_cols, n_offsets, -1)
    # the tail of each offset's last word is zero
    assert not bits[..., n:].any()
    np.testing.assert_array_equal(
        bits[..., :n].reshape(n_cols, -1).astype(np.float32),
        np.asarray(s_flat))
