"""Per-kernel allclose vs the pure-jnp oracles: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs.base import NeuronConfig
from repro.kernels import ops, ref


@pytest.mark.parametrize("c,n", [(1, 32), (3, 70), (8, 128), (5, 200),
                                 (2, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_synapse_matmul_sweep(c, n, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(c * 1000 + n))
    spikes = (jax.random.uniform(k1, (c, n)) < 0.07).astype(dtype)
    w = jax.random.normal(k2, (c, n, n)).astype(dtype)
    got = ops.synapse_matmul(spikes, w)
    want = ref.synapse_matmul_ref(spikes, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_synapse_matmul_all_silent():
    """Block-event skip path: all-zero spikes must give exact zeros."""
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 130, 130))
    out = ops.synapse_matmul(jnp.zeros((4, 130)), w)
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("c,n", [(1, 32), (3, 150), (2, 128), (4, 257)])
def test_stdp_dense_update_sweep(c, n):
    ks = jax.random.split(jax.random.PRNGKey(c * 31 + n), 5)
    w = jnp.where(jax.random.uniform(ks[0], (c, n, n)) < 0.7,
                  jax.random.normal(ks[0], (c, n, n)), 0.0)
    xpre = jax.random.uniform(ks[1], (c, n))
    sspk = (jax.random.uniform(ks[2], (c, n)) < 0.06).astype(jnp.float32)
    tspk = (jax.random.uniform(ks[3], (c, n)) < 0.06).astype(jnp.float32)
    xpost = jax.random.uniform(ks[4], (c, n))
    kw = dict(a_plus=0.01, a_minus=0.012, lr=1.0, w_max=0.84)
    got = ops.stdp_dense_update(w, xpre, sspk, tspk, xpost, **kw)
    want = ref.stdp_dense_update_ref(w, xpre, sspk, tspk, xpost, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # structural invariants: zeros stay zero, negatives untouched
    assert bool((np.asarray(got)[np.asarray(w) == 0] == 0).all())
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(w) < 0],
                                  np.asarray(w)[np.asarray(w) < 0])


def test_stdp_dense_update_all_silent_matches_ref():
    """Block-event skip path: no spikes on either side => dw == 0, but
    the unconditional clip still applies (bitwise equal to the ref even
    for out-of-range starting weights)."""
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 140, 140)) * 5
    z = jnp.zeros((3, 140))
    tr = jax.random.uniform(jax.random.PRNGKey(2), (3, 140))
    kw = dict(a_plus=0.01, a_minus=0.012, lr=1.0, w_max=0.84)
    got = ops.stdp_dense_update(w, tr, z, z, tr, **kw)
    want = ref.stdp_dense_update_ref(w, tr, z, z, tr, **kw)
    assert bool(jnp.array_equal(got, want))
    # in-range weights are bitwise untouched
    w_in = jnp.clip(w, -0.8, 0.8)
    got = ops.stdp_dense_update(w_in, tr, z, z, tr, **kw)
    assert bool(jnp.array_equal(got, w_in))


@pytest.mark.parametrize("c,n", [(5, 170), (1, 32), (9, 129)])
def test_lif_step_sweep(c, n):
    cfg = NeuronConfig()
    ks = jax.random.split(jax.random.PRNGKey(c + n), 4)
    v = jax.random.uniform(ks[0], (c, n), minval=0, maxval=21)
    cc = jax.random.uniform(ks[1], (c, n), maxval=3)
    r = jax.random.randint(ks[2], (c, n), 0, 3)
    cur = jax.random.normal(ks[3], (c, n)) * 2
    got = ops.lif_step(cfg, v, cc, r, cur)
    kw = dict(decay_v=math.exp(-cfg.dt_ms / cfg.tau_m_ms),
              decay_c=math.exp(-cfg.dt_ms / cfg.tau_c_ms),
              gain=(1 - math.exp(-cfg.dt_ms / cfg.tau_m_ms))
              * cfg.tau_m_ms / cfg.dt_ms,
              g_c=cfg.g_c, alpha_c=cfg.alpha_c, v_rest=cfg.v_rest,
              v_reset=cfg.v_reset, v_threshold=cfg.v_threshold,
              arp_steps=round(cfg.tau_arp_ms / cfg.dt_ms))
    want = ref.lif_step_ref(v, cc, r, cur, **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_pad_to_shared_helper():
    """kernels/ops.pad_to — the one shared padding helper (ISSUE 5
    satellite: three kernels used to carry identical private copies).
    No-pad fast path returns the input object; odd (C, N) shapes pad
    with exact zeros at the high end only."""
    x = jnp.arange(12.0).reshape(3, 4)
    # no-pad fast path: same object, no copy
    assert ops.pad_to(x, 0, 3) is x
    assert ops.pad_to(x, 1, 2) is x
    assert ops.pad_to(x, 1, 4) is x
    # odd shapes pad up to the next multiple, zeros only in the new tail
    for axis, mult, want in [(0, 2, (4, 4)), (1, 128, (3, 128)),
                             (0, 8, (8, 4)), (1, 3, (3, 6))]:
        y = ops.pad_to(x, axis, mult)
        assert y.shape == want
        np.testing.assert_array_equal(np.asarray(y)[:3, :4], np.asarray(x))
        assert float(jnp.abs(y).sum()) == float(jnp.abs(x).sum())
    # 3-D operand (the (C, N, K) ELL blocks)
    z = jnp.ones((2, 5, 7))
    assert ops.pad_to(z, 1, 5) is z
    assert ops.pad_to(z, 2, 8).shape == (2, 5, 8)
    # every kernel module that pads uses THIS helper (no private
    # duplicates left); the fused and STDP kernels do not pad N at all
    from repro.kernels import (_padding, fused_step, lif_step, stdp_update,
                               synapse_matmul)
    for mod in (lif_step, synapse_matmul):
        assert mod.pad_to is _padding.pad_to
    for mod in (fused_step, lif_step, stdp_update, synapse_matmul):
        assert not hasattr(mod, "_pad_to")
    assert ops.pad_to is _padding.pad_to


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_decision(monkeypatch, backend, want):
    """kernels/ops.interpret_mode is the one place that decides: the CPU
    interprets, a TPU compiles, any other backend is refused — at the
    decision and through a kernel wrapper — instead of interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is not None:
        assert ops.interpret_mode() is want
        return
    with pytest.raises(RuntimeError, match=f"'{backend}'"):
        ops.interpret_mode()
    z = jnp.zeros((1, 8))
    with pytest.raises(RuntimeError, match="interpret"):
        ops.synapse_matmul(z, jnp.zeros((1, 8, 8)))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.integers(16, 150), st.floats(0.0, 0.3))
def test_property_synapse_matmul_linear(c, n, p):
    """Linearity: delivery(a+b) == delivery(a)+delivery(b) and silent
    blocks contribute nothing (hypothesis over shapes + densities)."""
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    a = (jax.random.uniform(ks[0], (c, n)) < p).astype(jnp.float32)
    b = (jax.random.uniform(ks[1], (c, n)) < p).astype(jnp.float32)
    w = jax.random.normal(ks[2], (c, n, n))
    lhs = ops.synapse_matmul(a + b, w)
    rhs = ops.synapse_matmul(a, w) + ops.synapse_matmul(b, w)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=2e-4, atol=2e-4)
