"""What the paper's 48x48 grid on one chip needs, at CPU sizes: bfloat16
synapses through the fused kernel, params built in blocks of columns, a
static run that writes no copy of its params, and static synapse tables
stored lane-padded where a TPU would lay them out column-minor
(network.lane_width)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ConnectivityConfig, DPSNNConfig
from repro.core import connectivity as conn
from repro.core import network as net
from repro.core import simulation as sim
from repro.core.connectivity import build_stencil
from repro.kernels import ell_deliver as ell
from repro.kernels import fused_step as fused


def _cfg(**kw):
    kw.setdefault("grid_h", 3)
    kw.setdefault("grid_w", 3)
    kw.setdefault("neurons_per_column", 136)
    kw.setdefault("seed", 5)
    kw.setdefault("weight_dtype", "bfloat16")
    return DPSNNConfig(**kw)


def _assert_same_run(r_a, r_b):
    assert int(r_a.spikes) == int(r_b.spikes)
    assert int(r_a.events) == int(r_b.events)
    assert bool(jnp.array_equal(r_a.state.hist, r_b.state.hist))
    assert bool(jnp.array_equal(r_a.state.lif.refrac, r_b.state.lif.refrac))
    np.testing.assert_allclose(np.asarray(r_a.state.lif.v),
                               np.asarray(r_b.state.lif.v),
                               rtol=1e-4, atol=1e-4)


def test_bf16_geometry_is_off_the_bf16_tiling():
    """N = 136: the fused kernel's last source slice is 8 rows and the ELL
    kernel steps 8 rows at a time, neither a multiple of bfloat16's
    16-row tile."""
    cfg = _cfg()
    n, k = cfg.neurons_per_column, build_stencil(cfg).k_total
    assert n - (n // fused.BLK_S) * fused.BLK_S == 8
    groups, rows = ell._row_steps(ell.row_block(n, k), k)
    assert groups * rows == 8


def test_bf16_fused_run_matches_ref():
    """impl='pallas_fused' with bfloat16 weight blocks against impl='ref'
    at the same weights: spikes, history and counters equal, membrane
    potentials within the multi-slice tolerance of the f32 kernels."""
    cfg = _cfg()
    params, state = sim.build(cfg)
    assert params.w_local.dtype == jnp.bfloat16
    assert params.rem_w.dtype == jnp.bfloat16
    r_ref = sim.run(cfg, params, state, 30, impl="ref")
    r_fus = sim.run(cfg, params, state, 30, impl="pallas_fused")
    assert int(r_ref.spikes) > 0
    _assert_same_run(r_ref, r_fus)


@pytest.mark.parametrize("weight_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fit", [4, 6])
def test_blocked_build_is_bitwise_the_unblocked(monkeypatch, weight_dtype,
                                                fit):
    """A budget of ``fit`` columns: 16 columns in four blocks of 4, or in
    three of 6 whose last overlaps the one before."""
    cfg = _cfg(grid_h=4, grid_w=4, neurons_per_column=64,
               weight_dtype=weight_dtype)
    cols = jnp.arange(cfg.n_columns, dtype=jnp.int32)
    whole = net.build_params(cfg, cols)
    monkeypatch.setattr(net, "BUILD_BUDGET", fit * 4 * 64 * 64)
    blocked = jax.jit(lambda c: net.build_params(cfg, c))(cols)
    for a, b in zip(whole, blocked):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.array_equal(a, b))


def _loop_lengths(cfg):
    """Lengths of the loops in the jaxpr of ``cfg``'s whole-grid build."""
    cols = jax.ShapeDtypeStruct((cfg.n_columns,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda c: net.build_params(cfg, c))(cols)
    return [e.params["length"] for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "scan"]


def test_build_blocks_at_the_paper_sizes():
    """The 24x24 grid (or a mesh's 24x24 tile) is one block; the 48x48
    grid four of 576 columns."""
    from repro.configs import dpsnn

    assert _loop_lengths(dpsnn.GRID_24) == []
    assert _loop_lengths(dpsnn.GRID_48_BF16) == [4]


def test_static_run_returns_no_params_and_equals_a_step_loop():
    cfg = _cfg(grid_h=4, grid_w=4, neurons_per_column=48,
               weight_dtype="float32")
    params, state = sim.build(cfg)
    out = jax.eval_shape(
        lambda p, s: sim.run(cfg, p, s, 7, impl="pallas_fused"),
        params, state)
    assert out.params is None
    big = params.w_local.shape
    assert all(x.shape != big for x in jax.tree_util.tree_leaves(out))
    res = sim.run(cfg, params, state, 7, impl="pallas_fused")
    step = jax.jit(net.make_step_fn(cfg, impl="pallas_fused"))
    s = state
    for _ in range(7):
        s = step(params, s)
    for a, b in zip(jax.tree_util.tree_leaves(res.state),
                    jax.tree_util.tree_leaves(s)):
        assert bool(jnp.array_equal(a, b))


# ---------------------------------------------------------------------------
# Lane-padded synapse tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,width", [
    ((2304, 1240, 1240), 1280), ((2304, 1240, 248), 256),
    ((2304, 1240, 256), 256), ((1024, 1240, 1240), 1280),
    ((576, 1240, 1240), 1240), ((576, 1240, 248), 248),
    ((1600, 1240, 1240), 1240), ((576, 1240, 1028), 1152),
    ((16, 20, 20), 20), ((2304, 72, 24), 24), ((128, 120, 120), 128)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_lane_width(shape, width):
    """Padded where a TPU lays the table out column-minor (2,304, 1,024 or
    128 columns: whole lane tiles) or target-minor (K = 1,028); the 24x24
    and 40x40 grids' tables are row-major as they are; a table that
    padding would not make row-major, or would widen by more than an
    eighth, keeps its width."""
    assert net.lane_width(shape) == width


def _padded_cfg(**kw):
    """16x8 columns (one lane tile) of 120 neurons, a lateral amplitude
    that gives K = 124: both tables padded to 128."""
    return _cfg(grid_h=16, grid_w=8, neurons_per_column=120,
                conn=ConnectivityConfig(amp_lateral=0.25), **kw)


def test_lane_padded_tables_hold_the_generated_synapses(monkeypatch):
    cfg = _padded_cfg()
    st = build_stencil(cfg)
    n, k = cfg.neurons_per_column, st.k_total
    cols = jnp.arange(cfg.n_columns, dtype=jnp.int32)
    params = net.build_params(cfg, cols)
    assert params.w_local.shape == (128, n, 128)
    assert params.rem_flat.shape == params.rem_w.shape == (128, n, 128)
    w, idx, rw = conn.generate_columns(cfg, cols)
    assert bool(jnp.array_equal(params.w_local[..., :n], w))
    assert not bool(params.w_local[..., n:].any())
    assert bool(jnp.array_equal(params.rem_flat[..., :k],
                                conn.flat_gather_index(st, idx, n)))
    assert bool(jnp.all(params.rem_flat[..., k:] == (st.n_offsets - 1) * n))
    assert bool(jnp.array_equal(params.rem_w[..., :k], rw))
    assert not bool(params.rem_w[..., k:].any())
    assert k == 124
    # three blocks of 43 columns, the last overlapping
    monkeypatch.setattr(net, "BUILD_BUDGET", 48 * 4 * n * n)
    blocked = net.build_params(cfg, cols)
    for a, b in zip(params, blocked):
        assert bool(jnp.array_equal(a, b))


@pytest.mark.parametrize("impl,weight_dtype", [
    ("pallas_fused", "float32"), ("pallas_fused", "bfloat16"),
    ("pallas", "float32")])
def test_lane_padded_run_equals_the_unpadded(impl, weight_dtype):
    """The padded tables' absent synapses change nothing: the kernels'
    paths on them equal the reference path on the same synapses stored
    at their own widths."""
    cfg = _padded_cfg(weight_dtype=weight_dtype)
    st = build_stencil(cfg)
    n, k = cfg.neurons_per_column, st.k_total
    params, state = sim.build(cfg)
    assert params.w_local.shape[2] > n and params.rem_w.shape[2] > k
    plain = params._replace(w_local=params.w_local[..., :n],
                            rem_flat=params.rem_flat[..., :k],
                            rem_w=params.rem_w[..., :k])
    r_plain = sim.run(cfg, plain, state, 25, impl="ref")
    r_fus = sim.run(cfg, params, state, 25, impl=impl)
    assert int(r_plain.spikes) > 0
    _assert_same_run(r_plain, r_fus)


def test_plastic_tables_keep_their_widths():
    """The STDP rule rewrites the tables at their own widths: a plastic
    network's are not padded where a static one's are."""
    static = _padded_cfg(weight_dtype="float32")
    plastic = dataclasses.replace(static, stdp=True)
    n, k = plastic.neurons_per_column, build_stencil(plastic).k_total
    cols = jnp.arange(plastic.n_columns, dtype=jnp.int32)
    params = jax.eval_shape(lambda c: net.build_params(plastic, c), cols)
    assert params.w_local.shape[2] == n and params.rem_w.shape[2] == k
    padded = jax.eval_shape(lambda c: net.build_params(static, c), cols)
    assert padded.w_local.shape[2] > n and padded.rem_w.shape[2] > k
