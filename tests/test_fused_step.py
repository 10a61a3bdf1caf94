"""Fused column-step megakernel + cross-step pipelined halo exchange
(ISSUE 5): bitwise fused-vs-ref parity single-shard and on radius>=2
meshes, STDP weight parity over 50+ steps, pipelined-exchange equality
on 2 and 4 real OS-process ranks, and the explicit rejection of
pipelining on delay-free stencils."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_multidevice
from repro.configs.base import (ConnectivityConfig, DPSNNConfig,
                                ExchangeConfig, STDPConfig)
from repro.core import simulation as sim


def _cfg(stdp=False, **kw):
    kw.setdefault("grid_h", 4)
    kw.setdefault("grid_w", 4)
    kw.setdefault("neurons_per_column", 48)
    kw.setdefault("seed", 3)
    return DPSNNConfig(stdp=stdp,
                       stdp_cfg=STDPConfig(a_plus=0.05, a_minus=0.055),
                       **kw)


# ---------------------------------------------------------------------------
# Single-shard fused vs ref (bitwise in the one-source-block regime)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stdp", [False, True])
def test_fused_single_shard_bitwise(stdp):
    """impl='pallas_fused' reproduces the ref trajectory bitwise in every
    event-derived quantity: spike totals, spike history ring, adaptation,
    refractory state and (under STDP) traces + final plastic weights.
    Membrane v may differ in the last ulp (kernels/fused_step.py numerics
    contract) — asserted allclose, never observable through threshold."""
    cfg = _cfg(stdp=stdp)
    params, state = sim.build(cfg)
    r_ref = sim.run(cfg, params, state, 100, impl="ref")
    r_fus = sim.run(cfg, params, state, 100, impl="pallas_fused")
    assert float(r_ref.spikes) == float(r_fus.spikes)
    assert float(r_ref.events) == float(r_fus.events)
    assert bool(jnp.array_equal(r_ref.state.hist, r_fus.state.hist))
    assert bool(jnp.array_equal(r_ref.state.lif.c, r_fus.state.lif.c))
    assert bool(jnp.array_equal(r_ref.state.lif.refrac,
                                r_fus.state.lif.refrac))
    np.testing.assert_allclose(np.asarray(r_ref.state.lif.v),
                               np.asarray(r_fus.state.lif.v),
                               rtol=0, atol=1e-5)
    if stdp:
        assert bool(jnp.array_equal(r_ref.state.stdp.x_pre,
                                    r_fus.state.stdp.x_pre))
        assert bool(jnp.array_equal(r_ref.state.stdp.x_post,
                                    r_fus.state.stdp.x_post))
        # the acceptance metric: final f32 plastic weights, bitwise
        assert bool(jnp.array_equal(r_ref.params.w_local,
                                    r_fus.params.w_local))
        assert bool(jnp.array_equal(r_ref.params.rem_w,
                                    r_fus.params.rem_w))


def test_fused_odd_column_count_bitwise():
    """C not divisible by the kernel's column tile (20 columns vs the
    16-column cap) exercises the column-padding path."""
    cfg = _cfg(stdp=True, grid_h=4, grid_w=5)
    params, state = sim.build(cfg)
    r_ref = sim.run(cfg, params, state, 60, impl="ref")
    r_fus = sim.run(cfg, params, state, 60, impl="pallas_fused")
    assert float(r_ref.spikes) == float(r_fus.spikes)
    assert bool(jnp.array_equal(r_ref.params.w_local, r_fus.params.w_local))


def test_fused_multiblock_allclose():
    """N > 128 spans several source blocks: the local matmul accumulates
    block partial sums, so the contract relaxes to allclose (same as the
    unfused Pallas kernels)."""
    cfg = _cfg(grid_h=3, grid_w=3, neurons_per_column=200, seed=1)
    params, state = sim.build(cfg)
    r_ref = sim.run(cfg, params, state, 30, impl="ref")
    r_fus = sim.run(cfg, params, state, 30, impl="pallas_fused")
    np.testing.assert_allclose(np.asarray(r_ref.state.lif.v),
                               np.asarray(r_fus.state.lif.v),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(r_ref.rate_hz), float(r_fus.rate_hz),
                               rtol=2e-2)


def test_fused_kernel_output_arity():
    from repro.kernels import ops
    cfg = _cfg()
    n, c = 32, 3
    z = jnp.zeros((c, n))
    zi = jnp.zeros((c, n), jnp.int32)
    out = ops.fused_step(cfg.neuron, z, z, zi, z, jnp.zeros((c, n, n)),
                         z, z)
    assert len(out) == 4
    out = ops.fused_step(cfg.neuron, z, z, zi, z, jnp.zeros((c, n, n)),
                         z, z, z, z, scfg=cfg.stdp_cfg)
    assert len(out) == 6
    # silent network stays silent through the fused step
    assert float(jnp.abs(out[3]).max()) == 0.0


# ---------------------------------------------------------------------------
# Mesh parity: fused + pipelined on a radius>=2 multi-ring 2x2 mesh
# (subprocess with 4 forced host devices)
# ---------------------------------------------------------------------------

def test_fused_pipelined_mesh_radius3_bitwise():
    """The acceptance matrix in one subprocess: impl='pallas_fused' x
    pipelined {off,on} x wire format {dense_packed,aer_sparse} on a 2x2
    mesh over a radius-3 gauss_exp stencil (tile 2 < r: multi-ring),
    STDP on — spike totals AND final f32 plastic weights bitwise-equal
    to the single-shard ref run."""
    out = run_multidevice("""
import dataclasses
import numpy as np
import jax
from repro.configs.base import (DPSNNConfig, ConnectivityConfig,
                                ExchangeConfig, STDPConfig)
from repro.core import exchange, simulation as sim
from repro.core.connectivity import build_stencil
from repro.core.partition import tile_column_ids

conn = ConnectivityConfig(lateral_profile='gauss_exp', amp_exp=0.03,
                          lambda_steps=2.0, radius=3,
                          aer_rate_bound_hz=200.0)
base = DPSNNConfig(grid_h=4, grid_w=4, neurons_per_column=40, seed=3,
                   conn=conn, stdp=True,
                   stdp_cfg=STDPConfig(a_plus=0.05, a_minus=0.055))
assert build_stencil(base).radius == 3
params, state = sim.build(base)
ref = sim.run(base, params, state, 60, impl='ref')
mesh = jax.make_mesh((2, 2), ('data', 'model'))
wl_ref = np.asarray(ref.params.w_local)
rw_ref = np.asarray(ref.params.rem_w)
for pipe in (False, True):
    for xmode in ('dense_packed', 'aer_sparse'):
        cfg = dataclasses.replace(
            base, conn=dataclasses.replace(conn, exchange_mode=xmode),
            exchange=ExchangeConfig(pipelined=pipe))
        run, spec = exchange.make_distributed_run(
            cfg, mesh, n_steps=60, impl='pallas_fused', with_state=True)
        res, st = run()
        assert float(res.spikes) == float(ref.spikes), (pipe, xmode)
        assert float(res.events) == float(ref.events), (pipe, xmode)
        assert int(res.aer_saturated.sum()) == 0
        stacked = jax.device_get(st)
        wl = np.asarray(stacked.plastic.w_local)
        rw = np.asarray(stacked.plastic.rem_w)
        for ty in range(2):
            for tx in range(2):
                ids = np.asarray(tile_column_ids(cfg, spec, ty, tx))
                s = ty * 2 + tx
                assert np.array_equal(wl[s], wl_ref[ids]), (pipe, xmode)
                assert np.array_equal(rw[s], rw_ref[ids]), (pipe, xmode)
print('OK', float(ref.spikes))
""")
    assert "OK" in out


def test_pipelined_ref_impl_mesh_bitwise():
    """Pipelining is impl-agnostic: the ref step under pipelined=True is
    bitwise-equal to the single-shard run too (the double buffer only
    moves the ring write, never the values)."""
    out = run_multidevice("""
import dataclasses
import jax
from repro.configs.base import DPSNNConfig, ExchangeConfig
from repro.core import exchange, simulation as sim
cfg = DPSNNConfig(grid_h=8, grid_w=8, neurons_per_column=32, seed=0,
                  exchange=ExchangeConfig(pipelined=True))
params, state = sim.build(cfg)
ref = sim.run(cfg, params, state, 80, impl='ref')
for shape in [(2, 2), (1, 4), (4, 1)]:
    mesh = jax.make_mesh(shape, ('data', 'model'))
    run, spec = exchange.make_distributed_run(cfg, mesh, n_steps=80)
    res = run()
    assert float(res.spikes) == float(ref.spikes), shape
print('OK', float(ref.spikes))
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Pipelining legality: rejected on a delay-free stencil
# ---------------------------------------------------------------------------

def test_pipelined_rejected_when_max_delay_zero():
    """A stencil with no axonal delay at all (no active offsets and
    min_delay_steps=0 => stencil.max_delay == 0) has no future step to
    defer the exchange into: the pipelined distributed run must raise at
    trace time, naming the fix."""
    conn = ConnectivityConfig(amp_lateral=0.0, min_delay_steps=0)
    cfg = DPSNNConfig(grid_h=2, grid_w=2, neurons_per_column=16, conn=conn,
                      exchange=ExchangeConfig(pipelined=True))
    from repro.core.connectivity import build_stencil
    assert build_stencil(cfg).max_delay == 0
    from repro.core import exchange
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    run, _ = exchange.make_distributed_run(cfg, mesh, n_steps=4)
    with pytest.raises(ValueError, match="pipelined"):
        run()


# ---------------------------------------------------------------------------
# Real OS-process ranks (multiprocess CI tier): pipelined fused equality
# ---------------------------------------------------------------------------

def _launch(args, timeout=900):
    from test_multiprocess import run_launcher
    return run_launcher(args, timeout=timeout)


@pytest.mark.parametrize("ranks,grid,neurons,steps", [
    (2, "4x4", 32, 40),
    (4, "8x8", 48, 60),
])
def test_pipelined_fused_real_ranks(ranks, grid, neurons, steps):
    """launch_distributed with --impl pallas_fused --pipelined across
    real OS processes (jax.distributed + gloo) produces spike totals
    bitwise-equal to the single-process fused run — the acceptance
    criterion's 4-rank real-process condition (and the 2-rank warmup)."""
    import json
    r = _launch(["--ranks", str(ranks), "--grid", grid,
                 "--neurons", str(neurons), "--steps", str(steps),
                 "--impl", "pallas_fused", "--pipelined"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["rank_count"] == ranks
    assert row["impl"] == "pallas_fused"
    assert row["pipelined"] is True
    assert row["single_process_match"] is True
