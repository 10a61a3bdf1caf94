"""Radius-R multi-ring halo exchange: stencil-radius derivation, ring
math, bitwise mesh==single-shard equivalence for the long-range
connectivity families (incl. tiles thinner than the radius), the
overlap-window trace-time guard, and the tiled ELL kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_multidevice
from repro.configs.base import ConnectivityConfig, DPSNNConfig
from repro.core.connectivity import build_stencil
from repro.core.exchange import halo_ring_widths
from repro.core.partition import make_tile_spec


def _exp_cfg(radius=2, **kw):
    conn = ConnectivityConfig(lateral_profile="exponential", amp_exp=0.03,
                              lambda_steps=2.0, radius=radius)
    return DPSNNConfig(conn=conn, **kw)


# ---------------------------------------------------------------------------
# Stencil-radius derivation and ring math (host-side, no devices)
# ---------------------------------------------------------------------------

def test_gaussian_default_derives_radius_2():
    """The 2015 paper's Gaussian stencil with the 1e-3 cutoff activates
    only a 5x5 interior of its 7x7 bound: derived halo radius is 2."""
    cfg = DPSNNConfig()
    assert cfg.conn.radius == 3
    assert build_stencil(cfg).radius == 2
    assert cfg.stencil_radius == 2


def test_exponential_reaches_the_stencil_bound():
    cfg = _exp_cfg(radius=4)
    st = build_stencil(cfg)
    assert st.radius == 4
    # long-range tail: offsets strictly beyond the Gaussian's reach
    assert any(max(abs(dy), abs(dx)) > 2 for dy, dx, *_ in st.offsets)


def test_gauss_exp_superposes_both_profiles():
    g = DPSNNConfig()
    ge = DPSNNConfig(conn=dataclasses.replace(
        g.conn, lateral_profile="gauss_exp", amp_exp=0.03, lambda_steps=2.0,
        radius=6))
    probs_g = {(dy, dx): p for dy, dx, p in g.stencil_offsets()}
    probs_ge = {(dy, dx): p for dy, dx, p in ge.stencil_offsets()}
    # every Gaussian offset survives with a strictly larger probability
    for k, p in probs_g.items():
        assert probs_ge[k] > p
    assert ge.stencil_radius > g.stencil_radius


def test_unknown_profile_raises():
    cfg = DPSNNConfig(conn=ConnectivityConfig(lateral_profile="cauchy"))
    with pytest.raises(ValueError, match="lateral_profile"):
        cfg.stencil_offsets()


def test_halo_ring_widths():
    assert halo_ring_widths(0, 4) == []
    assert halo_ring_widths(2, 4) == [2]          # classic single ring
    assert halo_ring_widths(4, 4) == [4]
    assert halo_ring_widths(5, 4) == [4, 1]       # multi-ring
    assert halo_ring_widths(9, 2) == [2, 2, 2, 2, 1]
    for r, d in [(1, 1), (3, 2), (7, 3), (8, 4)]:
        ws = halo_ring_widths(r, d)
        assert sum(ws) == r
        assert len(ws) == -(-r // d)
        assert all(ws[i] >= ws[i + 1] for i in range(len(ws) - 1))


def test_tile_spec_allows_tiles_thinner_than_radius():
    cfg = _exp_cfg(radius=3, grid_h=4, grid_w=4, neurons_per_column=16)
    spec = make_tile_spec(cfg, 2, 2)
    assert (spec.tile_h, spec.tile_w) == (2, 2)
    assert spec.radius == 3
    assert (spec.rings_y, spec.rings_x) == (2, 2)
    assert spec.permutes_per_step == 8
    # the classic one-ring regime keeps the 4 ppermutes/step of DESIGN §2
    gauss = DPSNNConfig(grid_h=8, grid_w=8, neurons_per_column=16)
    spec1 = make_tile_spec(gauss, 2, 2)
    assert (spec1.rings_y, spec1.rings_x) == (1, 1)
    assert spec1.permutes_per_step == 4


# ---------------------------------------------------------------------------
# Bitwise mesh == single-shard equivalence (subprocess, 4 devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grid,neurons,radius,profile", [
    (8, 32, 2, "exponential"),   # radius-2 long-range, tile 4 >= r
    (4, 40, 3, "gauss_exp"),     # tile 2 < r=3: multi-ring (2 rings/dir)
])
def test_radius_R_mesh_equivalence_bitwise(grid, neurons, radius, profile):
    """A radius>=2 long-range run on a 2x2 mesh is bitwise-equal to the
    single-shard oracle: same spike total AND bitwise-equal final f32
    plastic weights per column (STDP on, so a mis-sequenced or truncated
    halo would compound into the weights within a few steps)."""
    out = run_multidevice(f"""
import dataclasses
import numpy as np
import jax
from repro.configs.base import DPSNNConfig, ConnectivityConfig, STDPConfig
from repro.core import exchange, simulation as sim
from repro.core.connectivity import build_stencil
from repro.core.partition import tile_column_ids

conn = ConnectivityConfig(lateral_profile={profile!r}, amp_exp=0.03,
                          lambda_steps=2.0, radius={radius})
cfg = DPSNNConfig(grid_h={grid}, grid_w={grid},
                  neurons_per_column={neurons}, seed=3, conn=conn,
                  stdp=True, stdp_cfg=STDPConfig(a_plus=0.05, a_minus=0.055))
assert build_stencil(cfg).radius == {radius}
params, state = sim.build(cfg)
ref = sim.run(cfg, params, state, 60)
mesh = jax.make_mesh((2, 2), ('data', 'model'))
run, spec = exchange.make_distributed_run(cfg, mesh, n_steps=60,
                                          with_state=True)
res, st = run()
assert float(res.spikes) == float(ref.spikes), \\
    (float(res.spikes), float(ref.spikes))
assert float(res.events) == float(ref.events)
stacked = jax.device_get(st)
wl = np.asarray(stacked.plastic.w_local)
rw = np.asarray(stacked.plastic.rem_w)
wl_ref = np.asarray(ref.params.w_local)
rw_ref = np.asarray(ref.params.rem_w)
for ty in range(2):
    for tx in range(2):
        s = ty * 2 + tx
        ids = np.asarray(tile_column_ids(cfg, spec, ty, tx))
        assert np.array_equal(wl[s], wl_ref[ids]), ('w_local', ty, tx)
        assert np.array_equal(rw[s], rw_ref[ids]), ('rem_w', ty, tx)
print('OK', spec.rings_y, spec.rings_x, float(ref.spikes))
""")
    assert "OK" in out


def test_multi_ring_static_equivalence_across_meshes():
    """Static multi-ring runs agree bitwise across 2x2 / 1x4 / 4x1 tilings
    (different ring counts per axis on the same stencil)."""
    out = run_multidevice("""
import jax
from repro.configs.base import DPSNNConfig, ConnectivityConfig
from repro.core import exchange, simulation as sim
conn = ConnectivityConfig(lateral_profile='gauss_exp', amp_exp=0.03,
                          lambda_steps=2.0, radius=3)
cfg = DPSNNConfig(grid_h=4, grid_w=4, neurons_per_column=40, seed=0,
                  conn=conn)
params, state = sim.build(cfg)
ref = sim.run(cfg, params, state, 60)
for shape in [(2, 2), (1, 4), (4, 1)]:
    mesh = jax.make_mesh(shape, ('data', 'model'))
    run, spec = exchange.make_distributed_run(cfg, mesh, n_steps=60)
    res = run()
    assert float(res.spikes) == float(ref.spikes), \\
        (shape, float(res.spikes), float(ref.spikes))
print('OK', float(ref.spikes))
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Overlap-window guard (trace-time error; single device suffices)
# ---------------------------------------------------------------------------

def test_short_delay_stencil_rejected_at_trace_time():
    """A stencil whose remote delay is < 2 steps cannot ride the
    comm/compute overlap window: make_distributed_run must raise at
    trace time, not deliver stale halos."""
    conn = ConnectivityConfig(min_delay_steps=1, delay_per_step=0.0)
    cfg = DPSNNConfig(grid_h=2, grid_w=2, neurons_per_column=16, conn=conn)
    stencil = build_stencil(cfg)
    assert any(d < 2 for (_, _, _, d, _) in stencil.offsets)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from repro.core import exchange
    run, _ = exchange.make_distributed_run(cfg, mesh, n_steps=4)
    with pytest.raises(ValueError, match="overlap requires"):
        run()
