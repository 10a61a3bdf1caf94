"""Batched multi-tenant service (DESIGN.md §Service): the B=1 bitwise
guarantee, tenant independence under packing, slot recycling with
staggered durations, and the batched halo exchange on real meshes —
single-shard, 2x2 spatial, batch-sharded, and 2 real OS-process ranks
(the ``real_ranks`` tests), for both spike-halo wire formats."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import dpsnn as D
from repro.core import batched, counters
from repro.core import simulation as sim

from tests._subproc import run_multidevice
from tests.test_multiprocess import run_launcher


def _cfg(stdp=False, seed=42):
    return D.reduced(4, 4, 32, seed=seed, stdp=stdp)


def _dedicated(cfg, seed, n_steps, impl="ref"):
    """The single-tenant reference for tenant ``seed``: shared
    connectivity from cfg.seed, per-tenant state + drive from seed."""
    params, _ = sim.build(cfg)
    state = sim.build(cfg, seed=jnp.int32(seed))[1]
    return sim.run(cfg, params, state, n_steps, impl=impl,
                   seed=jnp.int32(seed))


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


# ---------------------------------------------------------------------------
# B=1 bitwise parity: a single-slot batch IS the single-tenant path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "pallas_fused"])
@pytest.mark.parametrize("stdp", [False, True])
def test_b1_bitwise_equals_single_tenant(impl, stdp):
    """Full final state — spikes, history ring, counters, traces and
    (under STDP) the plastic weights — must match bitwise."""
    cfg = _cfg(stdp=stdp)
    n_steps = 25
    params, state0 = sim.build(cfg)
    ref = sim.run(cfg, params, state0, n_steps, impl=impl)

    seeds = jnp.array([cfg.seed], jnp.int32)
    out = batched.run_batched(cfg, batched.batch_params(cfg, params, 1),
                              batched.init_tenants(cfg, seeds), seeds,
                              n_steps, impl)
    for got, want in zip(_leaves(out.state), _leaves(ref.state)):
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want))
    if stdp:
        np.testing.assert_array_equal(
            np.asarray(out.params.w_local)[0], np.asarray(ref.params.w_local))
        np.testing.assert_array_equal(
            np.asarray(out.params.rem_w)[0], np.asarray(ref.params.rem_w))


def test_b1_nu_scale_one_is_bitwise_neutral():
    """nu_scale=1.0 multiplies the Poisson rate by exactly 1 — the
    stimulus path must not perturb the B=1 guarantee."""
    cfg = _cfg()
    params, state0 = sim.build(cfg)
    ref = sim.run(cfg, params, state0, 20)
    seeds = jnp.array([cfg.seed], jnp.int32)
    out = batched.run_batched(cfg, params, batched.init_tenants(cfg, seeds),
                              seeds, 20, "ref",
                              nu_scale=jnp.ones((1,), jnp.float32))
    for got, want in zip(_leaves(out.state), _leaves(ref.state)):
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want))


# ---------------------------------------------------------------------------
# B>1 independence: batch-mates are invisible to each tenant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stdp", [False, True])
def test_tenants_independent_of_batch_mates(stdp):
    """Each slot of a B=3 batch matches its dedicated single-tenant run
    bitwise — including per-tenant plastic weights under STDP."""
    cfg = _cfg(stdp=stdp)
    n_steps = 20
    seeds_py = [cfg.seed, cfg.seed + 7, cfg.seed + 13]
    seeds = jnp.array(seeds_py, jnp.int32)
    params, _ = sim.build(cfg)
    out = batched.run_batched(cfg, batched.batch_params(cfg, params, 3),
                              batched.init_tenants(cfg, seeds), seeds,
                              n_steps)
    for b, s in enumerate(seeds_py):
        ref = _dedicated(cfg, s, n_steps)
        for got, want in zip(_leaves(out.state), _leaves(ref.state)):
            np.testing.assert_array_equal(np.asarray(got)[b],
                                          np.asarray(want))
        if stdp:
            np.testing.assert_array_equal(
                np.asarray(out.params.w_local)[b],
                np.asarray(ref.params.w_local))


def test_raster_totals_match_counters():
    cfg = _cfg()
    seeds = jnp.array([cfg.seed, cfg.seed + 1], jnp.int32)
    params, _ = sim.build(cfg)
    out = batched.run_batched(cfg, params, batched.init_tenants(cfg, seeds),
                              seeds, 15)
    per_raster = np.asarray(out.raster).sum(axis=(0, 2, 3))
    np.testing.assert_array_equal(per_raster,
                                  counters.value(out.state.spike_count))


# ---------------------------------------------------------------------------
# Slot recycling: staggered durations through the serving layer
# ---------------------------------------------------------------------------

def test_run_chunk_freezes_finished_slots_and_exits_early():
    cfg = _cfg()
    seeds = jnp.array([cfg.seed, cfg.seed + 1], jnp.int32)
    params, _ = sim.build(cfg)
    bstate = batched.init_tenants(cfg, seeds)
    out = batched.run_chunk(cfg, params, bstate, seeds,
                            jnp.array([7, 15], jnp.int32), 64, "ref")
    assert int(out.steps_taken) == 15          # early exit, not 64
    assert [int(x) for x in out.steps_left] == [0, 0]
    for b, (s, n_steps) in enumerate(zip([int(x) for x in seeds], [7, 15])):
        ref = _dedicated(cfg, s, n_steps)
        np.testing.assert_array_equal(
            np.asarray(out.state.spike_count)[b],
            np.asarray(ref.state.spike_count))
        np.testing.assert_array_equal(np.asarray(out.state.lif.v)[b],
                                      np.asarray(ref.state.lif.v))


@pytest.mark.parametrize("stdp", [False, True])
def test_server_recycles_slots_under_staggered_durations(stdp):
    """More jobs than slots, staggered durations: every job's totals
    (and raster) must still be bitwise its dedicated run's, and slots
    must actually recycle."""
    from repro.launch.serve import BatchedSimServer, SimJob

    cfg = _cfg(stdp=stdp)
    server = BatchedSimServer(cfg, slots=2, chunk=8)
    jobs = [("a", cfg.seed, 10), ("b", cfg.seed + 3, 17),
            ("c", cfg.seed + 5, 6), ("d", cfg.seed + 9, 12)]
    for jid, seed, n in jobs:
        server.submit(SimJob(job_id=jid, seed=seed, n_steps=n))
    results = {r.job_id: r for r in server.drain()}
    assert set(results) == {"a", "b", "c", "d"}
    assert server.stats["recycles"] >= 2
    for jid, seed, n in jobs:
        ref = _dedicated(cfg, seed, n)
        r = results[jid]
        assert r.spikes == float(ref.spikes), jid
        assert r.events == float(ref.events), jid
        assert r.raster.shape[0] == n
        assert r.raster.sum() == r.spikes


def test_server_streams_chunks_in_order():
    from repro.launch.serve import BatchedSimServer, SimJob

    cfg = _cfg()
    got = []
    server = BatchedSimServer(cfg, slots=1, chunk=4, keep_raster=False)
    server.submit(SimJob(job_id="s", seed=cfg.seed, n_steps=10,
                         on_chunk=lambda jid, t0, fr: got.append(
                             (t0, fr.shape[0]))))
    [res] = server.run()
    assert res.raster is None                  # keep_raster=False streams
    assert got == [(0, 4), (4, 4), (8, 2)]     # 10 steps in 4-step chunks
    ref = _dedicated(cfg, cfg.seed, 10)
    assert res.spikes == float(ref.spikes)


# ---------------------------------------------------------------------------
# Batched halo exchange: forced multi-device meshes, both wire formats
# ---------------------------------------------------------------------------

_DIST_SNIPPET = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import dpsnn as D
from repro.core import exchange, simulation as sim
{mesh_setup}
base = D.reduced(4, 4, 16, seed=42)
cfg = dataclasses.replace(
    base, conn=dataclasses.replace(base.conn, exchange_mode="{xmode}"))
run, spec = exchange.make_batched_distributed_run(
    cfg, mesh, n_steps=12, batch=2)
seeds = cfg.seed + jnp.arange(2, dtype=jnp.int32)
res = run(seeds)
params, _ = sim.build(cfg)
for b in range(2):
    s = jnp.int32(cfg.seed + b)
    state = sim.build(cfg, seed=s)[1]
    ref = sim.run(cfg, params, state, 12, seed=s)
    assert float(res.spikes[b]) == float(ref.spikes), (
        b, float(res.spikes[b]), float(ref.spikes))
    assert float(res.events[b]) == float(ref.events), b
print("OK", [float(x) for x in res.spikes])
"""

_SPATIAL_MESH = (
    "mesh = jax.make_mesh((2, 2), ('data', 'model'))")
_SERVICE_MESH = (
    "from repro.runtime.sharding import service_mesh\n"
    "mesh = service_mesh(2, 2, 1)")


@pytest.mark.parametrize("xmode", ["dense_packed", "aer_sparse"])
def test_batched_halo_2x2_spatial_mesh(xmode):
    """B=2 tenants over a 2x2 spatial mesh (no batch axis): every tenant
    matches its dedicated single-shard run bitwise, both wire formats."""
    out = run_multidevice(_DIST_SNIPPET.format(
        mesh_setup=_SPATIAL_MESH, xmode=xmode))
    assert "OK" in out


@pytest.mark.parametrize("xmode", ["dense_packed", "aer_sparse"])
def test_batched_halo_batch_sharded_mesh(xmode):
    """The same tenants sharded over the mesh's 'batch' axis (orthogonal
    to a 2x1 spatial mesh) — sharding the tenant axis must not change a
    single spike."""
    out = run_multidevice(_DIST_SNIPPET.format(
        mesh_setup=_SERVICE_MESH, xmode=xmode))
    assert "OK" in out


def test_batched_batch_indivisible_error_names_both():
    """batch must divide the mesh's batch axis; the error names both
    numbers (validated before any device work)."""
    import types

    from repro.core import exchange

    cfg = _cfg()
    fake = types.SimpleNamespace(
        shape={"batch": 2, "data": 1, "model": 1},
        axis_names=("batch", "data", "model"))
    with pytest.raises(ValueError, match="batch=3.*2 shards"):
        exchange.make_batched_distributed_run(cfg, fake, n_steps=2,
                                              batch=3)


def test_service_mesh_device_count_error():
    from repro.runtime.sharding import service_mesh

    with pytest.raises(ValueError, match="needs 8 devices"):
        service_mesh(2, 2, 2, devices=jax.devices()[:1])


def test_tenant_pspec_follows_mesh_axes():
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.runtime.sharding import (batch_shards, service_mesh,
                                        tenant_pspec)

    mesh = service_mesh(1, 1, 1, devices=jax.devices()[:1])
    assert batch_shards(mesh) == 1
    assert tenant_pspec(mesh, 1) == P("batch")
    assert tenant_pspec(mesh, 3) == P("batch", None, None)
    spatial = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                   ("data", "model"))
    assert batch_shards(spatial) == 1
    assert tenant_pspec(spatial, 2) == P(None, None)


# ---------------------------------------------------------------------------
# Real OS-process ranks (deselected in the multidevice tier via
# -k "not real_ranks"; the multiprocess tier runs them)
# ---------------------------------------------------------------------------

def test_real_ranks_batched_launcher_bitwise():
    """2 OS processes x 2 tenants: the launcher's per-tenant bitwise
    check against dedicated single-process runs must pass."""
    import json

    r = run_launcher(["--ranks", "2", "--batch", "2", "--grid", "4x4",
                      "--neurons", "32", "--steps", "20",
                      "--timed-reps", "1"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["batch_size"] == 2
    assert row["rank_count"] == 2
    assert row["single_process_match"] is True
    assert len(row["per_tenant_spikes"]) == 2


def test_real_ranks_batch_sharded_launcher_bitwise():
    """The tenant axis sharded over the 2 ranks (--batch-shards 2): each
    rank owns one tenant's full grid; totals still bitwise per tenant."""
    import json

    r = run_launcher(["--ranks", "2", "--batch", "2", "--batch-shards",
                      "2", "--grid", "4x4", "--neurons", "32",
                      "--steps", "20", "--timed-reps", "1"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["batch_shards"] == 2
    assert row["process_grid"] == [2, 1, 1]
    assert row["single_process_match"] is True
