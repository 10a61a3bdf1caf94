"""Multi-process runtime: real OS-process ranks (jax.distributed + gloo)
must reproduce the single-process trajectory bitwise; the weak-scaling
config generator must hold per-rank load constant up to the paper's
1024-rank point (~11M neurons / ~20G synapses)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_launcher(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # the launcher's internal per-rank timeout must expire BEFORE the
    # outer kill below, so its cleanup still reaps the worker processes
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.launch_distributed",
         "--json", "-", "--timeout", str(timeout - 120), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    return r


# ---------------------------------------------------------------------------
# Real multi-process runs (each spawns ranks+1 fresh interpreters)
# ---------------------------------------------------------------------------

def test_two_ranks_bitwise_vs_single():
    """2 OS processes exchanging real gloo messages == single process."""
    r = run_launcher(["--ranks", "2", "--grid", "4x4", "--neurons", "32",
                      "--steps", "40"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["rank_count"] == 2
    assert row["single_process_match"] is True


def test_four_ranks_bitwise_vs_single():
    """The acceptance-criterion run: launch_distributed --ranks 4 produces
    spike totals bitwise-equal to the single-process run."""
    r = run_launcher(["--ranks", "4", "--grid", "8x8", "--neurons", "48",
                      "--steps", "60"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["rank_count"] == 4
    assert row["process_grid"] == [2, 2]
    # schema contract (benchmarks/compare.py gates on these keys)
    for key in ("rank_count", "step_ms", "events_per_s", "spikes",
                "events", "grid", "syn_equiv"):
        assert key in row, key


def test_weak_mode_scales_grid():
    """--weak reinterprets --grid as the per-rank tile and still matches
    the single-process run of the scaled grid bitwise."""
    r = run_launcher(["--ranks", "2", "--weak", "--grid", "4x4",
                      "--neurons", "32", "--steps", "30"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["grid"] == "4x8"  # 1x2 process grid x 4x4 tile


def test_hierarchical_exchange_bitwise_vs_single():
    """ISSUE 9 acceptance: 4 real OS-process ranks in 2 node groups
    (--ranks-per-node 2) on a multi-ring gauss_exp geometry reproduce
    the single-process trajectory bitwise, with the per-ring auto
    wire-format selection and STDP riding the aggregated node frames."""
    r = run_launcher(["--ranks", "4", "--ranks-per-node", "2",
                      "--family", "gauss_exp", "--radius", "6",
                      "--grid", "8x8", "--neurons", "32", "--steps", "40",
                      "--exchange-mode", "auto", "--aer-rate-bound", "100",
                      "--stdp"])
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "BITWISE-EQUAL" in r.stdout, r.stdout
    row = json.loads([ln for ln in r.stdout.splitlines()
                      if ln.startswith("{")][0])
    assert row["single_process_match"] is True
    assert row["ranks_per_node"] == 2
    assert row["node_grid"] == [2, 1]
    assert row["exchange_mode"] == "auto"
    # the hierarchical accounting rides the row (EXPERIMENTS.md §Topology)
    assert row["inter_node_bytes_per_node"] > 0
    assert row["inter_node_messages_per_node"] > 0
    assert {e["mode"] for e in row["per_ring_modes"]} <= \
        {"dense_packed", "aer_sparse"}


def test_ranks_per_node_rejects_unsupported_combos():
    """--ranks-per-node composes with neither batching nor the
    supervised checkpoint loop yet — both must fail fast, not corrupt."""
    r = run_launcher(["--ranks", "4", "--ranks-per-node", "2",
                      "--grid", "8x8", "--neurons", "16", "--steps", "10",
                      "--batch", "2"])
    assert r.returncode != 0
    combined = r.stdout + r.stderr
    assert "--ranks-per-node" in combined, combined


# ---------------------------------------------------------------------------
# Process-grid factorization + partition error (pure host-side)
# ---------------------------------------------------------------------------

def test_process_grid_factorization():
    from repro.core.partition import process_grid
    assert process_grid(1) == (1, 1)
    assert process_grid(2) == (1, 2)
    assert process_grid(4) == (2, 2)
    assert process_grid(8) == (2, 4)
    assert process_grid(12) == (3, 4)
    assert process_grid(1024) == (32, 32)
    for n in (1, 2, 3, 4, 6, 7, 8, 12, 16, 24, 100, 1024):
        ry, rx = process_grid(n)
        assert ry * rx == n and ry <= rx
    with pytest.raises(ValueError):
        process_grid(0)


def test_make_tile_spec_indivisible_error_names_geometry():
    """The divisibility failure must name the grid and the rank count,
    not silently mis-tile (ISSUE 3 satellite)."""
    from repro.configs.base import DPSNNConfig
    from repro.core.partition import make_rank_tile_spec, make_tile_spec

    cfg = DPSNNConfig(grid_h=5, grid_w=6, neurons_per_column=16)
    with pytest.raises(ValueError) as e:
        make_tile_spec(cfg, 2, 2)
    msg = str(e.value)
    assert "5x6" in msg          # the column grid
    assert "2x2" in msg          # the shard grid
    assert "4 ranks" in msg      # the rank count
    assert "with_ranks" in msg   # points at the fix
    assert "grid_h=5 % row_shards=2 = 1" in msg   # rendered, not %%-escaped

    with pytest.raises(ValueError):
        make_rank_tile_spec(cfg, 4)
    # divisible case succeeds and matches the explicit call
    ok = make_rank_tile_spec(DPSNNConfig(grid_h=6, grid_w=6,
                                         neurons_per_column=16), 4)
    assert (ok.tiles_y, ok.tiles_x, ok.tile_h, ok.tile_w) == (2, 2, 3, 3)


def test_exchange_axis_size_assertion():
    """A TileSpec that disagrees with the mesh fails at trace time with
    both geometries named (core/exchange.assert_axis_sizes)."""
    from tests._subproc import run_multidevice

    out = run_multidevice("""
import jax
from repro.configs.base import DPSNNConfig
from repro.core import exchange
from repro.core.partition import make_tile_spec
cfg = DPSNNConfig(grid_h=8, grid_w=8, neurons_per_column=16, seed=0)
mesh = jax.make_mesh((2, 2), ('data', 'model'))
wrong = make_tile_spec(cfg, 4, 1)  # 4x1 spec on a 2x2 mesh
def bad():
    frame = jax.numpy.zeros((wrong.tile_h, wrong.tile_w, 16))
    exchange.assert_axis_sizes(wrong, 'data', 'model')
    return frame
try:
    jax.shard_map(bad, mesh=mesh, in_specs=(),
                  out_specs=jax.sharding.PartitionSpec(),
                  check_vma=False)()
    print('NO-ERROR')
except ValueError as e:
    assert 'do not match the tile grid' in str(e), e
    assert '4x1' in str(e), e
    print('OK')
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Weak-scaling config generator (ISSUE 3 satellite: per-rank invariance
# + the paper's 1024-rank totals)
# ---------------------------------------------------------------------------

def test_with_ranks_constant_per_rank_load():
    from repro.configs.base import DPSNNConfig
    from repro.configs.dpsnn import with_ranks
    from repro.core.partition import make_rank_tile_spec, process_grid

    tile = DPSNNConfig(grid_h=3, grid_w=4, neurons_per_column=50)
    per_rank_neurons = tile.n_neurons
    per_rank_syn = tile.total_equivalent_synapses
    for n in (1, 2, 4, 8, 16, 64, 256, 1024):
        cfg = with_ranks(tile, n)
        ry, rx = process_grid(n)
        assert (cfg.grid_h, cfg.grid_w) == (3 * ry, 4 * rx)
        assert cfg.n_neurons == n * per_rank_neurons
        assert cfg.total_equivalent_synapses == n * per_rank_syn
        # the scaled grid always tiles evenly over its own rank count
        spec = make_rank_tile_spec(cfg, n)
        assert (spec.tile_h, spec.tile_w) == (3, 4)


def test_with_ranks_paper_point_1024():
    """with_ranks(RANK_TILE_PAPER, 1024) is the paper's headline run:
    96x96 columns, ~11M neurons, ~20G equivalent synapses."""
    from repro.configs.dpsnn import RANK_TILE_PAPER, with_ranks

    cfg = with_ranks(RANK_TILE_PAPER, 1024)
    assert (cfg.grid_h, cfg.grid_w) == (96, 96)
    assert cfg.n_neurons == 11_427_840          # ~11.4M (paper Table 2)
    assert 19e9 < cfg.total_equivalent_synapses < 21e9   # "up to 20G"
    assert cfg.neurons_per_column == 1240       # Table 1 column size
    # per-rank share matches the rank tile exactly
    assert cfg.n_neurons // 1024 == RANK_TILE_PAPER.n_neurons


def test_with_ranks_preserves_family_and_plasticity():
    import dataclasses

    from repro.configs.dpsnn import reduced_family, with_ranks

    tile = dataclasses.replace(
        reduced_family("gauss_exp", grid_h=2, grid_w=2, neurons=16),
        stdp=True)
    cfg = with_ranks(tile, 8)
    assert cfg.conn == tile.conn
    assert cfg.stdp is True
    assert (cfg.grid_h, cfg.grid_w) == (4, 8)
