"""The 48x48 grid on one chip with bfloat16 synapses (cell ``g48-bf16``):
its file states the program's preset, and at a size the CPU holds, with
the column count a whole number of 128-lane tiles as at full size and the
local synapse table stored lane-padded as the program stores it there,
the program passes its limits and the bfloat16 control fails them."""
import dataclasses

import jax
import pytest

import calibrate
import harness
import reference
from conftest import small_cell

SEED = 2**31 + 4321


@pytest.fixture(autouse=True)
def several_blocks(monkeypatch):
    monkeypatch.setattr(reference, "BLOCK_BYTES", 1 << 20)


def test_config_is_the_program_preset():
    import cell as cellmod
    from repro.configs import dpsnn

    cfg = cellmod.program_config(cellmod.load_cell("g48-bf16"))
    assert cfg.weight_dtype == "bfloat16"
    assert dataclasses.replace(
        cfg, name=dpsnn.GRID_48_BF16.name) == dpsnn.GRID_48_BF16


def _lane_padded_cell():
    """16x16 columns (256, two lane tiles) of 120 neurons: a TPU lays
    such a local table out column-minor, so it is stored 128 targets
    wide; the remote one (K = 24) is too narrow to pad."""
    from repro.core import network as net

    cell = small_cell("g48-bf16", grid=16, neurons=120)
    st = reference.stencil(cell.network)
    assert net.lane_width((256, 120, 120), 2) == 128
    assert net.lane_width((256, 120, st.k_total), 2, 4) == st.k_total
    return cell


def test_lane_padded_program_passes_at_the_float32_level():
    cell = _lane_padded_cell()
    out = harness.run_cell(cell, SEED, 0.0, False, jax.devices("cpu")[:1],
                           0.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["v_gap_mV"]["value"] <= 1e-4
    assert out["checks"]["spike_flips"]["value"] == 0
    assert out["checks"]["event_gap"]["value"] == 0


def test_lane_padded_control_fails():
    cell = _lane_padded_cell()
    rows = []
    calibrate.readings(cell, [11], {11}, 1, jax.devices("cpu")[:1],
                       rows.append)
    assert {r["side"] for r in rows} == {"program", "control"}
    for row in rows:
        failed = [k for k, lim in cell.limits.items() if not row[k] <= lim]
        assert bool(failed) == (row["side"] == "control"), row
