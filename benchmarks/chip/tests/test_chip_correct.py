"""``correct`` at a size the CPU holds: the program's runs pass every
limit, the bfloat16 control fails, and so does a run whose timed path is
broken underneath.

These drive ``harness.run_cell`` (everything a run does but the look for a
TPU) and ``calibrate.readings`` on small cells, with the cells' own limits.
"""
import jax
import jax.numpy as jnp
import pytest

import calibrate
import harness
import reference
from conftest import small_cell

SEED = 2**31 + 12345          # seeds past 32 signed bits are allowed


@pytest.fixture(autouse=True)
def several_blocks(monkeypatch):
    """Blocks of two columns, so the reference runs block by block as it
    does at full size."""
    monkeypatch.setattr(reference, "BLOCK_BYTES", 1 << 20)


def _run(workload, hook=None):
    cell = small_cell(workload)
    devices = jax.devices("cpu")[:cell.chips]
    return harness.run_cell(cell, SEED, 0.0, False, devices, 0.0,
                            window_hook=hook)


def _failed(out):
    return [k for k, c in out["checks"].items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("workload", ["g24-static", "g24-stdp", "g48-mesh4"])
def test_program_passes_and_control_fails(workload):
    cell = small_cell(workload)
    rows = []
    calibrate.readings(cell, [11, 12], {11, 12}, 1,
                       jax.devices("cpu")[:cell.chips], rows.append)
    for row in rows:
        failed = [k for k, lim in cell.limits.items() if not row[k] <= lim]
        if row["side"] == "program":
            assert not failed, row
        else:
            assert failed, row


def _state(carry):
    """The one-chip carry's state, or the mesh's stacked state."""
    return carry if hasattr(carry, "lif") else carry[1]


def _with_state(carry, state):
    return state if hasattr(carry, "lif") else (carry[0], state)


def _unchanged(entry):
    return lambda carry: (carry, jnp.zeros(()))


def _half_left_out(entry):
    """Half of the columns keep their membrane state from before the call."""
    def call(carry):
        out, done = entry.call(carry)
        s0, s1 = _state(carry), _state(out)
        half = s1.lif.v.shape[-2] // 2
        v = s1.lif.v.at[..., half:, :].set(s0.lif.v[..., half:, :])
        return _with_state(out, s1._replace(lif=s1.lif._replace(v=v))), done
    return call


def _one_answer_altered(entry):
    def call(carry):
        out, done = entry.call(carry)
        s1 = _state(out)
        v = s1.lif.v.at[..., 0, 0].add(1.0)
        return _with_state(out, s1._replace(lif=s1.lif._replace(v=v))), done
    return call


FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out,
          "one_answer_altered": _one_answer_altered}


@pytest.mark.parametrize("workload", ["g24-static", "g24-stdp", "g48-mesh4"])
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"wall_s_per_sim_s", "setup_s"}
    assert out["device"]["memory_peak_bytes"] > 0
    assert list(out)[-1] == "checks"


class _Chip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return {"peak_bytes_in_use": self.peak}


def test_memory_peak_is_the_program_s_where_the_allocator_reads_less():
    program = [{"total_bytes": 8_000}]
    low = harness.device_info([_Chip(100), _Chip(200)], program)
    assert low["memory_peak_bytes"] == 8_000
    assert low["memory_peak_source"] == "memory_analysis"
    high = harness.device_info([_Chip(9_000), _Chip(10)], program)
    assert high["memory_peak_bytes"] == 9_000
    assert high["memory_peak_source"] == "peak_bytes_in_use"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["g24-static", "g24-stdp", "g48-mesh4"])
def test_broken_timed_path_is_not_correct(workload, fault):
    out = _run(workload, hook=FAULTS[fault])
    assert not out["correct"], out["checks"]
    assert _failed(out)


def test_mesh_without_its_halo_exchange_is_not_correct(monkeypatch):
    """The exchange between chips left out: each tile sees zeros past its
    edge, as if it were the sheet's."""
    from repro.core import exchange

    def no_exchange(frame, spec, *a, **kw):
        r = spec.radius
        return jnp.pad(frame, ((r, r), (r, r), (0, 0)))

    monkeypatch.setattr(exchange, "exchange_halo", no_exchange)
    out = _run("g48-mesh4")
    assert not out["correct"], out["checks"]
    assert "v_gap_mV" in _failed(out)
