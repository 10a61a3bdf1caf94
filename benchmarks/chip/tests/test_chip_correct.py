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


# ---------------------------------------------------------------------------
# Synapses stored in bfloat16 (the configuration's ``weight_dtype``)
# ---------------------------------------------------------------------------

def _weights(cell, dtype):
    """``cell`` with its synapses stored in ``dtype``."""
    net = {**cell.config["network"], "weight_dtype": dtype}
    return cell._replace(config={**cell.config, "network": net})


def test_bfloat16_synapses_pass_at_the_float32_level():
    """The reference rounds the weights as the program stores them, so the
    gap left is the float32 arithmetic's."""
    cell = _weights(small_cell("g24-static"), "bfloat16")
    out = harness.run_cell(cell, SEED, 0.0, False, jax.devices("cpu")[:1],
                           0.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["v_gap_mV"]["value"] <= 1e-4
    assert out["checks"]["spike_flips"]["value"] == 0
    assert out["checks"]["event_gap"]["value"] == 0


def test_bfloat16_synapses_control_fails():
    cell = _weights(small_cell("g24-static"), "bfloat16")
    rows = []
    calibrate.readings(cell, [11, 12], {11, 12}, 1, jax.devices("cpu")[:1],
                       rows.append)
    assert {r["side"] for r in rows} == {"program", "control"}
    for row in rows:
        failed = [k for k, lim in cell.limits.items() if not row[k] <= lim]
        assert bool(failed) == (row["side"] == "control"), row


def test_float32_synapses_under_a_bfloat16_label_read_far_apart():
    """The fault this comparison guards against: a reference whose weights
    are not the program's. A float32-synapse run compared as if its
    synapses were bfloat16 reads a gap far above the matched comparison."""
    import cell as cellmod
    import entries

    cell = small_cell("g24-static")
    devices = jax.devices("cpu")[:1]
    entry = entries.make(cell, cellmod.program_config(cell), devices)
    carry = entry.build(*entries.seed_words(SEED))
    entry.compile(carry)
    for _ in range(2):
        prev = carry
        carry, done = entry.call(carry)
        done.block_until_ready()
    a = entry.snapshot(entry.drop_params(prev))
    b = entry.snapshot(entry.drop_params(carry))
    k = cell.steps_per_call
    matched = reference.compare(cell.network, a, b, devices, k)
    labelled = reference.compare(_weights(cell, "bfloat16").network, a, b,
                                 devices, k)
    assert matched["v_gap_mV"] <= 1e-4
    assert labelled["v_gap_mV"] >= 100 * max(matched["v_gap_mV"], 1e-7)


def test_plastic_bfloat16_synapses_are_refused():
    """The reference does not round a plastic weight after each update, so
    a plastic network is compared at float32 weights only."""
    net = _weights(small_cell("g24-stdp"), "bfloat16").network
    with pytest.raises(ValueError, match="weight_dtype"):
        reference.compare(net, None, None, jax.devices("cpu")[:1], 1)
