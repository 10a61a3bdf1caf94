"""Every cell that BENCHMARK.json names finds its files, and every file
states what it must."""
import json
import os

import pytest

from conftest import CHIP, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_loads_with_every_field_stated(workload):
    import cell as cellmod

    c = cellmod.load_cell(workload)
    cfg = cellmod.program_config(c)
    assert cfg.nu_ext_hz == c.traffic["nu_ext_hz"]
    assert c.steps_per_call >= 1
    assert set(c.limits) >= {"v_gap_mV", "c_gap", "spike_flips", "event_gap"}
    if cfg.stdp:
        assert set(c.limits) >= {"trace_gap", "w_gap", "w_fixed_gap"}


def test_a_missing_field_is_refused():
    import cell as cellmod

    c = cellmod.load_cell(WORKLOADS[0])
    net = dict(c.config["network"])
    del net["c_ext"]
    with pytest.raises(ValueError, match="c_ext"):
        cellmod.program_config(c._replace(config={**c.config,
                                                  "network": net}))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert os.path.exists(os.path.join(CHIP, "metrics", metric + ".py"))


def test_configs_match_the_program_presets():
    """The files state the program's own paper presets, uncut."""
    import dataclasses

    import cell as cellmod
    from repro.configs import dpsnn

    want = {"g24-static": dpsnn.GRID_24,
            "g24-stdp": dataclasses.replace(dpsnn.GRID_24, stdp=True),
            "g48-mesh4": dpsnn.GRID_48}
    for workload, preset in want.items():
        cfg = cellmod.program_config(cellmod.load_cell(workload))
        assert dataclasses.replace(cfg, name=preset.name) == preset
