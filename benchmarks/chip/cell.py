"""One benchmark cell, found by name: its configuration, traffic and limits.

``BENCHMARK.json`` at the checkout's root names each cell (``workloads``),
its configuration (``configs[].file``) and its traffic mix. Everything
else the harness needs sits in files of its own under this directory:

* ``configs/<config>.json``: the network as it is run. ``network`` holds
  every field of the program's ``DPSNNConfig`` (nested groups whole) but
  the drive rate; ``impl``, ``mesh`` and ``steps_per_call`` say how the
  window drives it.
* ``traffic/<traffic>.json``: the drive (``nu_ext_hz``) and the steps run
  in set-up before the window (``settle_steps``).
* ``limits/<workload>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from.

A later cell adds files and entries; no file here needs an edit for it.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell(NamedTuple):
    workload: str
    chips: int
    config: dict          # configs/<name>.json as read
    traffic: dict         # traffic/<name>.json as read
    limits: dict          # limits/<workload>.json "limits": name -> limit
    per_layer: list       # BENCHMARK.json per_layer entries that apply

    @property
    def network(self) -> dict:
        """The plain numbers of the network and its drive (what the
        reference reads)."""
        return {**self.config["network"],
                "nu_ext_hz": self.traffic["nu_ext_hz"]}

    @property
    def steps_per_call(self) -> int:
        return int(self.config["steps_per_call"])

    @property
    def mesh(self):
        m = self.config.get("mesh")
        return tuple(m) if m else None


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, bench_path: str | None = None) -> Cell:
    bench = _load(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if workload not in wl:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(wl)}")
    w = wl[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(ROOT, cfgs[w["config"]]["file"]))
    traffic = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    limits = _load(os.path.join(HERE, "limits", workload + ".json"))
    return Cell(
        workload=workload, chips=int(w["chips"]), config=config,
        traffic=traffic, limits=limits["limits"],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def _strict(cls, values: dict, where: str):
    """``cls(**values)`` with every field given and no other: the file, not
    the program's defaults, states the configuration."""
    names = {f.name for f in dataclasses.fields(cls)}
    missing, extra = names - set(values), set(values) - names
    if missing or extra:
        raise ValueError(f"{where}: missing {sorted(missing)}, "
                         f"unknown {sorted(extra)}")
    return cls(**values)


def program_config(cell: Cell) -> Any:
    """The program's ``DPSNNConfig`` for this cell."""
    from repro.configs import base

    net = dict(cell.network)
    nested = {"neuron": base.NeuronConfig, "conn": base.ConnectivityConfig,
              "exchange": base.ExchangeConfig, "stdp_cfg": base.STDPConfig,
              "guard": base.GuardConfig}
    for key, cls in nested.items():
        net[key] = _strict(cls, net[key], f"{cell.config['name']}.{key}")
    net["name"] = cell.config["name"]
    return _strict(base.DPSNNConfig, net, cell.config["name"])
