#!/usr/bin/env python3
"""Compile each cell's window program for a described TPU v5e, without a
chip, and print its memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py [workload ...]

One call of the window's program (``steps_per_call`` steps) at the cell's
real sizes, for one chip of a described ``v5e:2x2`` or, for a cell on four
chips, its 2x2 mesh, with the Pallas kernels compiled by Mosaic. Nothing
runs: this finds what the chip's compiler refuses and whether the program
fits a chip's memory before any chip time is spent. One JSON line per
cell: argument, output, alias and temp bytes per chip, and their total.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    import cell as cellmod
    import entries
    from repro.core import simulation as sim
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    # the one interpret decision sees the CPU here: compile the kernels
    ops.interpret_mode = lambda backend=None: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in (argv or names):
        cell = cellmod.load_cell(name)
        cfg = cellmod.program_config(cell)
        if cell.mesh:
            drv = entries.Mesh(cfg, cell.config["impl"], cell.steps_per_call,
                               topo.devices[:cell.chips], cell.mesh)
            shard = NamedSharding(drv.mesh, P(tuple(drv.mesh.axis_names)))
            struct = jax.eval_shape(drv.init_fn, 0, 0)
            carry = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=shard), struct)
        else:
            drv = entries.OneChip(cfg, cell.config["impl"],
                                  cell.steps_per_call, topo.devices[0])
            one = SingleDeviceSharding(topo.devices[0])
            carry = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one),
                jax.eval_shape(lambda: sim.build(cfg)))
        exe = drv.compile(carry)
        text = exe.as_text()
        print(json.dumps({
            "workload": name, "chips": cell.chips,
            "memory_per_chip": drv.memory()[0],
            "mosaic_kernel": "tpu_custom_call" in text,
            "collective_permute": "collective-permute" in text}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
