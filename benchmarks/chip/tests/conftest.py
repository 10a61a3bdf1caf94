"""The benchmark's own tests, on the CPU at small sizes:

    pytest benchmarks/chip/tests

Four virtual CPU devices stand in for the 2x2 mesh; the Pallas kernels run
interpreted; the persistent compile cache stays off.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

def small_cell(workload: str, grid: int = 4, neurons: int = 128):
    """A benchmark cell cut to a size the CPU runs in seconds: ``grid``
    columns a side (twice that over a mesh) of ``neurons`` each."""
    import cell as cellmod

    c = cellmod.load_cell(workload)
    return shrink(c, grid, neurons)


def shrink(c, grid: int = 4, neurons: int = 128):
    """``c`` with ``grid`` columns a side (twice that over a mesh) of
    ``neurons`` each."""
    config = dict(c.config)
    net = dict(config["network"])
    g = 2 * grid if c.mesh else grid
    net.update(grid_h=g, grid_w=g, neurons_per_column=neurons)
    config["network"] = net
    return c._replace(config=config)

