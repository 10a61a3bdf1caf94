#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 benchmarks/chip/run.py --workload g24-static --seed 7 \\
        --seconds 10 --trace 0

``BENCHMARK.json`` (at the checkout's root) names the cell's configuration,
traffic and chips; ``cell.py`` says where each file lies. The last line of
standard output is one JSON object: ``correct``, ``attempted`` (steps
simulated in the window), ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number compared with the reference beside its limit. The
same numbers are the last lines of standard error; the lines before them
there are informational.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result. JAX's persistent compile cache is kept in
``.jax_cache`` at the checkout's root.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cell as cellmod

    cell = cellmod.load_cell(args.workload)
    # the cache lives in the checkout, at a fixed path, whatever the
    # environment says; the program's enable_compile_cache takes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        print(f"run.py: needs a TPU: {e}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache

    import harness

    enable_compile_cache()
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices[:cell.chips], T_START)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
