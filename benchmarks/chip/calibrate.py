#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload g24-static \\
        --seeds 101-112 --control-seeds 101-103 --calls 3

In one process (set-up is long and the network is the configuration's
own, so only the first state changes with the seed): for each seed, the
window's program from a state built from that seed, one warm call and
``--calls`` calls as a window makes them, then ``reference.compare`` of the
last call (the program's readings, for the lower end of each limit). For
each control seed the same, then the control (``reference.control_chunk``:
the reference in bfloat16, in the program's place) from the same state, and
its readings (the upper end). One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        if part:
            lo, _, hi = part.partition("-")
            out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(cell, seed_list, control_seeds, calls, devices, emit):
    import cell as cellmod
    import entries
    import reference

    cfg = cellmod.program_config(cell)
    net = cell.network
    entry = entries.make(cell, cfg, devices)
    compiled = False
    for seed in seed_list:
        s_state, t0 = entries.seed_words(seed)
        carry = entry.build(s_state, t0)
        if not compiled:
            entry.compile(carry)
            compiled = True
        for _ in range(calls + 1):
            prev = carry
            carry, done = entry.call(carry)
            done.block_until_ready()
        a = entry.snapshot(entry.drop_params(prev))
        b = entry.snapshot(entry.drop_params(carry))
        del prev, carry, done
        emit({"seed": seed, "side": "program", "t": a.t,
              **reference.compare(net, a, b, devices,
                                   cell.steps_per_call)})
        del b
        if seed in control_seeds:
            t1 = time.perf_counter()
            ctrl = reference.control_chunk(net, a, cell.steps_per_call,
                                           devices)
            emit({"seed": seed, "side": "control", "t": a.t,
                  "control_s": time.perf_counter() - t1,
                  **reference.compare(net, a, ctrl, devices,
                                     cell.steps_per_call)})
            del ctrl
        del a


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--calls", type=int, default=3,
                    help="calls after the warm one, as a window makes them")
    args = ap.parse_args(argv)

    import cell as cellmod

    cell = cellmod.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    devices = jax.devices("tpu")[:cell.chips]
    if len(devices) < cell.chips:
        print(f"needs {cell.chips} chips", file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    readings(cell, seeds(args.seeds), set(seeds(args.control_seeds)),
             args.calls, devices,
             lambda row: print(json.dumps({"workload": args.workload, **row}),
                               flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
