"""DPSNN simulation driver (the paper's workload).

    PYTHONPATH=src python -m repro.launch.sim --grid 8x8 --neurons 64 \
        --steps 500 [--devices 4] [--impl pallas_fused] [--pipelined] \
        [--no-compress]

``--preset 48x48-bf16`` (any key of ``configs/dpsnn.GRIDS``) runs one of
the paper's grids at its own size in place of ``--grid``/``--neurons``.

On a multi-device host (XLA_FLAGS=--xla_force_host_platform_device_count=N
or a real pod) the grid is tiled over a 2-D mesh with halo exchange;
otherwise the single-shard reference path runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import DPSNNConfig
from repro.configs.dpsnn import GRIDS
from repro.core import exchange, metrics as M, simulation as sim
from repro.runtime.compile_cache import enable_compile_cache


def parse_grid(s: str):
    h, w = s.split("x")
    return int(h), int(w)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", default="8x8")
    ap.add_argument("--preset", choices=sorted(GRIDS),
                    help="a paper grid (configs/dpsnn.py) in place of "
                         "--grid and --neurons")
    ap.add_argument("--neurons", type=int, default=64)
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--impl", default="ref",
                    choices=["ref", "pallas", "pallas_fused"])
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x2 (data x model); empty = single shard")
    ap.add_argument("--pipelined", action="store_true",
                    help="cross-step pipelined halo exchange (mesh runs)")
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--stdp", action="store_true")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()
    enable_compile_cache()

    gh, gw = parse_grid(args.grid)
    from repro.configs.base import ExchangeConfig
    cfg = DPSNNConfig(grid_h=gh, grid_w=gw, neurons_per_column=args.neurons)
    if args.preset:
        cfg = GRIDS[args.preset]
        gh, gw = cfg.grid_h, cfg.grid_w
    cfg = dataclasses.replace(
        cfg, stdp=args.stdp, seed=args.seed,
        exchange=ExchangeConfig(pipelined=args.pipelined))
    print(f"grid {gh}x{gw}, {cfg.n_neurons} neurons, "
          f"{cfg.recurrent_synapses/1e6:.1f}M recurrent synapses "
          f"({cfg.local_fanin}+{cfg.remote_fanin}/neuron), "
          f"plasticity {'ON (STDP)' if cfg.stdp else 'off'}")

    if args.mesh:
        dy, dx = parse_grid(args.mesh)
        mesh = jax.make_mesh((dy, dx), ("data", "model"))
        run, spec = exchange.make_distributed_run(
            cfg, mesh, n_steps=args.steps, impl=args.impl,
            compress=not args.no_compress)
        t0 = time.perf_counter()
        res = run()
        res.rate_hz.block_until_ready()
        dt = time.perf_counter() - t0
        rate, events = float(res.rate_hz), float(res.events)
    else:
        params, state = sim.build(cfg)
        t0 = time.perf_counter()
        res = sim.run(cfg, params, state, args.steps, impl=args.impl)
        res.rate_hz.block_until_ready()
        dt = time.perf_counter() - t0
        rate, events = float(res.rate_hz), float(res.events)
        print(f"bytes/synapse: "
              f"{M.bytes_per_synapse(cfg, params, res.state):.2f}")
        if cfg.stdp:
            dw = jnp.abs(res.params.w_local - params.w_local)
            print(f"STDP weight drift: mean |dw| "
                  f"{float(dw.sum() / (params.w_local != 0).sum()):.3e}, "
                  f"max {float(dw.max()):.3e}")

    sim_s = args.steps * cfg.neuron.dt_ms * 1e-3
    print(f"{args.steps} steps in {dt:.2f}s "
          f"(incl. compile) | rate {rate:.2f} Hz | "
          f"{events:.3e} synaptic events | "
          f"{dt/max(events,1):.3e} s/event | "
          f"{dt/sim_s:.1f}x slower than real time")


if __name__ == "__main__":
    main()
