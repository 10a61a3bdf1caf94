"""The program's entry points as the window drives them.

``OneChip`` drives ``core/simulation.run`` (``impl`` from the
configuration) with a fixed chunk of steps per call and carries the state,
and under STDP the plastic params, from call to call. ``Mesh`` drives
``core/exchange.make_distributed_resume`` over a mesh of chips and carries
the stacked per-shard state. Both build their first state on the device
from the seed: the membrane potentials from one seed word (the program's
``init_state``) and the step counter's start, which keys the drive stream,
from another. The connectivity is the configuration's own (its ``seed``).

Each call is compiled ahead of the window (``lower().compile()``), so a
call in the window cannot compile, and ends in ``block_until_ready`` on a
scalar of its result, not on a transfer of the state.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

import reference


def seed_words(seed: int) -> tuple[int, int]:
    """Two words from a seed of any size: the int32 membrane-potential seed
    and the step counter's start (below 2**24, far from int32 overflow)."""
    w = np.random.SeedSequence(seed).generate_state(2)
    return int(w[0] >> 1), int(w[1] % (1 << 24))


class OneChip:
    def __init__(self, cfg, impl: str, steps: int, device):
        self.cfg, self.impl, self.steps, self.device = cfg, impl, steps, device
        self.device_kind = device.device_kind
        self.exe = None

    @functools.cached_property
    def _make(self):
        from repro.core import simulation as sim

        def make(s, t):
            params, state = sim.build(self.cfg, seed=s)
            return params, state._replace(t=t)

        return jax.jit(make)

    def build(self, seed: int, t0: int):
        with jax.default_device(self.device):
            carry = self._make(jnp.int32(seed), jnp.int32(t0))
        return jax.block_until_ready(carry)

    def compile(self, carry):
        from repro.core import simulation as sim

        params, state = carry
        self.exe = sim.run.lower(self.cfg, params, state, self.steps,
                                 impl=self.impl).compile()
        return self.exe

    def call(self, carry):
        """Dispatch one call; returns the next carry and the scalar to wait
        on. A static run's result carries a copy of the unchanged params;
        the next call is given the originals and the copy is dropped."""
        params, state = carry
        res = self.exe(params, state)
        nxt = res.params if self.cfg.stdp else params
        return (nxt, res.state), res.spikes

    def counters(self, carry) -> tuple[int, int]:
        state = carry[1]
        return (reference.counter_value(state.spike_count),
                reference.counter_value(state.event_count))

    def drop_params(self, carry):
        """The carry without the static weights, which the reference
        regenerates (under STDP the weights are state and stay)."""
        params, state = carry
        return (params if self.cfg.stdp else None, state)

    def snapshot(self, carry) -> reference.Snapshot:
        params, state = carry
        d = state.hist.shape[0]
        t = int(state.t)
        frame0 = t - d
        frames = state.hist[jnp.asarray([(frame0 + i) % d for i in range(d)])]
        snap = reference.Snapshot(
            t=t, v=state.lif.v, c=state.lif.c, refrac=state.lif.refrac,
            frames=frames, frame0=frame0,
            spikes=reference.counter_value(state.spike_count),
            events=reference.counter_value(state.event_count))
        if self.cfg.stdp:
            snap = snap._replace(x_pre=state.stdp.x_pre,
                                 x_post=state.stdp.x_post,
                                 w_local=params.w_local, rem_w=params.rem_w)
        return snap

    def memory(self) -> list:
        return [_memory_analysis(self.exe)]


class Mesh:
    def __init__(self, cfg, impl: str, steps: int, devices, shape):
        from jax.sharding import Mesh as JMesh

        from repro.core import exchange

        if cfg.stdp:
            raise ValueError("the mesh entry carries no plastic weights")
        self.cfg, self.steps = cfg, steps
        self.device_kind = devices[0].device_kind
        self.mesh = JMesh(np.array(devices).reshape(shape), ("data", "model"))
        self.run, self.spec = exchange.make_distributed_resume(
            cfg, self.mesh, n_steps=steps, impl=impl)
        self.exe = None

    @functools.cached_property
    def init_fn(self):
        """Jitted ``(seed, t0) -> stacked state``: each shard's first state
        from the program's ``init_shard``, one shard per chip."""
        from jax.sharding import PartitionSpec as P

        from repro.core import exchange
        from repro.core.connectivity import build_stencil

        row_axes, col_axis, _, _, _ = exchange.mesh_layout(self.mesh)
        stencil = build_stencil(self.cfg)
        joint = tuple(self.mesh.axis_names)

        def init(s, t):
            st = exchange.init_shard(self.cfg, self.spec, stencil, row_axes,
                                     col_axis, seed=s)
            st = st._replace(t=t)
            return jax.tree_util.tree_map(lambda x: x[None], st)

        return jax.jit(jax.shard_map(init, mesh=self.mesh,
                                     in_specs=(P(), P()), out_specs=P(joint),
                                     check_vma=False))

    def build(self, seed: int, t0: int):
        return jax.block_until_ready(
            self.init_fn(jnp.int32(seed), jnp.int32(t0)))

    def compile(self, carry):
        self.exe = self.run.lower(carry).compile()
        return self.exe

    def call(self, carry):
        res, stacked = self.exe(carry)
        return stacked, res.spikes

    def counters(self, stacked) -> tuple[int, int]:
        return (reference.counter_value(jax.device_get(stacked.spike_count)),
                reference.counter_value(jax.device_get(stacked.event_count)))

    def drop_params(self, carry):
        return carry

    def snapshot(self, stacked) -> reference.Snapshot:
        host = jax.device_get(stacked)
        sp = self.spec
        r, th, tw = sp.radius, sp.tile_h, sp.tile_w
        gh, gw = self.cfg.grid_h, self.cfg.grid_w
        n = self.cfg.neurons_per_column
        t = int(host.t[0])
        d = host.hist_ext.shape[1]

        def glob(tiles):
            """(S, th, tw, ...) per-shard tiles -> (C, ...) global order."""
            out = np.zeros((gh, gw) + tiles.shape[3:], tiles.dtype)
            for s in range(tiles.shape[0]):
                ty, tx = divmod(s, sp.tiles_x)
                out[ty * th:(ty + 1) * th, tx * tw:(tx + 1) * tw] = tiles[s]
            return out.reshape(gh * gw, *tiles.shape[3:])

        per = lambda x: glob(x.reshape(x.shape[0], th, tw, n))
        # the ring holds steps t-1-d .. t-2 (slot = step % d); the spikes
        # of step t-1 wait in ``pending`` for the next exchange
        frame0 = t - 1 - d
        interior = host.hist_ext[:, :, r:r + th, r:r + tw, :]
        frames = [glob(interior[:, (frame0 + i) % d]) for i in range(d)]
        frames.append(glob(host.pending))
        return reference.Snapshot(
            t=t, v=per(host.lif.v), c=per(host.lif.c),
            refrac=per(host.lif.refrac), frames=np.stack(frames),
            frame0=frame0,
            spikes=reference.counter_value(host.spike_count),
            events=reference.counter_value(host.event_count))

    def memory(self) -> list:
        return [_memory_analysis(self.exe)]


def _memory_analysis(exe) -> dict:
    m = exe.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    return out


def make(cell, cfg, devices):
    if cell.mesh:
        return Mesh(cfg, cell.config["impl"], cell.steps_per_call, devices,
                    cell.mesh)
    return OneChip(cfg, cell.config["impl"], cell.steps_per_call, devices[0])
