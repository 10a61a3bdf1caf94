"""Scan-based simulation loop + summary metrics (single-shard).

The distributed loop lives in :mod:`repro.core.exchange`; it reuses the
same neuron/delivery code and only swaps the neighbour-table construction
for a halo exchange.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import DPSNNConfig
from repro.core import counters
from repro.core import network as net
from repro.core import plasticity as plast
from repro.core.connectivity import build_stencil, neuron_types
from repro.core.network import NetworkParams, NetworkState


class SimResult(NamedTuple):
    state: NetworkState
    rate_hz: jax.Array        # mean firing rate over the run
    events: jax.Array        # total synaptic events (paper metric)
    spikes: jax.Array         # total spikes
    rate_trace: jax.Array     # (T,) per-step population rate (Hz)
    params: Optional[NetworkParams] = None  # final plastic params (STDP only)


def build(cfg: DPSNNConfig, *, seed=None):
    """Generate params + fresh state for the full grid on one shard.

    ``seed`` overrides ``cfg.seed`` for the *state* draw only (membrane
    voltages); connectivity always comes from ``cfg.seed`` — tenants of
    the batched service share one network and differ in state/drive
    (DESIGN.md §Service)."""
    col_ids = jnp.arange(cfg.n_columns, dtype=jnp.int32)
    params = net.build_params(cfg, col_ids)
    state = net.init_state(cfg, col_ids, seed=seed)
    return params, state


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "impl"))
def run(cfg: DPSNNConfig, params: NetworkParams, state: NetworkState,
        n_steps: int, impl: str = "ref", seed=None,
        nu_scale=None) -> SimResult:
    """Simulate ``n_steps`` of ``cfg.neuron.dt_ms`` each.

    With ``cfg.stdp`` the synaptic weights are dynamical state: params
    join the scan carry, every step applies the pair-based STDP update
    (local outer products + remote ELL gather through the previous step's
    pre-trace table — the same one-step-lag semantics the distributed
    halo exchange delivers, DESIGN.md §Plasticity), and the final plastic
    params are returned in ``SimResult.params``. Static params stay out
    of the carry: the loop reads the caller's buffers, the program
    writes no copy of them, and ``SimResult.params`` is None.

    ``seed``/``nu_scale`` (traced, optional) select a per-tenant Poisson
    drive stream / stimulus intensity — the single-tenant reference for
    one slot of the batched service (tests/test_batched_service.py).
    """
    stencil = build_stencil(cfg)
    grid_hw = (cfg.grid_h, cfg.grid_w)
    col_ids = jnp.arange(cfg.n_columns, dtype=jnp.int32)
    is_inh = neuron_types(cfg)

    def body(carry, _):
        p0, s0 = carry if cfg.stdp else (params, carry)
        s1 = net.step_single(cfg, p0, s0, stencil=stencil, grid_hw=grid_hw,
                             col_ids=col_ids, impl=impl, seed=seed,
                             nu_scale=nu_scale)
        if cfg.stdp:
            spikes = jnp.take(s1.hist, s0.t % s0.hist.shape[0], axis=0)
            table = plast.pre_trace_table(s0.stdp.x_pre, stencil, grid_hw)
            # impl='pallas_fused': the megakernel already advanced the
            # traces inside the step (s1.stdp); hand them to stdp_update
            # instead of recomputing the decay+bump (bitwise-identical)
            fused = impl == "pallas_fused"
            p1, traces = plast.stdp_update(
                cfg, cfg.stdp_cfg, p0, s0.stdp, spikes, is_inh,
                pre_trace_table=table, rem_flat=p0.rem_flat, impl=impl,
                new_traces=s1.stdp if fused else None,
            )
            s1 = s1._replace(stdp=traces)
        step_spikes = jnp.take(s1.hist, s0.t % s0.hist.shape[0], axis=0)
        step_rate = step_spikes.sum() / (
            s0.hist.shape[1] * s0.hist.shape[2]
        ) / (cfg.neuron.dt_ms * 1e-3)
        return ((p1, s1) if cfg.stdp else s1), step_rate

    carry, rate_trace = jax.lax.scan(
        body, (params, state) if cfg.stdp else state, None, length=n_steps)
    final_params, final = carry if cfg.stdp else (None, carry)
    sim_seconds = n_steps * cfg.neuron.dt_ms * 1e-3
    n_neurons = state.hist.shape[1] * state.hist.shape[2]
    spikes = counters.value(final.spike_count)
    return SimResult(
        state=final,
        rate_hz=spikes / (n_neurons * sim_seconds),
        events=counters.value(final.event_count),
        spikes=spikes,
        rate_trace=rate_trace,
        params=final_params,
    )


def events_per_simulated_second(cfg: DPSNNConfig, rate_hz: float) -> float:
    """Analytic synaptic-event throughput (paper's normalisation):
    recurrent events = rate * recurrent synapses; external events =
    nu_ext * C_ext * neurons."""
    rec = rate_hz * (cfg.local_fanin + cfg.remote_fanin) * cfg.n_neurons
    ext = cfg.nu_ext_hz * cfg.c_ext * cfg.n_neurons
    return rec + ext
