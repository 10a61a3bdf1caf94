"""The least work a simulated step needs, whatever implements it.

Roofline shares divide the least time of this work by a measured time, so
the counts are of what the semantics need and never of what the current
implementation moves: an event-driven delivery then reads a higher share,
never one above 100 %. The counts come from the program's exact spike and
event counters over the traced window and from the configuration:

* each recurrent synaptic event (a spike reaching one of its synapses)
  reads that synapse's weight, at the configuration's ``weight_dtype``,
  and an index of the fewest whole bytes that address a source among the
  column's offsets;
* each neuron's state is read and written once: the membrane potential
  and the adaptation variable at ``dtype``, a one-byte refractory counter,
  and one bit of spike; under STDP also both traces;
* under STDP each plastic (excitatory) synapse that a spike of its source
  or of its target touched is read and written once;
* one operation (an add) per synaptic event, drive arrivals included, ten
  per neuron update, and four per plastic synapse touched.

The program counts the drive's arrivals into the event counter; the
recurrent events are that counter less the drive's expected arrivals
(``N * c_ext * nu_ext * dt``), which the realized Poisson draws match to
about 0.01 % of the recurrent events at the paper's sizes.

``StepWork.kernels`` is the share that the Pallas kernels do: delivery of
every recurrent event, local (the fused column kernel) and remote (the ELL
delivery kernel), the neuron update and the dense local STDP rule. It
counts events (spikes times their synapses), not the synapses a kernel
happens to read. The remote STDP rule and, on a mesh, the rebuild of the
params run in XLA outside the kernels and are left out of it.
"""
from __future__ import annotations

import json
import math
import os
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))

NEURON_UPDATE_FLOPS = 10
STDP_FLOPS = 4


class Work(NamedTuple):
    flops: float
    bytes: float


class StepWork(NamedTuple):
    """Least work of one step on average over a window."""
    step: Work
    kernels: Work


_ITEMSIZE = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2}


def _itemsize(dtype: str) -> int:
    return _ITEMSIZE[dtype]


def index_bytes(neurons: int, n_offsets: int) -> int:
    """Fewest whole bytes that address one source neuron in any of the
    column's ``n_offsets`` source columns."""
    return max(1, math.ceil(math.log2(neurons * n_offsets) / 8))


def step_work(net: dict, k_total: int, n_offsets: int, steps: int,
              d_events: int, d_spikes: int) -> StepWork:
    """Least work per step from the counters' increments over ``steps``."""
    n = net["neurons_per_column"]
    neurons = net["grid_h"] * net["grid_w"] * n
    dt_s = net["neuron"]["dt_ms"] * 1e-3
    ext = neurons * net["c_ext"] * net["nu_ext_hz"] * dt_s * steps
    recurrent = max(0.0, d_events - ext)
    local = max(0.0, recurrent - d_spikes * k_total)
    wb = _itemsize(net["weight_dtype"])
    syn_b = wb + index_bytes(n, n_offsets)
    sb = _itemsize(net["dtype"])
    state_b = 2 * (2 * sb + 1) + 1 / 8
    if net["stdp"]:
        state_b += 2 * 2 * sb
    local_fanin = round(net["conn"]["p_local"] * (n - 1))
    exc = net["conn"]["exc_fraction"]
    flops_neuron = NEURON_UPDATE_FLOPS * neurons * steps
    step = Work(flops=d_events + flops_neuron,
                bytes=recurrent * syn_b + neurons * steps * state_b)
    # the kernels add each recurrent event and, once a neuron, the drive's
    # current, which XLA computes from the arrivals
    kern = Work(flops=recurrent + flops_neuron + neurons * steps,
                bytes=recurrent * syn_b + neurons * steps * state_b)
    if net["stdp"]:
        # synapses out of the spiking neurons (counted by the events) and
        # into them (their mean fan-in), excitatory sources only; the
        # kernels' share is the local rule's
        touched = exc * (recurrent + d_spikes * (local_fanin + k_total))
        touched_local = exc * (local + d_spikes * local_fanin)
        step = Work(step.flops + STDP_FLOPS * touched,
                    step.bytes + 2 * wb * touched)
        kern = Work(kern.flops + STDP_FLOPS * touched_local,
                    kern.bytes + 2 * wb * touched_local)
    return StepWork(step=Work(step.flops / steps, step.bytes / steps),
                    kernels=Work(kern.flops / steps, kern.bytes / steps))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(table)})")
    return table[device_kind]


def least_time(work: Work, peak: dict) -> tuple[float, str]:
    """Seconds the chip needs at least for ``work``, and which bound."""
    t_flops = work.flops / peak["flops_per_s"]
    t_bytes = work.bytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops,
                                                           "compute")
