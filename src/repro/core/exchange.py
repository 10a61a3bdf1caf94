"""Distributed DPSNN step: shard_map + ppermute halo exchange.

This is the JAX-native port of the paper's MPI spike exchange:

* columns tiled 2-D over the mesh (partition.py),
* per step, each shard exchanges only the **newly emitted spike frame's
  halo strips** (2-phase exchange — horizontal then vertical on the
  horizontally-extended strips — so corner data arrives without diagonal
  sends). A stencil of radius R runs ceil(R/tile) **chained ppermute
  rings** per direction (DESIGN.md §2 ring-count math): 4 ppermutes/step
  in the classic one-ring regime, 2*(rings_y+rings_x) when long-range
  (exponential-family) halos span multiple shards,
* axonal delays are served from a **halo-extended history ring buffer**,
  so all delayed reads are shard-local,
* halo payloads cross the wire in one of two formats selected by
  ``ConnectivityConfig.exchange_mode`` (DESIGN.md §AER): dense
  **bit-packed** frames (32 neurons/uint32 — a 32x collective-bytes
  reduction over f32, activity-independent) or **AER sparse event
  lists** ``(count:int32, addresses:int32[cap])`` — the source paper's
  event-driven exchange, whose payload scales with the firing-rate bound
  (beats bit-packing below the crossover rate ``1/(32*factor*dt)``).
  Both modes are bitwise-equal while no send saturates its capacity;
  saturation is surfaced per step as ``DistResult.aer_saturated``,
* the exchange of step t-1's spikes is issued *before* the heavy delivery
  matmul of step t and consumed only after it, so XLA's async
  collective-permute overlaps with the MXU work (requires every remote
  delay >= 2 steps, which distance-proportional delays guarantee; checked
  at trace time). The paper's MPI exchange is blocking — this overlap is
  one of our beyond-paper optimizations (EXPERIMENTS.md §Perf). With
  ``ExchangeConfig.pipelined`` the window widens from sub-step to a FULL
  step: the exchanged frame is double-buffered across the scan boundary
  (``DistState.ext_pending``) and written into the ring one step later —
  legal because every remote read sits at delay >= 2, bitwise-equal by
  construction (DESIGN.md §Fusion),
* under STDP (DPSNN's first-class plasticity, DESIGN.md §Plasticity) the
  pre-synaptic trace halo strips ride the same 2-phase exchange and the
  same overlap window; live weights join the per-shard dynamical state
  (:class:`PlasticState`) so they checkpoint/restore like the neurons,
* on a **hierarchical mesh** (axes ('ndata','data','nmodel','model'),
  runtime/multiprocess.py `--ranks-per-node`) the exchange runs
  two-level (DESIGN.md §Hierarchy): the ranks of a node group first
  all-gather their tiles into one coalesced node frame (intra-node
  lanes), node-level rings then cross as a **single ppermute message
  per neighbour-node pair** between lane-(0,0) corner ranks, an
  intra-node psum broadcasts each received strip to the members, and
  every rank slices its own halo window back out — bitwise-equal to
  the flat exchange (:func:`exchange_halo_hier`),
* ``ExchangeConfig.exchange_mode == "auto"`` resolves the wire format
  **per ring** from the exact byte accounting in runtime/compression.py
  (``ring_mode_table``) — each (phase, ring) send independently ships
  whichever of dense/AER is fewer bytes at the configured rate bound
  (:func:`exchange_halo_modes`).

Invariants the rest of the comms layer relies on:

* **Ring ordering** is fixed: all horizontal (east, then west) rings
  near-to-far, then all vertical (south, then north) rings over the
  horizontally-extended strips — corners ride the vertical phase, and
  runtime/compression.py enumerates sends in exactly this order, so
  per-ring mode tables index real sends.
* **Delay-slot legality**: every remote (non-zero-offset) synapse has
  delay >= 2 steps, which is what lets the exchange overlap compute;
  pipelining additionally requires ``stencil.max_delay >= 1``. Both are
  checked at trace time.
* **Wire equivalence**: dense bit-packing is exact; AER decode is
  bitwise-equal to dense while no send saturates its capacity
  (saturation is flagged, never silent); the hierarchical aggregation
  copies values exactly (gather/permute/psum-of-zeros), so every
  format/topology combination yields bitwise-identical trajectories.
* Under per-ring ``"auto"`` and under the hierarchical exchange, the
  STDP trace side payload always crosses as a dense f32 strip (no
  event-driven trace reconstruction on mixed-mode rings), which keeps
  plastic runs bitwise-equal across all of the above.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import DPSNNConfig
from repro.core import connectivity as conn
from repro.core import counters
from repro.core import network as net
from repro.core import plasticity as plast
from repro.core.connectivity import StencilSpec, build_stencil
from repro.core.network import NetworkParams
from repro.core.neuron import LIFState
from repro.core.partition import TileSpec, tile_column_ids
from repro.core.plasticity import STDPState
from repro.runtime import integrity
from repro.runtime.integrity import GuardState

# ---------------------------------------------------------------------------
# Spike bit-packing (dense_packed halo payloads)
# ---------------------------------------------------------------------------

def packed_width(n: int) -> int:
    return (n + 31) // 32


def pack_spikes(x: jax.Array) -> jax.Array:
    """(..., N) 0/1 floats -> (..., ceil(N/32)) uint32 bitmaps."""
    n = x.shape[-1]
    pad = packed_width(n) * 32 - n
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    bits = (x > 0).astype(jnp.uint32).reshape(*x.shape[:-1], -1, 32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return (bits * weights).sum(axis=-1, dtype=jnp.uint32)


def unpack_spikes(p: jax.Array, n: int, dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`pack_spikes` (truncates padding)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = jnp.bitwise_and(
        jnp.right_shift(p[..., None], shifts), jnp.uint32(1)
    )
    flat = bits.reshape(*p.shape[:-1], p.shape[-1] * 32)
    return flat[..., :n].astype(dtype)


# ---------------------------------------------------------------------------
# AER sparse event lists (aer_sparse halo payloads, DESIGN.md §AER)
# ---------------------------------------------------------------------------
#
# The source paper's exchange is *event-driven*: ranks ship only the
# addresses of axons that actually spiked, so payload scales with the
# ~7.5 Hz cortical firing rate instead of the neuron count
# (arXiv:1511.09325 Sec. 3; payload measurements in arXiv:1310.8478 and
# the EURETILE D7.3 report, arXiv:1408.4587). JAX collectives need
# static shapes, so each send carries a fixed-capacity event list
# ``int32[1 + cap]`` = ``(count, addresses[cap])``; unused address slots
# hold the sentinel ``m`` (= units in the strip) and are dropped by the
# scatter decode. ``cap`` is sized from a configurable firing-rate bound
# — ``ceil(capacity_factor * m * rate_bound_hz * dt)`` — and a send whose
# true count exceeds it truncates the event list AND raises the step's
# saturation flag (``DistResult.aer_saturated``); dropping spikes
# silently is forbidden. Under STDP a gathered ``f32[cap]`` pre-trace
# side payload reuses the same addresses (see ``exchange_halo_aer``).


def aer_capacity(n_units: int, rate_bound_hz: float,
                 capacity_factor: float, dt_ms: float) -> int:
    """Static event-list capacity for a send of ``n_units`` binary units:
    ``max(1, ceil(capacity_factor * expected events per step))`` where
    the expectation is taken at the configured firing-rate *bound*."""
    expected = n_units * rate_bound_hz * dt_ms * 1e-3
    return max(1, int(math.ceil(capacity_factor * expected)))


def aer_encode(frame: jax.Array, cap: int):
    """(...) 0/1 frame -> (``int32[1 + cap]`` event list, overflowed bool).

    Layout: ``[count, addr_0 .. addr_{cap-1}]`` with flattened-frame
    addresses in ascending order; slots past ``count`` hold the sentinel
    ``frame.size``. ``count`` is the TRUE event count (it may exceed
    ``cap`` — that is the overflow signal the decoder and the saturation
    flag both key on; the address list itself is truncated to ``cap``).
    """
    flat = frame.reshape(-1)
    m = flat.shape[0]
    count = (flat > 0).sum().astype(jnp.int32)
    addr = jnp.flatnonzero(flat > 0, size=cap, fill_value=m).astype(jnp.int32)
    return jnp.concatenate([count[None], addr]), count > cap


def aer_decode(events: jax.Array, shape: tuple, dtype=jnp.float32
               ) -> jax.Array:
    """Inverse of :func:`aer_encode`: scatter ones at the listed
    addresses. Address slots at/after ``count`` are masked to the
    out-of-range sentinel and dropped — a zero-filled event list (what a
    ppermute delivers at the open sheet boundary) decodes to an all-zero
    frame, and an overflowed list decodes its ``cap`` surviving events.
    """
    cap = events.shape[0] - 1
    m = 1
    for s in shape:
        m *= s
    count, addr = events[0], events[1:]
    addr = jnp.where(jnp.arange(cap, dtype=jnp.int32) < count, addr, m)
    flat = jnp.zeros((m,), dtype).at[addr].set(
        jnp.asarray(1, dtype), mode="drop")
    return flat.reshape(shape)


def aer_gather_values(values: jax.Array, events: jax.Array) -> jax.Array:
    """Gather ``f32[cap]`` side-payload values at an event list's
    addresses (sentinel slots read a zero pad slot)."""
    flat = jnp.concatenate(
        [values.reshape(-1), jnp.zeros((1,), values.dtype)])
    return flat[events[1:]]


def aer_scatter_values(events: jax.Array, values: jax.Array, shape: tuple
                       ) -> jax.Array:
    """Scatter a gathered side payload back to a dense (zeros elsewhere)
    frame, masking slots at/after ``count`` like :func:`aer_decode`."""
    cap = events.shape[0] - 1
    m = 1
    for s in shape:
        m *= s
    count, addr = events[0], events[1:]
    addr = jnp.where(jnp.arange(cap, dtype=jnp.int32) < count, addr, m)
    return jnp.zeros((m,), values.dtype).at[addr].set(
        values, mode="drop").reshape(shape)


# ---------------------------------------------------------------------------
# Halo exchange
# ---------------------------------------------------------------------------

def assert_axis_sizes(spec: TileSpec, row_axes, col_axis) -> None:
    """Trace-time guard: the mesh axes this step runs over must match the
    TileSpec's shard grid. Runs inside shard_map (sizes are static), so a
    mismatched mesh — e.g. a multi-process launch whose global device
    count disagrees with the tile decomposition — fails at trace time
    with the two geometries named, instead of silently exchanging halos
    with the wrong neighbours."""
    rows, cols = jax.lax.axis_size(row_axes), jax.lax.axis_size(col_axis)
    if (rows, cols) != (spec.tiles_y, spec.tiles_x):
        raise ValueError(
            f"mesh axes {rows}x{cols} (row_axes={row_axes!r}, "
            f"col_axis={col_axis!r}) do not match the tile grid "
            f"{spec.tiles_y}x{spec.tiles_x} of {spec} — the halo exchange "
            f"would pair wrong neighbours. Rebuild the spec from the mesh "
            f"(partition.make_tile_spec) or fix the mesh shape."
        )


def _shift(x: jax.Array, axis_name, direction: int) -> jax.Array:
    """ppermute by +-1 along (possibly tuple) mesh axis. Shards at the open
    boundary receive zeros (the cortical sheet edge, paper Sec. 2)."""
    size = jax.lax.axis_size(axis_name)
    if size == 1:
        return jnp.zeros_like(x)
    if direction > 0:      # receive from my +1 neighbour (they send to -1)
        perm = [(j, j - 1) for j in range(1, size)]
    else:                  # receive from my -1 neighbour
        perm = [(j, j + 1) for j in range(size - 1)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_ring_widths(radius: int, tile_dim: int) -> list:
    """Per-ring strip widths for a radius-``radius`` halo over tiles of
    ``tile_dim`` columns/rows: ring k (1-based) contributes
    ``min(tile_dim, radius - (k-1)*tile_dim)`` — ``ceil(radius/tile_dim)``
    rings in total, summing to exactly ``radius``."""
    widths = []
    left = radius
    while left > 0:
        w = min(tile_dim, left)
        widths.append(w)
        left -= w
    return widths


def _collect_rings(f, axis: int, axis_name, direction: int,
                   radius: int, send_fn):
    """Gather the radius-deep halo beyond one face of ``f`` along ``axis``
    by **chained ppermute rings**: round k forwards the strip received in
    round k-1, so ring-k data crosses k hops in k rounds with only
    nearest-neighbour sends (no long-distance permutes, no diagonal
    sends). Strips narrow as the remaining radius shrinks, so total bytes
    equal one contiguous radius-wide strip.

    ``f`` may be a pytree of same-leading-shape arrays (e.g. the AER
    path's ``(spike_frame, trace_frame)`` pair, so both payloads slice
    and forward in lockstep and the trace gather can reuse the spike
    addresses); ``send_fn`` receives and returns the whole pytree.

    ``direction=+1`` collects toward increasing coordinate (east/south
    face: each ring contributes its *leading* rows/cols);
    ``direction=-1`` the mirror. Shards at the open boundary receive
    zeros from ppermute and forward them on — the cortical sheet edge
    propagates through every ring for free.
    """
    tm = jax.tree_util.tree_map
    dim = jax.tree_util.tree_leaves(f)[0].shape[axis]
    parts = []
    cur = f
    for w in halo_ring_widths(radius, dim):
        if direction > 0:
            strip = tm(lambda x: jax.lax.slice_in_dim(x, 0, w, axis=axis),
                       cur)
        else:
            strip = tm(
                lambda x: jax.lax.slice_in_dim(
                    x, x.shape[axis] - w, x.shape[axis], axis=axis),
                cur)
        cur = send_fn(strip, axis_name, direction)
        parts.append(cur)
    if direction < 0:
        parts = parts[::-1]
    return tm(lambda *xs: jnp.concatenate(xs, axis=axis), *parts)


def _extend_tree(payload, send_fn, r: int, row_axes, col_axis):
    """Two-phase (horizontal rings, then vertical rings of the
    horizontally-extended strips) halo extension of a pytree payload:
    each (th, tw, N) leaf becomes (th+2r, tw+2r, N). Corners ride the
    vertical phase — no diagonal sends at any radius."""
    tm = jax.tree_util.tree_map
    if r == 0:
        return payload
    east = _collect_rings(payload, 1, col_axis, +1, r, send_fn)
    west = _collect_rings(payload, 1, col_axis, -1, r, send_fn)
    wide = tm(lambda a, b, c: jnp.concatenate([a, b, c], axis=1),
              west, payload, east)
    south = _collect_rings(wide, 0, row_axes, +1, r, send_fn)
    north = _collect_rings(wide, 0, row_axes, -1, r, send_fn)
    return tm(lambda a, b, c: jnp.concatenate([a, b, c], axis=0),
              north, wide, south)


def exchange_halo(frame: jax.Array, spec: TileSpec, row_axes, col_axis,
                  compress: bool = True, trace: jax.Array | None = None,
                  shift_fn=None):
    """(th, tw, N) interior spike frame -> (th+2r, tw+2r, N) extended frame.

    Two phases: horizontal rings first, then vertical rings of the
    horizontally-extended array (corners ride along — still no diagonal
    sends at any radius). Each direction runs ``ceil(r / tile_dim)``
    chained ppermute rounds (:func:`_collect_rings`); with ``r`` inside
    one tile this is the classic single round, 4 ppermutes/step total.
    With ``compress`` every strip crosses the wire as uint32 bitmaps.

    With ``trace`` (a second (th, tw, N) frame — the STDP pre-synaptic
    traces, DESIGN.md §Plasticity), its halo strips ride the same ring
    schedule as f32 payloads (traces are real-valued, no bit-packing) and
    the function returns ``(ext_frame, ext_trace)``. Both exchanges are
    issued together, so they share the comm/compute overlap window of the
    distributed step.

    ``shift_fn`` (default the raw ring :func:`_shift`) is the collective
    every wire message rides — the integrity guard substitutes its
    checksum-framing wrapper here (DESIGN.md §Integrity).
    """
    r = spec.radius
    n = frame.shape[-1]
    dtype = frame.dtype
    shift = _shift if shift_fn is None else shift_fn

    def send(payload, axis_name, direction):
        if compress:
            return unpack_spikes(
                shift(pack_spikes(payload), axis_name, direction), n, dtype
            )
        return shift(payload, axis_name, direction)

    ext = _extend_tree(frame, send, r, row_axes, col_axis)
    if trace is None:
        return ext
    return ext, _extend_tree(trace, shift, r, row_axes, col_axis)


def exchange_halo_aer(frame: jax.Array, spec: TileSpec, row_axes, col_axis,
                      *, rate_bound_hz: float, capacity_factor: float,
                      dt_ms: float, trace: jax.Array | None = None,
                      shift_fn=None):
    """AER (address-event representation) spike-halo exchange: the
    source paper's event-driven wire format (DESIGN.md §AER).

    Same two-phase chained-ring schedule as :func:`exchange_halo`, but
    every strip crosses the wire as a fixed-capacity ``int32[1 + cap]``
    event list ``(count, addresses[cap])`` (:func:`aer_encode`) instead
    of bit-packed words, so payload bytes scale with the configured
    firing-rate bound rather than the strip's neuron count. The decode
    scatters ones back into a dense strip, which is **bitwise-equal** to
    the dense-mode strip whenever ``count <= cap`` — everything
    downstream (ring buffer, delayed delivery, STDP, overlap window) is
    untouched. Forwarded rings re-encode the decoded strip, so multi-ring
    halos cost k hops of *event-sized* messages.

    With ``trace`` (the STDP pre-synaptic trace frame), a gathered
    ``f32[cap]`` side payload rides each send **reusing the same
    addresses** — the receiver reconstructs the dense trace halo from
    these sparse values plus local exponential decay (see ``dist_step``);
    only spiking addresses need fresh values because the trace recurrence
    ``x' = x * exp(-dt/tau) + spike`` is locally computable everywhere
    else.

    Returns ``(ext_frame, ext_sparse_trace_or_None, saturated)`` where
    ``saturated`` is a scalar bool — True iff ANY send this step had
    more events than its capacity (events beyond ``cap`` are truncated
    from the wire, never dropped silently: the flag is surfaced per step
    in ``DistResult.aer_saturated``).
    """
    r = spec.radius
    dtype = frame.dtype
    with_trace = trace is not None
    sat = [jnp.zeros((), jnp.bool_)]
    shift = _shift if shift_fn is None else shift_fn

    def send(payload, axis_name, direction):
        spike = payload[0] if with_trace else payload
        shape = spike.shape
        m = spike.size
        cap = aer_capacity(m, rate_bound_hz, capacity_factor, dt_ms)
        events, overflow = aer_encode(spike, cap)
        sat[0] = sat[0] | overflow
        events_r = shift(events, axis_name, direction)
        out = aer_decode(events_r, shape, dtype)
        if not with_trace:
            return out
        vals = aer_gather_values(payload[1], events)
        vals_r = shift(vals, axis_name, direction)
        return out, aer_scatter_values(events_r, vals_r, shape)

    payload = (frame, trace) if with_trace else frame
    ext = _extend_tree(payload, send, r, row_axes, col_axis)
    if with_trace:
        return ext[0], ext[1], sat[0]
    return ext, None, sat[0]


# ---------------------------------------------------------------------------
# Per-ring wire-format selection + the hierarchical two-level exchange
# (DESIGN.md §Hierarchy)
# ---------------------------------------------------------------------------

# axis names of the hierarchical mesh built by
# runtime.multiprocess.make_process_mesh(ranks_per_node=g): the node
# grid ('ndata' x 'nmodel') majors over the intra-node lane grid
# ('data' x 'model'), so flattening ('ndata','data') row-major is the
# global tile row — the flat exchange runs unchanged over the tuple
# axes, which is what makes flat-vs-hierarchical bitwise comparison on
# the SAME mesh possible (tests/test_hier_exchange.py).
HIER_AXES = ("ndata", "data", "nmodel", "model")
HIER_ROW_AXES = ("ndata", "data")
HIER_COL_AXIS = ("nmodel", "model")
HIER_LANE_AXES = ("data", "model")
# sentinel axis names routed to the node-level shift (never a real mesh
# axis): _extend_tree only forwards axis_name to its send_fn, so the
# node exchange reuses the exact flat ring schedule at node granularity
_NODE_H = "__node_h__"
_NODE_V = "__node_v__"


def mesh_layout(mesh: Mesh):
    """Resolve a mesh's axis convention: returns ``(row_axes, col_axis,
    node, row_shards, col_shards)`` where ``node`` is the
    :class:`~repro.core.partition.NodeSpec` of a hierarchical
    ('ndata','data','nmodel','model') mesh, or None for the flat
    ('data','model') / ('pod','data','model') conventions."""
    names = mesh.axis_names
    if "nmodel" in names:
        node = NodeSpec(nodes_y=mesh.shape["ndata"],
                        nodes_x=mesh.shape["nmodel"],
                        group_h=mesh.shape["data"],
                        group_w=mesh.shape["model"])
        return (HIER_ROW_AXES, HIER_COL_AXIS, node,
                node.nodes_y * node.group_h, node.nodes_x * node.group_w)
    multi_pod = "pod" in names
    row_axes = ("pod", "data") if multi_pod else "data"
    return (row_axes, "model", None,
            mesh.shape["data"] * mesh.shape.get("pod", 1),
            mesh.shape["model"])


def resolve_ring_modes(cfg: DPSNNConfig, spec: TileSpec, node=None, *,
                       compress: bool = True):
    """None under the uniform policy (``ExchangeConfig.exchange_mode ==
    "inherit"``: every ring uses ``conn.exchange_mode``), or the
    ``{(phase, ring): mode}`` per-ring selection dict under ``"auto"`` —
    the argmin of the exact byte accounting at the configured rate bound
    (runtime.compression.ring_mode_table), resolved at trace time."""
    policy = getattr(cfg.exchange, "exchange_mode", "inherit")
    if policy not in ("inherit", "auto"):
        raise ValueError(
            f"unknown ExchangeConfig.exchange_mode {policy!r} "
            f"(expected 'inherit' or 'auto')")
    if policy != "auto":
        return None
    from repro.runtime.compression import ring_mode_table

    return {(e["phase"], e["ring"]): e["mode"]
            for e in ring_mode_table(cfg, spec, node, compress=compress)}


def _make_mode_send(modes: dict, shift_fn, *, n: int, dtype,
                    rate_bound_hz: float, capacity_factor: float,
                    dt_ms: float, compress: bool, with_trace: bool,
                    phase_of):
    """Build a ``send_fn`` for :func:`_collect_rings` that picks the wire
    format per (phase, ring) from ``modes`` and ships the STDP trace
    side payload as a dense f32 strip on every ring regardless of the
    spike format (module docstring invariants). Returns
    ``(send_fn, sat)`` with ``sat`` the closure's saturation
    accumulator.
    """
    sat = [jnp.zeros((), jnp.bool_)]
    ring_counter: dict = {}

    def send(payload, axis_name, direction):
        spike = payload[0] if with_trace else payload
        key = (phase_of(axis_name), direction)
        k = ring_counter.get(key, 0) + 1
        ring_counter[key] = k
        mode = modes[(key[0], k)]
        if mode == "aer_sparse":
            cap = aer_capacity(spike.size, rate_bound_hz, capacity_factor,
                               dt_ms)
            events, overflow = aer_encode(spike, cap)
            sat[0] = sat[0] | overflow
            out = aer_decode(shift_fn(events, axis_name, direction),
                             spike.shape, dtype)
        elif compress:
            out = unpack_spikes(
                shift_fn(pack_spikes(spike), axis_name, direction), n,
                dtype)
        else:
            out = shift_fn(spike, axis_name, direction)
        if with_trace:
            return out, shift_fn(payload[1], axis_name, direction)
        return out

    return send, sat


def exchange_halo_modes(frame: jax.Array, spec: TileSpec, row_axes,
                        col_axis, *, modes: dict, rate_bound_hz: float,
                        capacity_factor: float, dt_ms: float,
                        compress: bool = True,
                        trace: jax.Array | None = None,
                        shift_fn=None):
    """Flat halo exchange with a per-ring wire format
    (``ExchangeConfig.exchange_mode == "auto"``): same two-phase
    chained-ring schedule as :func:`exchange_halo`, but every (phase,
    ring) send uses whichever of dense-packed / AER the byte accounting
    resolved cheaper (``modes`` from :func:`resolve_ring_modes`).
    Bitwise-equal to both uniform modes while no AER ring saturates;
    the STDP ``trace`` rides dense f32 on every ring, so mixed spike
    formats never touch plastic values. Returns
    ``(ext_frame, ext_trace_or_None, saturated)``.
    """
    phase_of = lambda a: "h" if a == col_axis else "v"  # noqa: E731
    send, sat = _make_mode_send(
        modes, _shift if shift_fn is None else shift_fn,
        n=frame.shape[-1], dtype=frame.dtype,
        rate_bound_hz=rate_bound_hz, capacity_factor=capacity_factor,
        dt_ms=dt_ms, compress=compress, with_trace=trace is not None,
        phase_of=phase_of)
    payload = (frame, trace) if trace is not None else frame
    ext = _extend_tree(payload, send, spec.radius, row_axes, col_axis)
    if trace is not None:
        return ext[0], ext[1], sat[0]
    return ext, None, sat[0]


def exchange_halo_hier(frame: jax.Array, spec: TileSpec, node, *,
                       modes: dict | None = None,
                       mode: str = "dense_packed",
                       rate_bound_hz: float = 0.0,
                       capacity_factor: float = 2.0, dt_ms: float = 1.0,
                       compress: bool = True,
                       trace: jax.Array | None = None,
                       wrap_shift=None):
    """Hierarchical two-level halo exchange (DESIGN.md §Hierarchy).

    Runs on the 4-axis mesh (:data:`HIER_AXES`). Three stages, all
    value-exact:

    1. **intra-node aggregate** — the node's ``group_h x group_w`` lane
       ranks all-gather their (bit-packed) tile frames into one
       coalesced ``(group_h*tile_h, group_w*tile_w, N)`` node frame,
       replicated on every member;
    2. **inter-node rings** — the flat two-phase chained-ring schedule
       (:func:`_extend_tree`) runs at *node* granularity:
       ``ceil(r / node_dim)`` rings per direction instead of
       ``ceil(r / tile_dim)``, and each ring strip crosses as a
       **single ppermute message between the lane-(0,0) corner ranks**
       of the neighbouring nodes (one point-to-point per neighbour node
       per ring, not per member rank), in the per-ring wire format from
       ``modes`` (or uniformly ``mode``). An intra-node ``psum`` over
       the lane axes then broadcasts the received strip to the other
       members — exact, since they contribute zeros;
    3. **scatter-back** — each rank dynamic-slices its own
       ``(tile_h+2r, tile_w+2r, N)`` halo window out of the extended
       node frame at its lane coordinate.

    The extended node frame equals the global frame restricted to the
    node's radius-r window (same zeros at the open sheet boundary), so
    every rank's window is bitwise what the flat exchange delivers.
    The STDP ``trace`` frame rides the same stages as raw f32. Returns
    ``(ext_frame, ext_trace_or_None, saturated)``.

    ``wrap_shift`` (the integrity guard's ``HaloGuard.wrap``) decorates
    the inter-node ``node_shift`` so each corner-to-corner message ships
    a checksum word; the lane-``psum`` that replicates the strip adds
    zeros to the framed uint32 message, which is lossless, so receive-
    side verification stays exact (DESIGN.md §Integrity).
    """
    r = spec.radius
    n = frame.shape[-1]
    dtype = frame.dtype
    gy, gx = node.group_h, node.group_w
    ny, nx = node.nodes_y, node.nodes_x
    sizes = tuple(jax.lax.axis_size(a) for a in HIER_AXES)
    if sizes != (ny, gy, nx, gx):
        raise ValueError(
            f"hierarchical mesh axes {HIER_AXES} have sizes {sizes}, "
            f"which do not match NodeSpec {node} (want ({ny}, {gy}, "
            f"{nx}, {gx})) — rebuild the mesh with "
            f"runtime.multiprocess.make_process_mesh(ranks_per_node=...)")
    if modes is None:
        h_rings = len(halo_ring_widths(r, gx * spec.tile_w))
        v_rings = len(halo_ring_widths(r, gy * spec.tile_h))
        modes = {("h", k): mode for k in range(1, h_rings + 1)}
        modes.update({("v", k): mode for k in range(1, v_rings + 1)})

    def flat_rank(a, b, j, l):  # noqa: E741
        return ((a * gy + b) * nx + j) * gx + l

    def node_shift(x, axis_name, direction):
        # one message per neighbour-node pair: lane (0,0) of each node
        # sends to lane (0,0) of the neighbour; every other lane is not
        # a ppermute destination (receives zeros), and the psum over the
        # lane axes replicates the strip node-wide (zeros + x is exact)
        if axis_name == _NODE_H:
            if nx == 1:
                return jnp.zeros_like(x)
            if direction > 0:
                perm = [(flat_rank(a, 0, j, 0), flat_rank(a, 0, j - 1, 0))
                        for a in range(ny) for j in range(1, nx)]
            else:
                perm = [(flat_rank(a, 0, j, 0), flat_rank(a, 0, j + 1, 0))
                        for a in range(ny) for j in range(nx - 1)]
        else:
            if ny == 1:
                return jnp.zeros_like(x)
            if direction > 0:
                perm = [(flat_rank(a, 0, j, 0), flat_rank(a - 1, 0, j, 0))
                        for a in range(1, ny) for j in range(nx)]
            else:
                perm = [(flat_rank(a, 0, j, 0), flat_rank(a + 1, 0, j, 0))
                        for a in range(ny - 1) for j in range(nx)]
        recv = jax.lax.ppermute(x, HIER_AXES, perm)
        return jax.lax.psum(recv, HIER_LANE_AXES)

    def gather_node(x, pack):
        # (th, tw, ...) tile -> (gy*th, gx*tw, ...) node frame,
        # replicated over the node's lanes (bit-packed on the wire)
        y = pack_spikes(x) if pack else x
        g = jax.lax.all_gather(y, HIER_LANE_AXES, tiled=False)
        g = g.reshape(gy, gx, *y.shape)
        g = jnp.moveaxis(g, 1, 2).reshape(
            gy * y.shape[0], gx * y.shape[1], *y.shape[2:])
        return unpack_spikes(g, n, dtype) if pack else g

    with_trace = trace is not None
    payload = gather_node(frame, pack=compress)
    if with_trace:
        payload = (payload, gather_node(trace, pack=False))
    phase_of = lambda a: "h" if a == _NODE_H else "v"  # noqa: E731
    if wrap_shift is not None:
        node_shift = wrap_shift(node_shift)
    send, sat = _make_mode_send(
        modes, node_shift, n=n, dtype=dtype, rate_bound_hz=rate_bound_hz,
        capacity_factor=capacity_factor, dt_ms=dt_ms, compress=compress,
        with_trace=with_trace, phase_of=phase_of)
    ext = _extend_tree(payload, send, r, _NODE_V, _NODE_H)

    ly = jax.lax.axis_index("data")
    lx = jax.lax.axis_index("model")

    def window(x):
        return jax.lax.dynamic_slice(
            x, (ly * spec.tile_h, lx * spec.tile_w, 0),
            (spec.tile_h + 2 * r, spec.tile_w + 2 * r, x.shape[-1]))

    if with_trace:
        return window(ext[0]), window(ext[1]), sat[0]
    return window(ext), None, sat[0]


# ---------------------------------------------------------------------------
# Distributed state
# ---------------------------------------------------------------------------

class PlasticState(NamedTuple):
    """Per-shard dynamical synaptic state under STDP.

    The live weights move out of the (regenerable) params and into the
    scan carry: unlike the static run, a plastic run's weights cannot be
    regenerated from column ids, so they checkpoint/restore with the rest
    of the dynamical state (DESIGN.md §Plasticity).
    """
    w_local: jax.Array       # (C, N, N) live intra-column weights
    rem_w: jax.Array         # (C, N, K) live remote ELL weights
    traces: STDPState        # x_pre/x_post, (C, N) each
    # AER mode only: (th+2r, tw+2r, N) halo-extended pre-trace frame,
    # reconstructed event-driven on the receiver (sparse shipped values at
    # spike addresses + local exponential decay everywhere else) instead
    # of shipping dense f32 trace strips. Holds ext(x_pre(t-1)) after
    # step t — bitwise-equal to the dense-mode trace halo (DESIGN.md
    # §AER). None under dense_packed.
    trace_ext: Optional[jax.Array] = None


class DistState(NamedTuple):
    lif: LIFState            # leaves (C, N), C = tile columns
    hist_ext: jax.Array      # (D, th+2r, tw+2r, N) halo-extended ring buffer
    pending: jax.Array       # (th, tw, N) spikes of step t-1, pre-exchange
    t: jax.Array
    spike_count: jax.Array
    event_count: jax.Array
    plastic: Optional[PlasticState] = None  # present iff cfg.stdp
    # did ANY of this shard's aer_sparse sends overflow its static event
    # capacity THIS step (spikes truncated from the wire — flagged, never
    # silent). Scanned out per step into DistResult.aer_saturated.
    # Always a scalar bool (constant False under dense_packed); the None
    # default exists only so the class can be built before a backend is
    # initialised (multi-process workers import this module pre-init).
    aer_sat: Optional[jax.Array] = None
    # cross-step pipelined exchange (ExchangeConfig.pipelined, DESIGN.md
    # §Fusion): the double buffer — the already-exchanged halo extension
    # of spikes(t-2), carried un-consumed through step t-1 so the
    # collective had a FULL step of compute to hide behind, and written
    # into the history ring only at step t (every remote read sits at
    # delay >= 2, so the deferred slot is never read earlier). None when
    # pipelining is off.
    ext_pending: Optional[jax.Array] = None  # (th+2r, tw+2r, N)
    # inter-spike-interval statistics, accumulated in the scan carry so
    # they checkpoint/reshard with the rest of the state and survive a
    # supervisor restart (DESIGN.md §Elasticity): per-neuron time of the
    # last spike (-1 = never spiked) plus running sum / sum-of-squares /
    # count of ISIs in steps. Integer-valued float32 sums, so they are
    # exact and order-independent under the reshard's partial-sum merge.
    # Optional (None default) only for structural compatibility — every
    # runner populates them.
    last_spike_t: Optional[jax.Array] = None  # (C, N) int32
    isi_sum: Optional[jax.Array] = None       # f32 scalar, ISI in steps
    isi_sumsq: Optional[jax.Array] = None     # f32 scalar
    isi_count: Optional[jax.Array] = None     # f32 scalar
    # in-band integrity verdict (runtime/integrity.py, DESIGN.md
    # §Integrity): five scalar leaves accumulated inside the scan —
    # present iff cfg.guard.enabled, None otherwise so guard-off runs
    # keep the exact pre-guard state structure (checkpoints included).
    guard: Optional[GuardState] = None


def _shard_coords(spec: TileSpec, row_axes, col_axis):
    ty = jax.lax.axis_index(row_axes)
    tx = jax.lax.axis_index(col_axis)
    return ty, tx


def shard_col_ids(cfg: DPSNNConfig, spec: TileSpec, row_axes, col_axis):
    ty, tx = _shard_coords(spec, row_axes, col_axis)
    return tile_column_ids(cfg, spec, ty, tx)


def build_shard(cfg: DPSNNConfig, spec: TileSpec, row_axes, col_axis
                ) -> NetworkParams:
    """Per-shard synapse generation from mesh coordinates (deterministic
    per global column id — see partition.py docstring)."""
    return net.build_params(cfg, shard_col_ids(cfg, spec, row_axes, col_axis))


def init_shard(cfg: DPSNNConfig, spec: TileSpec, stencil: StencilSpec,
               row_axes, col_axis,
               params: Optional[NetworkParams] = None,
               seed: Optional[jax.Array] = None,
               col_ids: Optional[jax.Array] = None) -> DistState:
    """Deterministic per global column id — any mesh produces the same
    global trajectory (bitwise) as the single-shard simulator.

    Under ``cfg.stdp`` the initial plastic weights are seeded from
    ``params`` (pass the shard's freshly built params), so they start
    bitwise-equal to the single-shard generation for the same columns.

    ``seed`` overrides ``cfg.seed`` for the state draw (one tenant of the
    batched service); connectivity/params always derive from ``cfg.seed``.
    ``col_ids`` bypasses the mesh-coordinate lookup (for abstract
    evaluation outside shard_map — :func:`stacked_state_template`).
    """
    if col_ids is None:
        col_ids = shard_col_ids(cfg, spec, row_axes, col_axis)
    single = net.init_state(cfg, col_ids, stencil, seed=seed)
    n = cfg.neurons_per_column
    d = stencil.max_delay + 1
    r = spec.radius
    dtype = jnp.dtype(cfg.dtype)
    aer = cfg.conn.exchange_mode == "aer_sparse"
    plastic = None
    if cfg.stdp:
        if params is None:
            params = net.build_params(cfg, col_ids)
        plastic = PlasticState(
            w_local=params.w_local,
            rem_w=params.rem_w,
            traces=plast.init_stdp(spec.columns_per_tile, n, dtype),
            trace_ext=(jnp.zeros((spec.tile_h + 2 * r, spec.tile_w + 2 * r,
                                  n), dtype) if aer else None),
        )
    return DistState(
        lif=single.lif,
        hist_ext=jnp.zeros((d, spec.tile_h + 2 * r, spec.tile_w + 2 * r, n),
                           dtype),
        pending=jnp.zeros((spec.tile_h, spec.tile_w, n), dtype),
        t=jnp.int32(0),
        spike_count=counters.zero(),
        event_count=counters.zero(),
        plastic=plastic,
        aer_sat=jnp.zeros((), jnp.bool_),
        # zero in-flight frame == the empty pre-t=0 history, so the
        # pipelined schedule starts bitwise-equal to the unpipelined one
        ext_pending=(jnp.zeros((spec.tile_h + 2 * r, spec.tile_w + 2 * r,
                                n), dtype)
                     if cfg.exchange.pipelined else None),
        last_spike_t=jnp.full((spec.columns_per_tile, n), -1, jnp.int32),
        isi_sum=jnp.float32(0),
        isi_sumsq=jnp.float32(0),
        isi_count=jnp.float32(0),
        guard=integrity.init_guard() if cfg.guard.enabled else None,
    )


def dist_step(cfg: DPSNNConfig, params: NetworkParams, state: DistState, *,
              spec: TileSpec, stencil: StencilSpec, row_axes, col_axis,
              impl: str = "ref", compress: bool = True,
              seed: Optional[jax.Array] = None,
              nu_scale: Optional[jax.Array] = None,
              node: Optional[NodeSpec] = None) -> DistState:
    """One distributed step (runs per-shard under shard_map).

    Device- and process-agnostic: the ppermutes span whatever the mesh
    axes span. On a single-process mesh they are intra-process copies;
    on a process-major multi-process mesh (runtime/multiprocess.py) the
    same permutes cross OS-process boundaries as real messages (gloo TCP
    on CPU, ICI on TPU) — the JAX-native analogue of the paper's MPI
    spike exchange.

    With ``cfg.exchange.pipelined`` the exchanged halo frame is **double-
    buffered** across steps (DESIGN.md §Fusion): the exchange issued this
    step is only carried (``DistState.ext_pending``), and the frame
    received from the *previous* step's exchange is written into the
    history ring — every remote read sits at delay >= 2, so deferring the
    write by one step is invisible to the dynamics (bitwise-equal) while
    the collective gains a full step of compute to hide behind instead
    of the sub-step overlap window. Under STDP the lag-1 pre-trace halo
    is consumed on arrival in both schedules (its one-step semantics
    cannot defer), which pins the collective back to the sub-step window
    whenever plasticity is on — the paper's measured configuration
    (plasticity off) gets the full-step slack.

    With ``node`` (a :class:`~repro.core.partition.NodeSpec`; requires
    the hierarchical 4-axis mesh) the halo exchange runs two-level
    (:func:`exchange_halo_hier`); with
    ``cfg.exchange.exchange_mode == "auto"`` the wire format resolves
    per ring (:func:`resolve_ring_modes`) — both orthogonal to
    pipelining and STDP, and all combinations bitwise-equal to the flat
    uniform-mode step.
    """
    assert_axis_sizes(spec, row_axes, col_axis)
    r = spec.radius
    n = cfg.neurons_per_column
    c = spec.columns_per_tile
    d_slots = state.hist_ext.shape[0]
    pipelined = cfg.exchange.pipelined
    if any(delay < 2 for (_, _, _, delay, _) in stencil.offsets):
        raise ValueError(
            "comm/compute overlap requires every remote delay >= 2 steps "
            "(distance-proportional delays guarantee this)"
        )
    if pipelined and stencil.max_delay == 0:
        raise ValueError(
            "pipelined halo exchange requires an axonal-delay ring "
            "(stencil.max_delay >= 1): with no delay there is no future "
            "step to defer the exchanged spike table into — disable "
            "ExchangeConfig.pipelined or restore min_delay_steps >= 1"
        )
    mode = cfg.conn.exchange_mode
    if mode not in ("dense_packed", "aer_sparse"):
        raise ValueError(
            f"unknown exchange_mode {mode!r} "
            f"(expected 'dense_packed' or 'aer_sparse')")
    aer = mode == "aer_sparse"
    # per-ring wire-format selection (ExchangeConfig.exchange_mode ==
    # "auto"): resolved once at trace time from the exact byte
    # accounting; None means every ring inherits `mode`
    ring_modes = resolve_ring_modes(cfg, spec, node, compress=compress)
    hier = node is not None
    plastic = state.plastic
    if plastic is not None:
        # live plastic weights replace the frozen generated ones
        params = params._replace(w_local=plastic.w_local,
                                 rem_w=plastic.rem_w)

    # integrity guard (DESIGN.md §Integrity): one HaloGuard per step
    # frames every wire message below with a checksum word; `shift`/
    # `wrap` stay None when the guard is off, so the exchange functions
    # fall back to the raw ring _shift and trace the pre-guard graph.
    gcfg = cfg.guard
    hguard = shift = wrap = None
    if gcfg.enabled:
        hguard = integrity.HaloGuard(gcfg, state.t)
        shift = hguard.wrap(_shift)
        wrap = hguard.wrap

    # (1) issue the halo exchange of step t-1's spikes FIRST -------------
    # (under STDP the pre-trace halo strips ride the same two ppermute
    # phases, inside the same overlap window). In aer_sparse mode every
    # strip crosses as a fixed-capacity (count, addresses[cap]) event
    # list; the result is bitwise-equal to dense_packed whenever no send
    # saturates (aer_sat flags when one does).
    aer_sat = jnp.zeros((), jnp.bool_)
    new_trace_ext = None
    with jax.named_scope("dpsnn.halo"):
        if plastic is not None:
            pre_frame = plastic.traces.x_pre.reshape(
                spec.tile_h, spec.tile_w, n)
            if hier or ring_modes is not None:
                # hierarchical and/or per-ring-mode paths: the trace halo
                # rides dense f32 on every ring (module invariants), so
                # pre_ext already carries exact values — interior included
                if hier:
                    ext_frame, pre_ext, aer_sat = exchange_halo_hier(
                        state.pending, spec, node, modes=ring_modes,
                        mode=mode, rate_bound_hz=cfg.conn.aer_rate_bound_hz,
                        capacity_factor=cfg.conn.aer_capacity_factor,
                        dt_ms=cfg.neuron.dt_ms, compress=compress,
                        trace=pre_frame, wrap_shift=wrap)
                else:
                    ext_frame, pre_ext, aer_sat = exchange_halo_modes(
                        state.pending, spec, row_axes, col_axis,
                        modes=ring_modes,
                        rate_bound_hz=cfg.conn.aer_rate_bound_hz,
                        capacity_factor=cfg.conn.aer_capacity_factor,
                        dt_ms=cfg.neuron.dt_ms, compress=compress,
                        trace=pre_frame, shift_fn=shift)
                if plastic.trace_ext is not None:
                    # keep the (aer_sparse-allocated) halo'd trace table
                    # maintained with the same values the event-driven
                    # reconstruction would produce — it holds ext(x_pre(t-1))
                    # after step t, exactly like the flat AER path
                    new_trace_ext = pre_ext
            elif aer:
                ext_frame, sparse_tr, aer_sat = exchange_halo_aer(
                    state.pending, spec, row_axes, col_axis,
                    rate_bound_hz=cfg.conn.aer_rate_bound_hz,
                    capacity_factor=cfg.conn.aer_capacity_factor,
                    dt_ms=cfg.neuron.dt_ms, trace=pre_frame, shift_fn=shift)
                # Event-driven trace-halo reconstruction: the exchanged trace
                # obeys x_pre(t-1) = x_pre(t-2)*dp + spikes(t-1) at EVERY
                # neuron, so the halo copy only needs fresh (shipped) values
                # at spiking addresses — everywhere else the receiver decays
                # its previous halo frame locally with the same dp the sender
                # used, which is bitwise-identical (x*dp + 0 == x*dp for the
                # non-negative traces). Interior is overwritten with the
                # shard's own exact x_pre.
                dp = jnp.exp(
                    -cfg.neuron.dt_ms / cfg.stdp_cfg.tau_plus_ms
                ).astype(pre_frame.dtype)
                pre_ext = jnp.where(ext_frame > 0, sparse_tr,
                                    plastic.trace_ext * dp)
                pre_ext = jax.lax.dynamic_update_slice(
                    pre_ext, pre_frame, (r, r, 0))
                new_trace_ext = pre_ext
            else:
                ext_frame, pre_ext = exchange_halo(
                    state.pending, spec, row_axes, col_axis, compress=compress,
                    trace=pre_frame, shift_fn=shift)
        elif hier or ring_modes is not None:
            if hier:
                ext_frame, _, aer_sat = exchange_halo_hier(
                    state.pending, spec, node, modes=ring_modes, mode=mode,
                    rate_bound_hz=cfg.conn.aer_rate_bound_hz,
                    capacity_factor=cfg.conn.aer_capacity_factor,
                    dt_ms=cfg.neuron.dt_ms, compress=compress,
                    wrap_shift=wrap)
            else:
                ext_frame, _, aer_sat = exchange_halo_modes(
                    state.pending, spec, row_axes, col_axis, modes=ring_modes,
                    rate_bound_hz=cfg.conn.aer_rate_bound_hz,
                    capacity_factor=cfg.conn.aer_capacity_factor,
                    dt_ms=cfg.neuron.dt_ms, compress=compress, shift_fn=shift)
        elif aer:
            ext_frame, _, aer_sat = exchange_halo_aer(
                state.pending, spec, row_axes, col_axis,
                rate_bound_hz=cfg.conn.aer_rate_bound_hz,
                capacity_factor=cfg.conn.aer_capacity_factor,
                dt_ms=cfg.neuron.dt_ms, shift_fn=shift)
        else:
            ext_frame = exchange_halo(state.pending, spec, row_axes, col_axis,
                                      compress=compress, shift_fn=shift)

    # (2) ring write (pipelined only, before the reads) ------------------
    # pipelined: consume the PREVIOUS step's exchange — write the carried
    # double buffer (ext of spikes(t-2)) into slot t-2 BEFORE the reads
    # below (delay-2 offsets read that very slot this step). The frame
    # is a scan-carried value, NOT this step's collective, so the reads
    # depending on it cost nothing; the exchange issued above stays in
    # flight until step t+1. Unpipelined: the reads must take from the
    # PRE-write ring (slot t-1 is never read at delay >= 2) so the
    # delivery compute keeps zero dataflow dependency on the in-flight
    # permutes — the write happens after compute, step (4).
    new_ext_pending = None
    if pipelined:
        with jax.named_scope("dpsnn.ring"):
            hist_ext = jax.lax.dynamic_update_index_in_dim(
                state.hist_ext, state.ext_pending, (state.t - 2) % d_slots,
                axis=0)
        read_hist = hist_ext
        new_ext_pending = ext_frame
    else:
        read_hist = state.hist_ext

    # (3) heavy local work while the permutes are in flight --------------
    # local delivery: delay 1 == the carried pending frame (shard-local);
    # remote delivery: delays >= 2 come from the extended ring buffer
    s_loc = state.pending.reshape(c, n)
    with jax.named_scope("dpsnn.ring"):
        per_offset = []
        for (dy, dx, _k, delay, _p) in stencil.offsets:
            frame = jnp.take(read_hist, (state.t - delay) % d_slots, axis=0)
            block = net.offset_slice(frame, dy, dx, r, spec.tile_h,
                                     spec.tile_w, n)
            per_offset.append(block.reshape(c, n))
        s_flat = jnp.stack(per_offset, axis=1).reshape(
            c, stencil.n_offsets * n)
    col_ids = shard_col_ids(cfg, spec, row_axes, col_axis)
    ext_drive, ext_counts = net.external_drive(cfg, state.t, col_ids,
                                               seed=seed, nu_scale=nu_scale)

    new_traces = None
    gflags = None
    if impl == "pallas_fused":
        # one megakernel for delivery + LIF + trace decay (DESIGN §Fusion)
        lif, spikes, new_traces, gflags = net.fused_stage(
            cfg, params, state.lif,
            plastic.traces if plastic is not None else None,
            s_loc, s_flat, ext_drive)
    else:
        lif, spikes = net.unfused_stage(cfg, params, state.lif, s_loc,
                                        s_flat, ext_drive, impl)

    # chaos NaN injection lands on the freshly computed membrane state so
    # the guard verdict below detects it within the same step
    if gcfg.enabled and gcfg.chaos_nan_at_step >= 0:
        lif = lif._replace(v=integrity.inject_nan(gcfg, state.t, lif.v))
        gflags = None      # kernel flags pre-date the injection

    # (3b) STDP: consume the trace exchange — local outer-product update
    # plus remote ELL gather-update through the halo'd pre-trace table.
    # Same one-step-lag table the single-shard loop builds by shifting
    # (bitwise-equal values => bitwise-equal weight trajectories).
    new_plastic = None
    if plastic is not None:
        with jax.named_scope("dpsnn.stdp"):
            per_tr = [
                net.offset_slice(pre_ext, dy, dx, r, spec.tile_h,
                                 spec.tile_w, n).reshape(c, n)
                for (dy, dx, _k, _delay, _p) in stencil.offsets
            ]
            table = jnp.stack(per_tr, axis=1).reshape(
                c, stencil.n_offsets * n)
        is_inh = conn.neuron_types(cfg)
        new_params, traces = plast.stdp_update(
            cfg, cfg.stdp_cfg, params, plastic.traces, spikes, is_inh,
            pre_trace_table=table, rem_flat=params.rem_flat, impl=impl,
            new_traces=new_traces,  # fused: kernel-advanced, not recomputed
        )
        new_plastic = PlasticState(
            w_local=new_params.w_local, rem_w=new_params.rem_w,
            traces=traces, trace_ext=new_trace_ext,
        )

    # (4) unpipelined: consume the exchange — write extended frame t-1
    # into the ring AFTER the compute above, so the collective had the
    # whole step's compute to hide behind (first read at t+1)
    if not pipelined:
        with jax.named_scope("dpsnn.ring"):
            hist_ext = jax.lax.dynamic_update_index_in_dim(
                state.hist_ext, ext_frame, (state.t - 1) % d_slots, axis=0)

    # exact int32 counts, as in network.step_single (core/counters.py)
    k_tot = stencil.k_total
    n_spikes = spikes.astype(jnp.int32)
    events = ((n_spikes * (params.local_outdeg.astype(jnp.int32) + k_tot)
               ).sum() + ext_counts.sum())

    # (5) ISI accumulation: a neuron spiking at t with a recorded previous
    # spike contributes isi = t - last_spike_t. Sums are integer-valued
    # f32 (exact), so the checkpoint reshard can merge per-shard partials
    # in any order without changing the statistics.
    spiked = spikes > 0
    had_prior = state.last_spike_t >= 0
    contrib = spiked & had_prior
    isi = (state.t - state.last_spike_t).astype(jnp.float32)
    isi_sum = state.isi_sum + jnp.where(contrib, isi, 0.0).sum()
    isi_sumsq = state.isi_sumsq + jnp.where(contrib, isi * isi, 0.0).sum()
    isi_count = state.isi_count + contrib.sum().astype(jnp.float32)
    last_spike_t = jnp.where(spiked, state.t, state.last_spike_t)

    # (6) integrity verdict (DESIGN.md §Integrity): invariant monitors on
    # this step's freshly computed state plus the halo checksums and the
    # AER-saturation escalation, folded into the carried GuardState.
    new_guard = None
    if gcfg.enabled:
        tr = new_plastic.traces if new_plastic is not None else None
        code = integrity.step_verdict(
            gcfg, v=lif.v, spikes=spikes,
            x_pre=tr.x_pre if tr is not None else None,
            x_post=tr.x_post if tr is not None else None,
            kernel_flags=gflags)
        new_guard = integrity.guard_update(
            gcfg, state.guard, step_code=code, t=state.t,
            aer_sat=aer_sat, chk_fail=hguard.fail, chk_count=hguard.count)

    return DistState(
        lif=lif,
        hist_ext=hist_ext,
        pending=spikes.reshape(spec.tile_h, spec.tile_w, n),
        t=state.t + 1,
        spike_count=counters.add(state.spike_count, n_spikes.sum()),
        event_count=counters.add(state.event_count, events),
        plastic=new_plastic,
        aer_sat=aer_sat,
        ext_pending=new_ext_pending,
        last_spike_t=last_spike_t,
        isi_sum=isi_sum,
        isi_sumsq=isi_sumsq,
        isi_count=isi_count,
        guard=new_guard,
    )


# ---------------------------------------------------------------------------
# Top-level distributed runner
# ---------------------------------------------------------------------------

class DistResult(NamedTuple):
    rate_hz: jax.Array
    events: jax.Array
    spikes: jax.Array
    state_checksum: jax.Array
    # per-step AER saturation flags, (n_steps,) int32 in {0, 1}: step i is
    # 1 iff ANY rank's send overflowed its static event capacity at step
    # i (events beyond capacity were truncated from the wire — the run is
    # degraded and says so; silent drops are forbidden). All zeros under
    # dense_packed and for any AER run within its rate bound.
    aer_saturated: Optional[jax.Array] = None


def _stack_specs(tree, joint):
    """out/in specs for per-shard state carried as a stacked global array
    with a leading shard axis (leaf (..,) per shard -> (S, ..) global)."""
    return jax.tree_util.tree_map(lambda _: P(joint), tree)


def make_distributed_run(cfg: DPSNNConfig, mesh: Mesh, *, n_steps: int,
                         impl: str = "ref", compress: bool = True,
                         with_state: bool = False,
                         replicate_state: bool = False):
    """Build a jitted ``run(key) -> DistResult`` (or, with ``with_state``,
    ``run(key, stacked_state|None is not supported -> use resume fn)``)
    that generates, initialises and simulates the sharded network entirely
    on-device.

    Works on any mesh with axes ('data','model') or ('pod','data','model')
    — grid rows shard over ('pod','data'), grid columns over 'model' —
    or the hierarchical ('ndata','data','nmodel','model') convention
    (:func:`mesh_layout`), under which every step runs the two-level
    exchange of DESIGN.md §Hierarchy.

    When ``with_state`` the function returns ``(DistResult, stacked_state)``
    where every state leaf gains a leading per-shard axis (size =
    n_devices) — the layout used by the checkpointer, and accepted back by
    :func:`make_distributed_resume` to continue a run (fault tolerance).

    With ``replicate_state`` the stacked state is additionally
    ``all_gather``-ed over the whole mesh so EVERY process holds the full
    (S, ...) global stack in process-major shard order — the layout the
    supervisor checkpoints from rank 0 and the elastic reshard consumes
    (``stacked_state_template`` describes it; DESIGN.md §Elasticity).
    """
    row_axes, col_axis, node, row_shards, col_shards = mesh_layout(mesh)
    joint = tuple(mesh.axis_names)
    spec = make_tile_spec(cfg, row_shards, col_shards)
    stencil = build_stencil(cfg)

    def simulate(params, state):
        def body(s, _):
            s1 = dist_step(cfg, params, s, spec=spec, stencil=stencil,
                           row_axes=row_axes, col_axis=col_axis,
                           impl=impl, compress=compress, node=node)
            return s1, s1.aer_sat

        final, sat_steps = jax.lax.scan(body, state, None, length=n_steps)
        spikes = counters.value(jax.lax.psum(final.spike_count, joint))
        events = counters.value(jax.lax.psum(final.event_count, joint))
        sim_s = n_steps * cfg.neuron.dt_ms * 1e-3
        rate = spikes / (cfg.n_neurons * sim_s)
        checksum = jax.lax.psum(final.lif.v.sum(), joint)
        # a step is saturated if ANY rank overflowed: max over the mesh
        saturated = jax.lax.pmax(sat_steps.astype(jnp.int32), joint)
        return DistResult(rate, events, spikes, checksum, saturated), final

    def fresh():
        params = build_shard(cfg, spec, row_axes, col_axis)
        state = init_shard(cfg, spec, stencil, row_axes, col_axis,
                           params=params)
        out, final = simulate(params, state)
        if with_state:
            stacked = jax.tree_util.tree_map(lambda x: x[None], final)
            if replicate_state:
                stacked = jax.tree_util.tree_map(
                    lambda x: jax.lax.all_gather(x, joint, tiled=True),
                    stacked)
            return out, stacked
        return out

    result_specs = DistResult(P(), P(), P(), P(), P())
    if with_state:
        struct = _state_structure(cfg, spec, stencil)
        state_specs = (jax.tree_util.tree_map(lambda _: P(), struct)
                       if replicate_state else _stack_specs(struct, joint))
        out_specs = (result_specs, state_specs)
    else:
        out_specs = result_specs

    fn = jax.shard_map(fresh, mesh=mesh, in_specs=(), out_specs=out_specs,
                       check_vma=False)
    return jax.jit(fn), spec


def make_distributed_resume(cfg: DPSNNConfig, mesh: Mesh, *, n_steps: int,
                            impl: str = "ref", compress: bool = True,
                            replicate_state: bool = False):
    """``run(stacked_state) -> (DistResult, stacked_state)`` — continue a
    simulation from checkpointed per-shard state (restart after failure).
    Parameters are regenerated deterministically on every shard, so only
    dynamical state crosses the checkpoint boundary.

    With ``replicate_state`` the stacked state is **replicated** on both
    sides instead of mesh-sharded: the input may be the host numpy tree a
    checkpoint restore (or :func:`checkpoint.checkpointer.reshard`)
    produced — every process passes the identical full (S, ...) stack,
    each shard slices its own process-major entry, and the output is
    all_gathered back to every process (the supervisor's chunked-run
    layout, DESIGN.md §Elasticity)."""
    row_axes, col_axis, node, row_shards, col_shards = mesh_layout(mesh)
    joint = tuple(mesh.axis_names)
    spec = make_tile_spec(cfg, row_shards, col_shards)
    stencil = build_stencil(cfg)

    def resume(stacked):
        if replicate_state:
            ty, tx = _shard_coords(spec, row_axes, col_axis)
            s = ty * spec.tiles_x + tx
            state = jax.tree_util.tree_map(
                lambda x: jnp.take(x, s, axis=0), stacked)
        else:
            state = jax.tree_util.tree_map(lambda x: x[0], stacked)
        params = build_shard(cfg, spec, row_axes, col_axis)

        def body(s, _):
            s1 = dist_step(cfg, params, s, spec=spec, stencil=stencil,
                           row_axes=row_axes, col_axis=col_axis,
                           impl=impl, compress=compress, node=node)
            return s1, s1.aer_sat

        final, sat_steps = jax.lax.scan(body, state, None, length=n_steps)
        spikes = counters.value(jax.lax.psum(final.spike_count, joint))
        events = counters.value(jax.lax.psum(final.event_count, joint))
        sim_s = n_steps * cfg.neuron.dt_ms * 1e-3
        rate = spikes / (cfg.n_neurons * sim_s)
        checksum = jax.lax.psum(final.lif.v.sum(), joint)
        saturated = jax.lax.pmax(sat_steps.astype(jnp.int32), joint)
        out = DistResult(rate, events, spikes, checksum, saturated)
        stacked_out = jax.tree_util.tree_map(lambda x: x[None], final)
        if replicate_state:
            stacked_out = jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, joint, tiled=True),
                stacked_out)
        return out, stacked_out

    struct = _state_structure(cfg, spec, stencil)
    if replicate_state:
        specs = jax.tree_util.tree_map(lambda _: P(), struct)
    else:
        specs = _stack_specs(struct, joint)
    fn = jax.shard_map(resume, mesh=mesh, in_specs=(specs,),
                       out_specs=(DistResult(P(), P(), P(), P(), P()), specs),
                       check_vma=False)
    return jax.jit(fn), spec


def make_batched_distributed_run(cfg: DPSNNConfig, mesh: Mesh, *,
                                 n_steps: int, batch: int,
                                 impl: str = "ref", compress: bool = True,
                                 with_stimulus: bool = False,
                                 with_state: bool = False):
    """Batched multi-tenant distributed runner (DESIGN.md §Service).

    B independent tenants advance under one ``vmap`` of :func:`dist_step`
    *inside* shard_map: the halo ppermutes batch elementwise, so each
    collective carries the whole (b_local, strip) batched frame in one
    message — both wire formats (``dense_packed`` bitmaps and
    ``aer_sparse`` event lists gain a leading tenant axis; capacities are
    per-tenant, saturation flags OR across tenants).

    The mesh may carry an optional leading ``'batch'`` axis **orthogonal**
    to the spatial column mesh (``('pod',)'data','model'``): tenants shard
    over 'batch' (``b_local = batch // batch_shards`` per shard) while
    every batch shard owns the full column tile of its spatial
    coordinate. Per-tenant reductions (spikes/events/rate/checksum) psum
    over the *spatial* axes only, then all_gather over 'batch', so every
    rank returns the full replicated (batch,) vectors.

    Returns ``(jitted_run, spec)`` where ``run(seeds)`` (or
    ``run(seeds, nu_scale)`` with ``with_stimulus``) takes per-tenant
    (batch,) int32 seeds and yields a :class:`DistResult` of (batch,)
    leaves (``aer_saturated`` stays (n_steps,), OR of all ranks and
    tenants). With ``with_state`` the runner also returns the stacked
    per-shard state whose leaves carry (n_shards, b_local, ...) — the
    layout the checkpointer round-trips.
    """
    if "nmodel" in mesh.axis_names:
        raise ValueError(
            "the batched multi-tenant runner does not support the "
            "hierarchical ('ndata','data','nmodel','model') mesh — run "
            "tenants on a flat spatial mesh, or drop --ranks-per-node")
    batch_shards = mesh.shape.get("batch", 1)
    if batch % batch_shards:
        raise ValueError(
            f"batch={batch} tenants do not divide over the mesh's "
            f"batch axis of {batch_shards} shards — choose batch as a "
            f"multiple of {batch_shards} (each shard runs "
            f"batch/batch_shards tenants in lockstep)")
    multi_pod = "pod" in mesh.axis_names
    row_axes = ("pod", "data") if multi_pod else "data"
    col_axis = "model"
    joint = tuple(mesh.axis_names)
    spatial = tuple(a for a in mesh.axis_names if a != "batch")
    row_shards = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    col_shards = mesh.shape["model"]
    spec = make_tile_spec(cfg, row_shards, col_shards)
    stencil = build_stencil(cfg)
    t_spec = P("batch") if "batch" in mesh.shape else P()

    def simulate(seeds, nu_scale):
        params = build_shard(cfg, spec, row_axes, col_axis)
        state = jax.vmap(
            lambda s: init_shard(cfg, spec, stencil, row_axes, col_axis,
                                 params=params, seed=s))(seeds)

        def one(s, sd, nsc):
            return dist_step(cfg, params, s, spec=spec, stencil=stencil,
                             row_axes=row_axes, col_axis=col_axis,
                             impl=impl, compress=compress, seed=sd,
                             nu_scale=nsc if with_stimulus else None)

        if with_stimulus:
            vstep = jax.vmap(one, in_axes=(0, 0, 0))
            advance = lambda s: vstep(s, seeds, nu_scale)  # noqa: E731
        else:
            vstep = jax.vmap(lambda s, sd: one(s, sd, None),
                             in_axes=(0, 0))
            advance = lambda s: vstep(s, seeds)  # noqa: E731

        def body(s, _):
            s1 = advance(s)
            return s1, s1.aer_sat                  # (b_local,) per step

        final, sat_steps = jax.lax.scan(body, state, None, length=n_steps)
        spikes = counters.value(
            jax.lax.psum(final.spike_count, spatial))         # (b_local,)
        events = counters.value(jax.lax.psum(final.event_count, spatial))
        sim_s = n_steps * cfg.neuron.dt_ms * 1e-3
        rate = spikes / (cfg.n_neurons * sim_s)
        checksum = jax.lax.psum(final.lif.v.sum(axis=(1, 2)), spatial)
        saturated = jax.lax.pmax(
            sat_steps.any(axis=1).astype(jnp.int32), joint)   # (n_steps,)
        if batch_shards > 1:
            # replicate the per-tenant vectors: every rank (including the
            # one the launcher reads) gets the full (batch,) result
            rate, events, spikes, checksum = (
                jax.lax.all_gather(x, "batch", tiled=True)
                for x in (rate, events, spikes, checksum))
        out = DistResult(rate, events, spikes, checksum, saturated)
        if with_state:
            return out, jax.tree_util.tree_map(lambda x: x[None], final)
        return out

    seeds_spec = t_spec
    result_specs = DistResult(P(), P(), P(), P(), P())
    in_specs = (seeds_spec, seeds_spec) if with_stimulus else (seeds_spec,)
    if with_state:
        out_specs = (result_specs,
                     _stack_specs(_state_structure(cfg, spec, stencil),
                                  joint))
    else:
        out_specs = result_specs
    if not with_stimulus:
        fn = jax.shard_map(lambda seeds: simulate(seeds, None), mesh=mesh,
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=False)
    else:
        fn = jax.shard_map(simulate, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(fn), spec


def _state_structure(cfg: DPSNNConfig, spec: TileSpec,
                     stencil: StencilSpec) -> DistState:
    """A DistState-shaped pytree of placeholders (for spec construction)."""
    plastic = None
    aer = cfg.conn.exchange_mode == "aer_sparse"
    if cfg.stdp:
        plastic = PlasticState(w_local=0, rem_w=0,
                               traces=STDPState(x_pre=0, x_post=0),
                               trace_ext=0 if aer else None)
    return DistState(
        lif=LIFState(v=0, c=0, refrac=0),
        hist_ext=0, pending=0, t=0, spike_count=0, event_count=0,
        plastic=plastic, aer_sat=0,
        ext_pending=0 if cfg.exchange.pipelined else None,
        last_spike_t=0, isi_sum=0, isi_sumsq=0, isi_count=0,
        guard=(GuardState(tripped=0, trip_code=0, trip_step=0, sat_run=0,
                          checksum_fails=0)
               if cfg.guard.enabled else None),
    )


def stacked_state_template(cfg: DPSNNConfig, n_ranks: int):
    """``(template, spec, stencil)`` for a checkpointed distributed run.

    ``template`` is a :class:`DistState` of host numpy zeros whose leaves
    carry the shard-stacked global shapes ``(S, ...)`` that
    :func:`make_distributed_run`/``make_distributed_resume`` emit with
    ``replicate_state=True`` — the ``tree_like`` the checkpointer
    validates restores against, and the shape contract
    ``checkpoint.checkpointer.reshard`` maps between mesh sizes
    (DESIGN.md §Elasticity). Built with ``jax.eval_shape``: no synapse
    generation or device work happens.
    """
    import numpy as np

    from repro.core.partition import make_rank_tile_spec

    spec = make_rank_tile_spec(cfg, n_ranks)
    stencil = build_stencil(cfg)

    def mk():
        col_ids = tile_column_ids(cfg, spec, jnp.int32(0), jnp.int32(0))
        params = net.build_params(cfg, col_ids)
        return init_shard(cfg, spec, stencil, None, None, params=params,
                          col_ids=col_ids)

    shard_struct = jax.eval_shape(mk)
    s = spec.tiles_y * spec.tiles_x
    template = jax.tree_util.tree_map(
        lambda leaf: np.zeros((s, *leaf.shape), leaf.dtype), shard_struct)
    return template, spec, stencil


from repro.core.partition import NodeSpec, make_tile_spec  # noqa: E402
# (bottom import avoids a cycle: partition imports configs only)
