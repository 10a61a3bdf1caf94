"""Network containers and the single-shard step function.

The network is a grid of columns. Per shard we hold:

* ``w_local``  (C, N, N) dense intra-column weights  [src, tgt]
* ``rem_flat`` (C, N, K) int32 gather indices into the flattened
  (O*N,) per-column neighbour-spike table
* ``rem_w``    (C, N, K) remote weights
* spike **history ring buffer** (D, C, N) implementing axonal delays —
  the TPU-native replacement for DPSNN's per-synapse delayed delivery
  queues (DESIGN.md §2).

Local delivery has interchangeable implementations selected by ``impl``:
``"ref"`` (pure jnp, the oracle), ``"pallas"`` (kernels/synapse_matmul)
and ``"pallas_fused"`` (the column-step megakernel). They produce the same
currents (tests/test_kernels.py and tests/test_fused_step.py assert it).
Remote ELL delivery under ``"ref"`` is the reference's XLA gather
(:func:`deliver_remote_ref`, the oracle); both Pallas impls run
kernels/ell_deliver.py over the spike table packed 32 spikes to a word
(:func:`deliver_remote_packed`), which needs no gather.

Each layer of a step runs under one ``jax.named_scope``, which names its
ops in the compiled program (``op_name`` metadata) and in a profiler
trace, and changes no op: ``dpsnn.drive``, ``dpsnn.ring`` (delayed spike
table, history writes), ``dpsnn.halo`` (core/exchange.py),
``dpsnn.remote``, ``dpsnn.neuron`` (local delivery, LIF+SFA),
``dpsnn.stdp`` (core/plasticity.py) and ``dpsnn.params``. The scopes do
not nest, so each op has at most one; counters and the guard have none.
"""
from __future__ import annotations

import functools
import itertools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import DPSNNConfig
from repro.core import connectivity as conn
from repro.core import counters
from repro.core.connectivity import StencilSpec, build_stencil
from repro.core.neuron import LIFState, lif_init, lif_sfa_step


class NetworkParams(NamedTuple):
    w_local: jax.Array      # (C, N, NW), NW = N or its lane_width
    rem_flat: jax.Array     # (C, N, KW) idx into (O*N,) table, KW likewise
    rem_w: jax.Array        # (C, N, KW)
    local_outdeg: jax.Array  # (C, N) for synaptic-event accounting


LANES, SUBLANES = 128, 8


def _tiled_size(shape: tuple[int, ...], order: tuple[int, ...],
                itemsize: int) -> int:
    """Elements a TPU's tiled layout of ``shape`` holds with its axes in
    ``order`` (major to minor): the minor axis rounded up to whole
    128-lane tiles, the second-minor to 8 sublanes (1, 2 or 4 kept, and
    at least the rows one 32-bit word packs)."""
    up = lambda x, m: -(-x // m) * m
    *major, second, minor = (shape[i] for i in order)
    rows = second if second in (1, 2, 4) else up(second, SUBLANES)
    size = up(minor, LANES) * max(rows, 4 // itemsize)
    for d in major:
        size *= d
    return size


def tpu_row_major(shape: tuple[int, ...], itemsize: int = 4) -> bool:
    """Whether a TPU lays an array of ``shape`` out row-major by default.

    It takes the order of the axes whose tiles hold the fewest elements,
    the row-major order on a tie (tests/test_tpu_compile.py checks this
    against the TPU compiler's own default layouts)."""
    row = _tiled_size(shape, tuple(range(len(shape))), itemsize)
    return all(row <= _tiled_size(shape, order, itemsize)
               for order in itertools.permutations(range(len(shape))))


def lane_width(shape: tuple[int, int, int], *itemsizes: int) -> int:
    """Stored width of the minor axis of a static ``(C, N, width)``
    synapse table of elements of ``itemsizes`` bytes (4 if none given).

    The kernels read one column's ``(N, width)`` block in place, so the
    table has to be laid out row-major on the chip. A TPU lays it out
    otherwise where another order of the axes pads less: the columns along
    the lanes when C is a whole number of lane tiles and ``width`` is not
    (2,304 columns of 1,240 targets or 248 slots), the targets there for
    576 columns of K = 1,028 slots; every call then copies the whole
    table into the kernels' layout. Such a table is stored ``width``
    rounded up to whole lanes where that makes it row-major and adds at
    most an eighth to it (the kernels read every stored entry each step,
    so a narrow table, a small grid's K = 24, keeps its width and the
    copy); the added entries are absent synapses (weight 0). Any other
    table is stored at its own width."""
    c, n, width = shape
    wide = -(-width // LANES) * LANES
    if 8 * (wide - width) > width:
        return width
    for size in itemsizes or (4,):
        if (not tpu_row_major(shape, size)
                and tpu_row_major((c, n, wide), size)):
            return wide
    return width


class NetworkState(NamedTuple):
    lif: LIFState           # leaves (C, N)
    hist: jax.Array         # (D, C, N) spike history ring buffer
    t: jax.Array            # scalar int32 step counter
    spike_count: jax.Array  # counters.py pair: total spikes emitted
    event_count: jax.Array  # counters.py pair: total synaptic events
    stdp: Optional[Any] = None  # STDPState traces when cfg.stdp, else None
    guard: Optional[Any] = None  # GuardState when cfg.guard.enabled


# Bytes of the (block, N, N) float32 generation intermediates that one
# block of build_params may take.
BUILD_BUDGET = 4 << 30


def _column_params(cfg: DPSNNConfig, stencil: StencilSpec,
                   col_ids: jax.Array, nw: int, kw: int) -> NetworkParams:
    """The params of ``col_ids``, their tables ``nw`` / ``kw`` wide."""
    n, k = cfg.neurons_per_column, stencil.k_total
    w_local, rem_idx, rem_w = conn.generate_columns(cfg, col_ids)
    rem_flat = conn.flat_gather_index(stencil, rem_idx, n)
    outdeg = conn.local_out_degree(w_local).astype(jnp.float32)
    pad = lambda x, to, value=0: jnp.pad(
        x, ((0, 0), (0, 0), (0, to - x.shape[2])), constant_values=value)
    if nw != n:
        w_local = pad(w_local, nw)
    if kw != k:
        # absent slots of the last offset: source 0 of its column, weight 0
        rem_flat = pad(rem_flat, kw, (stencil.n_offsets - 1) * n)
        rem_w = pad(rem_w, kw)
    return NetworkParams(w_local=w_local, rem_flat=rem_flat, rem_w=rem_w,
                         local_outdeg=outdeg)


def build_params(cfg: DPSNNConfig, col_ids: jax.Array) -> NetworkParams:
    """The synapses of the columns ``col_ids``.

    They are generated in the fewest equal blocks of columns whose
    ``(block, N, N)`` float32 intermediates fit ``BUILD_BUDGET``, each
    written into the result in place, so that params which fill most of
    the chip are built without a second params-sized intermediate: the
    paper's 24x24 grid, or a 24x24 tile of a mesh, is one block; its
    48x48 grid four of 576 columns. Generation is deterministic per
    global column id, so every blocking gives the same bits. A block that
    does not divide the column count ends on a last block that overlaps
    the one before it and rewrites those columns with the same values.

    A static network's tables are stored at :func:`lane_width`; a plastic
    network's, which the STDP rule rewrites, at their own widths."""
    stencil = build_stencil(cfg)
    nc, n, k = col_ids.shape[0], cfg.neurons_per_column, stencil.k_total
    nw, kw = n, k
    if not cfg.stdp:
        size = jnp.dtype(cfg.weight_dtype).itemsize
        nw, kw = lane_width((nc, n, n), size), lane_width((nc, n, k), size, 4)
    fit = max(1, BUILD_BUDGET // (4 * n * n))
    block = -(-nc // -(-nc // fit))
    with jax.named_scope("dpsnn.params"):
        if block == nc:
            return _column_params(cfg, stencil, col_ids, nw, kw)
        out = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda ids: _column_params(cfg, stencil, ids,
                                                      nw, kw), col_ids))

        def put(i, acc):
            c0 = jnp.minimum(i * block, nc - block)
            part = _column_params(
                cfg, stencil, jax.lax.dynamic_slice_in_dim(col_ids, c0, block),
                nw, kw)
            return jax.tree_util.tree_map(
                lambda a, p: jax.lax.dynamic_update_slice_in_dim(a, p, c0, 0),
                acc, part)

        return jax.lax.fori_loop(0, -(-nc // block), put, out)


def init_state(cfg: DPSNNConfig, col_ids: jax.Array,
               stencil: Optional[StencilSpec] = None, *,
               seed: Optional[jax.Array] = None) -> NetworkState:
    """Initial state, **deterministic per global column id**: every mesh
    decomposition (including single-shard) produces the identical network
    trajectory — the property behind exact elastic re-partitioning
    (tests/test_distributed.py asserts bitwise equality across meshes).

    ``seed`` overrides ``cfg.seed`` for the membrane-voltage draw; it may
    be a traced int32 (the batched service vmaps over per-tenant seeds).
    ``PRNGKey`` of a traced int equals ``PRNGKey`` of the same Python int,
    so ``seed == cfg.seed`` reproduces the unbatched init bitwise
    (DESIGN.md §Service)."""
    stencil = stencil or build_stencil(cfg)
    n = cfg.neurons_per_column
    n_columns = col_ids.shape[0]
    d = stencil.max_delay + 1
    dtype = jnp.dtype(cfg.dtype)
    base = jax.random.PRNGKey(
        (cfg.seed if seed is None else seed) + 0x51F)

    def col_init(cid):
        return lif_init(cfg.neuron, (n,), dtype, jax.random.fold_in(base, cid))

    stdp = None
    if cfg.stdp:
        from repro.core.plasticity import init_stdp  # deferred: avoids cycle
        stdp = init_stdp(n_columns, n, dtype)
    guard = None
    if cfg.guard.enabled:
        from repro.runtime.integrity import init_guard
        guard = init_guard()
    return NetworkState(
        lif=jax.vmap(col_init)(col_ids),
        hist=jnp.zeros((d, n_columns, n), dtype),
        t=jnp.int32(0),
        spike_count=counters.zero(),
        event_count=counters.zero(),
        stdp=stdp,
        guard=guard,
    )


# ---------------------------------------------------------------------------
# Delivery
# ---------------------------------------------------------------------------

def deliver_local_ref(spikes: jax.Array, w_local: jax.Array) -> jax.Array:
    """(C,N) x (C,N,NW) -> (C,N): batched MXU matmul over columns (targets
    past N, a lane-padded table's, are dropped).

    ``Precision.HIGHEST`` keeps the reference f32 on the chip too (a TPU
    multiplies f32 operands in one bf16 pass at default precision); on
    the CPU it changes nothing."""
    cur = jnp.einsum(
        "cs,cst->ct", spikes, w_local,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(spikes.dtype)
    n = spikes.shape[1]
    return cur if cur.shape[1] == n else cur[:, :n]


def deliver_remote_ref(s_flat: jax.Array, rem_flat: jax.Array,
                       rem_w: jax.Array) -> jax.Array:
    """Gather-and-reduce ELL delivery.

    s_flat:   (C, O*N) neighbour spike table (offset-major)
    rem_flat: (C, N, K) indices into the O*N axis
    rem_w:    (C, N, K)
    returns   (C, N) currents
    """
    c, n, k = rem_flat.shape
    with jax.named_scope("dpsnn.remote"):
        gathered = jnp.take_along_axis(
            s_flat, rem_flat.reshape(c, n * k), axis=1
        ).reshape(c, n, k)
        return (gathered * rem_w).sum(axis=-1).astype(s_flat.dtype)


def deliver_remote_packed(s_flat: jax.Array, rem_flat: jax.Array,
                          rem_w: jax.Array, *,
                          stencil: StencilSpec) -> jax.Array:
    """ELL delivery through the Pallas kernel (kernels/ell_deliver.py):
    the same currents as :func:`deliver_remote_ref`, read from the spike
    table packed 32 spikes to a word, with no gather."""
    from repro.kernels import ops
    slots = [k for (_dy, _dx, k, _d, _p) in stencil.offsets]
    # a lane-padded table's absent slots belong to the last offset
    slots[-1] += rem_flat.shape[2] - stencil.k_total
    slots = tuple(slots)
    with jax.named_scope("dpsnn.remote"):
        words = ops.pack_spikes(s_flat, stencil.n_offsets)
        return ops.ell_deliver(words, rem_flat, rem_w, slots=slots,
                               out_dtype=s_flat.dtype)


def _delivery_fns(impl: str, stencil: StencilSpec):
    if impl == "ref":
        return deliver_local_ref, deliver_remote_ref
    if impl == "pallas":
        from repro.kernels import ops
        return ops.synapse_matmul, functools.partial(deliver_remote_packed,
                                                     stencil=stencil)
    raise ValueError(
        f"unknown delivery impl {impl!r} (expected 'ref' or 'pallas'; "
        f"'pallas_fused' runs the whole step as one megakernel and is "
        f"dispatched in step_single/dist_step, not per delivery fn)")


def offset_slice(g_ext: jax.Array, dy: int, dx: int, r: int,
                 h: int, w: int, n: int) -> jax.Array:
    """(h+2r, w+2r, N) halo-extended frame -> the (h, w, N) block seen
    from the neighbour at stencil offset (dy, dx).

    This is THE shift convention — shared by spike delivery and the STDP
    pre-trace tables, single-shard (zero-padded full grid) and
    distributed (halo-extended tile) alike. The bitwise
    mesh==single-shard equivalence tests depend on every table builder
    going through this one helper.
    """
    return jax.lax.slice(g_ext, (r + dy, r + dx, 0),
                         (r + dy + h, r + dx + w, n))


def neighbour_table_single(hist: jax.Array, t: jax.Array,
                           stencil: StencilSpec,
                           grid_hw: tuple[int, int]) -> jax.Array:
    """Build the (C, O*N) delayed neighbour-spike table for a full
    (unsharded) grid. Per active offset o: delayed slice of the history,
    shifted by (dy, dx) with zero boundary (cortical sheet edge).
    """
    gh, gw = grid_hw
    d_slots, c_cols, n = hist.shape
    r = stencil.radius
    with jax.named_scope("dpsnn.ring"):
        per_offset = []
        for (dy, dx, _k, delay, _p) in stencil.offsets:
            s = jnp.take(hist, (t - delay) % d_slots, axis=0)   # (C, N)
            g = jnp.pad(s.reshape(gh, gw, n), ((r, r), (r, r), (0, 0)))
            g = offset_slice(g, dy, dx, r, gh, gw, n)
            per_offset.append(g.reshape(c_cols, n))
        s_ext = jnp.stack(per_offset, axis=1)                # (C, O, N)
        return s_ext.reshape(c_cols, stencil.n_offsets * n)


# ---------------------------------------------------------------------------
# Step
# ---------------------------------------------------------------------------

def external_drive(cfg: DPSNNConfig, t: jax.Array, col_ids: jax.Array, *,
                   seed: Optional[jax.Array] = None,
                   nu_scale: Optional[jax.Array] = None,
                   ) -> tuple[jax.Array, jax.Array]:
    """Poisson thalamo-cortical input: C_ext synapses at nu_ext each.

    Keyed per (global column id, step) so the stream is independent of the
    mesh decomposition. ``seed`` overrides ``cfg.seed`` (per-tenant drive
    streams; may be traced) and ``nu_scale`` multiplies the Poisson rate
    (per-tenant stimulus intensity). Both default to the unbatched path:
    with ``seed is None`` / ``nu_scale is None`` the expression is
    *textually identical* to the single-tenant code, the basis of the
    B=1 bitwise guarantee (DESIGN.md §Service)."""
    lam = cfg.c_ext * cfg.nu_ext_hz * cfg.neuron.dt_ms * 1e-3
    n = cfg.neurons_per_column
    with jax.named_scope("dpsnn.drive"):
        if nu_scale is not None:
            lam = jnp.float32(lam) * nu_scale
        base = jax.random.fold_in(
            jax.random.PRNGKey((cfg.seed if seed is None else seed) + 0xE57),
            t)

        def col_drive(cid):
            return jax.random.poisson(jax.random.fold_in(base, cid), lam,
                                      (n,))

        counts = jax.vmap(col_drive)(col_ids)
        return counts.astype(jnp.dtype(cfg.dtype)) * cfg.conn.j_ext, counts


def step_single(cfg: DPSNNConfig, params: NetworkParams,
                state: NetworkState, *, stencil: StencilSpec,
                grid_hw: tuple[int, int], col_ids: jax.Array,
                impl: str = "ref", seed: Optional[jax.Array] = None,
                nu_scale: Optional[jax.Array] = None,
                chaos_nan: Optional[jax.Array] = None) -> NetworkState:
    """One time step of the full (single-shard) network.

    ``impl='pallas_fused'`` replaces stages 1-3 (plus, under STDP, the
    trace decay+bump) with one megakernel call (kernels/fused_step.py);
    the returned state then carries the *already advanced* traces, which
    the caller's ``stdp_update`` consumes via ``new_traces`` instead of
    recomputing (DESIGN.md §Fusion).

    ``seed``/``nu_scale`` select a per-tenant drive stream / stimulus
    intensity (core/batched.py); ``None`` is the single-tenant path.
    ``chaos_nan`` (traced scalar step, or None) is the per-tenant NaN
    injection override for the guard's chaos path (DESIGN.md
    §Integrity); the static ``cfg.guard.chaos_nan_at_step`` is the
    single-tenant equivalent.
    """
    d_slots = state.hist.shape[0]

    # 1. recurrent delivery from delayed history
    with jax.named_scope("dpsnn.ring"):
        s_loc = jnp.take(
            state.hist, (state.t - cfg.conn.min_delay_steps) % d_slots,
            axis=0)
    s_flat = neighbour_table_single(state.hist, state.t, stencil, grid_hw)

    # 2. external Poisson drive
    ext, ext_counts = external_drive(cfg, state.t, col_ids,
                                     seed=seed, nu_scale=nu_scale)

    # 3. delivery + neuron update (one fused kernel, or three stages)
    new_stdp = state.stdp
    gflags = None
    if impl == "pallas_fused":
        lif, spikes, new_stdp, gflags = fused_stage(
            cfg, params, state.lif, state.stdp, s_loc, s_flat, ext)
    else:
        lif, spikes = unfused_stage(cfg, params, state.lif, s_loc, s_flat,
                                    ext, impl)

    # 3b. in-band integrity guard (DESIGN.md §Integrity): chaos NaN
    # injection lands on the freshly computed membrane state so the
    # verdict below detects it within the same step.
    new_guard = state.guard
    if cfg.guard.enabled:
        from repro.runtime import integrity
        gcfg = cfg.guard
        if gcfg.chaos_nan_at_step >= 0 or chaos_nan is not None:
            lif = lif._replace(
                v=integrity.inject_nan(gcfg, state.t, lif.v,
                                       chaos_step=chaos_nan))
            gflags = None      # kernel flags pre-date the injection
        tr = new_stdp if cfg.stdp else None
        code = integrity.step_verdict(
            gcfg, v=lif.v, spikes=spikes,
            x_pre=tr.x_pre if tr is not None else None,
            x_post=tr.x_post if tr is not None else None,
            kernel_flags=gflags)
        new_guard = integrity.guard_update(gcfg, state.guard,
                                           step_code=code, t=state.t)

    # 4. write new spikes into the ring buffer
    with jax.named_scope("dpsnn.ring"):
        hist = jax.lax.dynamic_update_index_in_dim(
            state.hist, spikes, state.t % d_slots, axis=0)

    # 5. synaptic-event accounting (the paper's normalisation unit):
    #    every emitted spike is delivered to its realized local out-degree
    #    plus (statistically exact for ELL) K_tot remote targets; external
    #    events count each Poisson arrival.
    #    Counted in int32 (exact) into the counters.py running totals.
    k_tot = stencil.k_total
    n_spikes = spikes.astype(jnp.int32)
    events = ((n_spikes * (params.local_outdeg.astype(jnp.int32) + k_tot)
               ).sum() + ext_counts.sum())

    return NetworkState(
        lif=lif,
        hist=hist,
        t=state.t + 1,
        spike_count=counters.add(state.spike_count, n_spikes.sum()),
        event_count=counters.add(state.event_count, events),
        # unfused: traces advance in the caller (simulation.run);
        # fused: the kernel already advanced them (caller consumes)
        stdp=new_stdp,
        guard=new_guard,
    )


def fused_stage(cfg: DPSNNConfig, params: NetworkParams, lif0: LIFState,
                stdp0, s_loc: jax.Array, s_flat: jax.Array,
                ext: jax.Array):
    """Shared dispatch of the column-step megakernel for both loops
    (``stdp0`` is the STDPState traces, or None when plasticity is off).
    Returns ``(lif', spikes, stdp', gflags)`` where ``stdp'`` carries the
    kernel-advanced traces under ``cfg.stdp`` (else ``stdp0`` unchanged)
    and ``gflags`` is the kernel-epilogue guard bitflag vector under
    ``cfg.guard.enabled`` (else None).
    """
    from repro.kernels import ops
    gcfg = cfg.guard if cfg.guard.enabled else None
    gflags = None
    rem = deliver_remote_packed(s_flat, params.rem_flat, params.rem_w,
                                stencil=build_stencil(cfg))
    with jax.named_scope("dpsnn.neuron"):
        if cfg.stdp:
            out = ops.fused_step(
                cfg.neuron, lif0.v, lif0.c, lif0.refrac, s_loc,
                params.w_local, rem, ext,
                stdp0.x_pre, stdp0.x_post, scfg=cfg.stdp_cfg, gcfg=gcfg)
            v, c, refrac, spikes, x_pre, x_post = out[:6]
            if gcfg is not None:
                gflags = out[6]
            stdp1 = stdp0._replace(x_pre=x_pre, x_post=x_post)
        else:
            out = ops.fused_step(
                cfg.neuron, lif0.v, lif0.c, lif0.refrac, s_loc,
                params.w_local, rem, ext, gcfg=gcfg)
            v, c, refrac, spikes = out[:4]
            if gcfg is not None:
                gflags = out[4]
            stdp1 = stdp0
    return LIFState(v=v, c=c, refrac=refrac), spikes, stdp1, gflags


def unfused_stage(cfg: DPSNNConfig, params: NetworkParams, lif0: LIFState,
                  s_loc: jax.Array, s_flat: jax.Array, ext: jax.Array,
                  impl: str):
    """Delivery and the neuron update as separate stages (``impl`` 'ref'
    or 'pallas'), shared by both loops. Returns ``(lif', spikes)``."""
    deliver_local, deliver_remote = _delivery_fns(impl, build_stencil(cfg))
    with jax.named_scope("dpsnn.neuron"):
        local = deliver_local(s_loc, params.w_local)
    remote = deliver_remote(s_flat, params.rem_flat, params.rem_w)
    with jax.named_scope("dpsnn.neuron"):
        return lif_sfa_step(cfg.neuron, lif0, local + remote + ext)


def make_step_fn(cfg: DPSNNConfig, *, impl: str = "ref"):
    """Closure-capturing step fn suitable for jit / scan."""
    stencil = build_stencil(cfg)
    grid_hw = (cfg.grid_h, cfg.grid_w)
    col_ids = jnp.arange(cfg.n_columns, dtype=jnp.int32)

    def step(params: NetworkParams, state: NetworkState) -> NetworkState:
        return step_single(cfg, params, state, stencil=stencil,
                           grid_hw=grid_hw, col_ids=col_ids, impl=impl)

    return step
