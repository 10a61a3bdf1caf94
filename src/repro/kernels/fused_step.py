"""Fused column-step megakernel (Pallas TPU kernel, DESIGN.md §Fusion).

One ``pallas_call`` executes the on-shard neuron pipeline of a simulation
step — block-event-skipped local synapse matmul, the closing sum with the
remote and external currents, LIF+SFA integrate-and-fire, and the STDP
pre/post trace decay+update — where the unfused ``impl='pallas'`` path
issues two kernels (``synapse_matmul``, ``lif_step``) plus the trace
update in jnp, each round-tripping the same ``(C, N)`` membrane/trace
state and spike slices through HBM.

Remote ELL delivery is *not* in the kernel: the caller computes the
remote currents with its own kernel over bit-packed spike words
(``kernels/ell_deliver.py``, through ``core/network.deliver_remote_packed``)
and passes them in. The kernel then adds them with the very expression
the reference uses.

Layout. Every per-column vector is passed as ``(C, 1, N)`` so that its
last two block dims equal the array's (the TPU (8, 128) block rule holds
for any N); the weights are ``(C, N, NW)`` with one whole column per block
(NW = N, or N rounded up to whole lanes for a lane-padded table).
The kernel pads nothing itself. Grid ``(C / BLK_C,)`` over column tiles; per
tile the kernel

1. accumulates the local delivery ``spikes @ w_local`` in 128-row source
   slices into a VMEM f32 scratch accumulator, **skipping** the MXU work
   of every (column, slice) whose spikes are all zero — at the paper's
   ~5 Hz about half the 128-neuron slices of a column are silent in any
   step, then
2. adds the remote and external currents and runs the LIF+SFA threshold
   dynamics and (under STDP) the exponential trace decay+bump, all while
   membrane potentials, adaptation and traces stay resident in VMEM.

``BLK_C`` (columns per tile) is the largest divisor of C whose weight
blocks fit ``VMEM_TILE_BUDGET``: 1 at the paper's column size (N=1240, a
6.15 MB weight block), up to ``MAX_BLK_C`` for test geometries where a
column is small and per-step overhead would otherwise dominate. Being a
divisor, it never pads the column axis either.

Numerics contract (tests/test_fused_step.py asserts all of it): every
stage replicates the ``ref`` expressions operation-for-operation (same
order, same dtypes, decay constants computed with the identical jnp
calls, the exp-Euler gain pre-folded exactly as XLA constant-folds it in
the ref path), so for column sizes within one source slice (N <= 128 —
every parity-test geometry) **spikes and every event-derived quantity
(spike history, counts, adaptation, refractory state, STDP traces and
plastic weights) are bitwise-equal** to the ref path over hundreds of
steps. Membrane potentials may differ in the final ulp (XLA contracts the
sub-threshold multiply-add chain with FMAs whose grouping depends on
fusion context). Beyond one source slice the local-matmul partial sums
accumulate slice-by-slice and currents match allclose — the contract the
unfused Pallas kernels have. The local matmul contracts at f32 precision
(``Precision.HIGHEST``) on the chip as in the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import GuardConfig, NeuronConfig, STDPConfig

BLK_S = 128            # source slice (MXU contraction dim)
MAX_BLK_C = 16         # column-tile cap
VMEM_TILE_BUDGET = 4 << 20   # soft budget for one tile's weight blocks
# Scoped-VMEM headroom above the double-buffered weight blocks: the ~14
# (BLK_C, 1, N) state/trace vectors (each padded to 8 sublanes in VMEM,
# double-buffered) plus the accumulator take well under 2 MiB at N=1240.
VMEM_HEADROOM = 4 << 20


def column_block(nc: int, block_bytes: int) -> int:
    """Columns per grid tile: the largest divisor of ``nc`` (capped at
    ``MAX_BLK_C``) whose weight blocks of ``block_bytes`` each fit the
    soft VMEM budget; at least 1, which is what the paper's N=1240
    columns get."""
    fit = max(1, min(MAX_BLK_C, VMEM_TILE_BUDGET // block_bytes))
    return max(d for d in range(1, fit + 1) if nc % d == 0)


def vmem_limit(blk_c: int, block_bytes: int) -> int:
    """Scoped-VMEM limit for one tile: the weight blocks double-buffered
    plus ``VMEM_HEADROOM``. At N=1240 in f32 this is 16.3 MB, just above
    the 16 MiB v5e default, which is why the kernel states it explicitly."""
    return 2 * blk_c * block_bytes + VMEM_HEADROOM


def _make_kernel(ncfg: NeuronConfig, n: int, nw: int, with_stdp: bool,
                 guard: GuardConfig | None = None, blk_c: int = 1):
    # Python-float constants close over the kernel exactly as they appear
    # in core/neuron.lif_sfa_step (weak-typed f32 promotion, identical
    # grouping) — bitwise parity depends on it.
    g_c, v_rest, v_reset = ncfg.g_c, ncfg.v_rest, ncfg.v_reset
    v_thr, alpha_c = ncfg.v_threshold, ncfg.alpha_c
    arp_steps = round(ncfg.tau_arp_ms / ncfg.dt_ms)
    slices = [(s0, min(BLK_S, n - s0)) for s0 in range(0, n, BLK_S)]

    def kernel(par_ref, sloc_ref, w_ref, rem_ref, ext_ref,
               v_ref, c_ref, r_ref, *rest):
        rest = list(rest)
        acc_ref = rest.pop()              # VMEM scratch accumulator
        go_ref = rest.pop() if guard is not None else None
        if with_stdp:
            (xpre_ref, xpost_ref,
             vo_ref, co_ref, ro_ref, so_ref, xpo_ref, xqo_ref) = rest
        else:
            vo_ref, co_ref, ro_ref, so_ref = rest

        acc_ref[...] = jnp.zeros_like(acc_ref)
        # f32 weights contract at f32 precision; bf16 weights times 0/1
        # spikes are exact products at the MXU's native precision (Mosaic
        # refuses an fp32 contraction of bf16 operands)
        precision = (jax.lax.Precision.HIGHEST if w_ref.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        # block-event skip: a silent (column, source slice) contributes
        # nothing, so its MXU work is skipped
        for j in range(blk_c):
            for s0, sz in slices:
                s = sloc_ref[j, :, s0:s0 + sz]            # (1, sz)

                @pl.when(jnp.max(jnp.abs(s)) > 0)
                def _acc():
                    acc_ref[j] += jax.lax.dot_general(
                        s.astype(w_ref.dtype), w_ref[j, s0:s0 + sz, :],
                        (((1,), (0,)), ((), ())),
                        precision=precision,
                        preferred_element_type=jnp.float32,
                    )                                      # (1, NW)

        decay_v, decay_c, gain = par_ref[0], par_ref[1], par_ref[2]
        dtype = v_ref.dtype
        # local delivery closes: f32 accumulator -> state dtype
        # (deliver_local_ref's single einsum->astype cast), then the
        # remote and external currents in the ref's order
        acc = acc_ref[...] if nw == n else acc_ref[:, :, :n]
        cur = acc.astype(dtype)
        cur = cur + rem_ref[...]
        cur = cur + ext_ref[...]

        # LIF+SFA — operation-for-operation lif_sfa_step
        v0, c0, refrac = v_ref[...], c_ref[...], r_ref[...]
        drive = cur - g_c * c0
        v1 = v_rest + (v0 - v_rest) * decay_v + drive * gain
        refractory = refrac > 0
        v1 = jnp.where(refractory, v_reset, v1)
        spikes_b = (v1 >= v_thr) & (~refractory)
        spikes = spikes_b.astype(dtype)

        v_out = jnp.where(spikes_b, v_reset, v1)
        vo_ref[...] = v_out
        co_ref[...] = c0 * decay_c + alpha_c * spikes
        ro_ref[...] = jnp.where(spikes_b, jnp.int32(arp_steps),
                                jnp.maximum(refrac - 1, 0))
        so_ref[...] = spikes

        if with_stdp:
            # exponential trace decay + spike bump (plasticity.py's
            # x' = x * exp(-dt/tau) + spikes, same expressions)
            dp, dm = par_ref[3], par_ref[4]
            xpo_ref[...] = xpre_ref[...] * dp + spikes
            xqo_ref[...] = xpost_ref[...] * dm + spikes

        if guard is not None:
            # fused guard reduction: per-column NaN/bounds bitflags over
            # the column's N neurons, broadcast along the 128 lanes of
            # the flag block
            bad_nan = (~jnp.isfinite(v_out)).astype(jnp.float32)
            bad_rng = ((v_out < guard.v_floor)
                       | (v_out > guard.v_ceil)).astype(jnp.float32)
            nan_f = jnp.max(bad_nan, axis=2, keepdims=True) > 0
            rng_f = jnp.max(bad_rng, axis=2, keepdims=True) > 0
            flags = (nan_f.astype(jnp.int32)
                     | (rng_f.astype(jnp.int32) << 1))    # (BLK_C, 1, 1)
            go_ref[...] = jnp.broadcast_to(flags, go_ref.shape)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("ncfg", "scfg", "gcfg", "interpret"))
def fused_step(ncfg: NeuronConfig, v, c, refrac, s_loc, w_local, rem_cur,
               ext, x_pre=None, x_post=None, *,
               scfg: STDPConfig | None = None,
               gcfg: GuardConfig | None = None,
               interpret: bool):
    """One fused on-shard step over all columns of a shard.

    Inputs (C = columns on this shard, N = neurons/column):

    * ``v, c, refrac``       (C, N) LIF state
    * ``s_loc``              (C, N) delayed local spike frame
    * ``w_local``            (C, N, NW) intra-column weights [src, tgt];
                             targets past N (a lane-padded table,
                             ``network.lane_width``) are absent
    * ``rem_cur``            (C, N) remote ELL currents
                             (``network.deliver_remote_packed``)
    * ``ext``                (C, N) external drive currents
    * ``x_pre, x_post``      (C, N) STDP traces (with ``scfg``)

    Returns ``(v', c', refrac', spikes)``, with ``scfg`` appending
    ``(x_pre', x_post')``, and ``gcfg`` appending a ``(C,)`` int32
    per-column guard bitflag vector (bit 0 = non-finite v', bit 1 =
    v' outside guard bounds) reduced inside the megakernel epilogue —
    the integrity guard costs no extra pass over the membrane state.
    """
    with_stdp = scfg is not None
    with_guard = gcfg is not None
    nc, n = v.shape
    dtype = v.dtype
    dt = ncfg.dt_ms
    # decay constants via the IDENTICAL jnp expressions the unfused path
    # evaluates (lif_sfa_step / plasticity.stdp_update) — a math.exp
    # double rounded to f32 can differ in the last ulp
    decay_v = jnp.exp(-dt / ncfg.tau_m_ms).astype(dtype)
    decay_c = jnp.exp(-dt / ncfg.tau_c_ms).astype(dtype)
    # lif_sfa_step writes `drive * (1.0 - decay_v) * (tau_m/dt)`; under
    # jit XLA constant-folds the two trailing constants into one gain
    # factor, so the kernel must receive the SAME pre-folded product to
    # stay bitwise-equal (multiplying at runtime re-associates)
    gain = (1.0 - decay_v) * (ncfg.tau_m_ms / dt)
    if with_stdp:
        dp = jnp.exp(-dt / scfg.tau_plus_ms).astype(dtype)
        dm = jnp.exp(-dt / scfg.tau_minus_ms).astype(dtype)
        params = jnp.stack([decay_v, decay_c, gain, dp, dm])
    else:
        params = jnp.stack([decay_v, decay_c, gain])

    nw = w_local.shape[2]
    block_bytes = n * nw * jnp.dtype(w_local.dtype).itemsize
    blk_c = column_block(nc, block_bytes)
    vecs = [v, c, refrac]
    if with_stdp:
        vecs += [x_pre, x_post]
    vspec = pl.BlockSpec((blk_c, 1, n), lambda ci: (ci, 0, 0))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # params
        vspec,                                                 # s_loc
        pl.BlockSpec((blk_c, n, nw), lambda ci: (ci, 0, 0)),   # w_local
        vspec, vspec,                                          # rem, ext
    ] + [vspec] * len(vecs)
    args = [params, s_loc[:, None], w_local, rem_cur[:, None],
            ext[:, None]] + [x[:, None] for x in vecs]

    out_shape = [
        jax.ShapeDtypeStruct((nc, 1, n), dtype),        # v'
        jax.ShapeDtypeStruct((nc, 1, n), dtype),        # c'
        jax.ShapeDtypeStruct((nc, 1, n), jnp.int32),    # refrac'
        jax.ShapeDtypeStruct((nc, 1, n), dtype),        # spikes
    ]
    if with_stdp:
        out_shape += [jax.ShapeDtypeStruct((nc, 1, n), dtype)] * 2
    out_specs = [vspec] * len(out_shape)
    if with_guard:
        out_shape.append(jax.ShapeDtypeStruct((nc, 1, 128), jnp.int32))
        out_specs.append(pl.BlockSpec((blk_c, 1, 128),
                                      lambda ci: (ci, 0, 0)))

    out = pl.pallas_call(
        _make_kernel(ncfg, n, nw, with_stdp,
                     guard=gcfg if with_guard else None, blk_c=blk_c),
        grid=(nc // blk_c,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((blk_c, 1, nw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(blk_c, block_bytes)),
        interpret=interpret,
    )(*args)
    if with_guard:
        return tuple(o[:, 0] for o in out[:-1]) + (out[-1][:, 0, 0],)
    return tuple(o[:, 0] for o in out)
