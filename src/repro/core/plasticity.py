"""STDP (spike-timing dependent plasticity).

DPSNN implements STDP as a first-class feature; the 2015 scaling paper
*disables* it for the reported measurements (CORTICONIC did not need it).
We implement it the same way: available, off by default
(``DPSNNConfig.stdp``), wired through both the single-shard loop
(core/simulation.py) and the distributed loop (core/exchange.py) — see
DESIGN.md §Plasticity for the exchange semantics.

TPU form: exponential pre/post traces; the dense local update is a pair of
per-column **outer products** (MXU-shaped; ``impl='pallas'`` runs them as
a block-event-skipping kernel, kernels/stdp_update.py), the remote ELL
update is a gather of pre-traces through the same neighbour table used for
delivery. Excitatory→* synapses only (standard cortical STDP); inhibitory
weights are left untouched. Weights are clipped to [0, w_max] and absent
synapses (exact zeros in the dense block) stay absent via the mask.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import DPSNNConfig, STDPConfig  # noqa: F401 (re-export)
from repro.core import network as net
from repro.core.connectivity import StencilSpec
from repro.core.network import NetworkParams


class STDPState(NamedTuple):
    x_pre: jax.Array    # (C, N) presynaptic traces
    x_post: jax.Array   # (C, N) postsynaptic traces


def init_stdp(n_columns: int, n: int, dtype=jnp.float32) -> STDPState:
    z = jnp.zeros((n_columns, n), dtype)
    return STDPState(x_pre=z, x_post=z)


def pre_trace_table(x_pre: jax.Array, stencil: StencilSpec,
                    grid_hw: tuple[int, int]) -> jax.Array:
    """(C, N) pre-trace frame -> (C, O*N) neighbour pre-trace table.

    Mirrors :func:`repro.core.network.neighbour_table_single` (same
    (dy, dx) shift convention, zero boundary at the sheet edge) but with a
    **uniform one-step lag** instead of per-offset axonal delays: callers
    pass the previous step's traces, which is exactly what one halo
    exchange can deliver in the distributed loop (DESIGN.md §Plasticity).
    The distributed path slices the identical values out of its
    halo-extended trace frame, so both paths gather bitwise-equal tables.
    """
    gh, gw = grid_hw
    c, n = x_pre.shape
    r = stencil.radius
    with jax.named_scope("dpsnn.stdp"):
        g = jnp.pad(x_pre.reshape(gh, gw, n), ((r, r), (r, r), (0, 0)))
        per_offset = [
            net.offset_slice(g, dy, dx, r, gh, gw, n).reshape(c, n)
            for (dy, dx, _k, _delay, _p) in stencil.offsets
        ]
        return jnp.stack(per_offset, axis=1).reshape(
            c, stencil.n_offsets * n)


def stdp_update(cfg: DPSNNConfig, scfg: STDPConfig, params: NetworkParams,
                st: STDPState, spikes: jax.Array, is_inh: jax.Array,
                pre_trace_table: jax.Array | None = None,
                rem_flat: jax.Array | None = None,
                impl: str = "ref",
                new_traces: STDPState | None = None):
    """One STDP step given this step's spikes (C, N).

    ``pre_trace_table`` is the (C, O*N) neighbour pre-trace table for the
    remote update (None => local-only update, used while halos are in
    flight in the distributed loop). With ``new_traces`` the trace
    decay+bump is NOT recomputed: the fused megakernel
    (``impl='pallas_fused'``, kernels/fused_step.py) already advanced the
    traces in VMEM alongside the neuron update and passes them through
    here, bitwise-identical to the recomputation.
    Returns (new_params, new_stdp_state).
    """
    with jax.named_scope("dpsnn.stdp"):
        dt = cfg.neuron.dt_ms
        if new_traces is not None:
            x_pre, x_post = new_traces.x_pre, new_traces.x_post
        else:
            dp = jnp.exp(-dt / scfg.tau_plus_ms).astype(st.x_pre.dtype)
            dm = jnp.exp(-dt / scfg.tau_minus_ms).astype(st.x_pre.dtype)
            x_pre = st.x_pre * dp + spikes
            x_post = st.x_post * dm + spikes

        exc_src = (~is_inh).astype(spikes.dtype)          # (N,)
        w_max = scfg.w_max_factor * cfg.conn.j_exc

        # --- local dense blocks: two outer products per column ---
        # single source of truth for the dense rule: kernels/ref.py oracle
        # (the pallas kernel is tested bitwise-equal against it)
        x_pre_exc = x_pre * exc_src[None, :]
        spk_exc = spikes * exc_src[None, :]
        kw = dict(a_plus=scfg.a_plus, a_minus=scfg.a_minus, lr=scfg.lr,
                  w_max=w_max)
        if impl in ("pallas", "pallas_fused"):
            # the dense weight write is a second full pass over (C, N, N) —
            # it stays the standalone block-event-skipping kernel even under
            # the fused step (the megakernel's weight tiles are consumed
            # before this step's spikes exist, DESIGN.md §Fusion)
            from repro.kernels import ops
            w_local = ops.stdp_dense_update(
                params.w_local, x_pre_exc, spk_exc, spikes, x_post, **kw)
        elif impl == "ref":
            from repro.kernels import ref as kref
            w_local = kref.stdp_dense_update_ref(
                params.w_local, x_pre_exc, spk_exc, spikes, x_post, **kw)
        else:
            raise ValueError(f"unknown stdp impl {impl!r}")

        rem_w = params.rem_w
        if pre_trace_table is not None and rem_flat is not None:
            c, n, k = rem_flat.shape
            pre_tr = jnp.take_along_axis(
                pre_trace_table, rem_flat.reshape(c, n * k), axis=1
            ).reshape(c, n, k)
            # remote post side: this column's own spikes / traces
            dw_r = scfg.lr * (
                scfg.a_plus * pre_tr * spikes[:, :, None]
                # depression for remote needs the *pre spike* table; the trace
                # table at tau->0 approximates it — we reuse pre_tr with the
                # post-trace, the standard pair-based asymmetry:
                - scfg.a_minus * pre_tr * x_post[:, :, None] * 0.5
            )
            rem_w = jnp.where(
                params.rem_w > 0,
                jnp.clip(params.rem_w + dw_r, 0.0, w_max),
                params.rem_w,
            )

        new_params = params._replace(w_local=w_local, rem_w=rem_w)
        return new_params, STDPState(x_pre=x_pre, x_post=x_post)
