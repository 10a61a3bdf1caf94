"""Plain reference of the simulator's step, and the comparison that
decides a run's ``correct``.

The reference imports nothing of the program. It reads the network's
numbers from the configuration file (``Cell.network``) and regenerates
what the program generates from them, with the same random streams: the
dense intra-column weights, the remote fan-in lists and their weights,
and the Poisson drive. The weights are stored as the configuration says
(``weight_dtype``): each is rounded once from float32 to that type, as the
program rounds it, so a network of bfloat16 synapses is compared with its
own weights and not with the float32 ones they were rounded from. It is
written for clarity and computes in float32, one block of target columns
at a time so that it fits beside the program's state. Under STDP the
weights a call starts from are the program's own; the reference does not
model a rounding after each update, so a plastic network is compared only
at float32 weights.

What it takes from the program is the state a compared call starts from
(membrane potentials, adaptation, refractory counters, STDP traces and
plastic weights) and the spikes the program emitted (its history ring),
as the reference of a served model takes the served tokens: every step is
recomputed from the program's own inputs, so one spike that rounding
flips does not spread through the network and hide the rest. Numbers
compared (each against its own limit, ``limits/<workload>.json``):

* ``v_gap_mV``, ``c_gap``: the largest gap of the membrane potential and of
  the adaptation variable after the call, over neurons whose spikes agree
  at every compared step;
* ``spike_flips``: (neuron, step) pairs whose spike differs;
* ``event_gap``: the program's synaptic-event counter increment against the
  events of the reference's own spikes and drive;
* under STDP ``trace_gap`` (pre- and post-synaptic traces), ``w_gap`` (every
  local and remote weight after the update, which runs on the program's
  spikes) and ``w_fixed_gap`` (weights the rule must never move: absent
  and inhibitory synapses, against their regenerated values).

``control_chunk`` is the control: the same reference computed in bfloat16
(state, weights and arithmetic), put in the program's place.
"""
from __future__ import annotations

import functools
import json
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LO_BITS = 20            # the program's counters are int32 pairs [hi, lo]


# ---------------------------------------------------------------------------
# The network's constants, from the configuration's numbers
# ---------------------------------------------------------------------------

class Stencil(NamedTuple):
    offsets: tuple          # ((dy, dx, K, delay), ...) in slot order
    k_total: int
    slot_offset: np.ndarray  # (K,) offset index of each fan-in slot
    max_delay: int


def stencil(net: dict) -> Stencil:
    """Active lateral offsets: probability by the lateral profile, cut off
    below ``cutoff``, a fixed fan-in ``K = max(1, round(p * N))`` and an
    axonal delay of ``min_delay + round(delay_per_step * distance)``."""
    c = net["conn"]
    n = net["neurons_per_column"]
    prof = c["lateral_profile"]
    entries = []
    r = c["radius"]
    for dy in range(-r, r + 1):
        for dx in range(-r, r + 1):
            if dy == 0 and dx == 0:
                continue
            p = 0.0
            if prof in ("gaussian", "gauss_exp"):
                p += c["amp_lateral"] * math.exp(
                    -(dy * dy + dx * dx) / (2.0 * c["alpha_steps"] ** 2))
            if prof in ("exponential", "gauss_exp"):
                p += c["amp_exp"] * math.exp(
                    -math.hypot(dy, dx) / c["lambda_steps"])
            if p >= c["cutoff"]:
                k = max(1, round(p * n))
                delay = c["min_delay_steps"] + int(
                    round(c["delay_per_step"] * math.hypot(dy, dx)))
                entries.append((dy, dx, k, delay))
    slot_offset = np.concatenate(
        [np.full(k, i, np.int32) for i, (_, _, k, _) in enumerate(entries)])
    max_delay = max([c["min_delay_steps"]] + [e[3] for e in entries])
    return Stencil(tuple(entries), int(slot_offset.size), slot_offset,
                   int(max_delay))


def n_excitatory(net: dict) -> int:
    return round(net["conn"]["exc_fraction"] * net["neurons_per_column"])


# ---------------------------------------------------------------------------
# Regenerated synapses and drive (the program's random streams)
# ---------------------------------------------------------------------------

def _magnitude(net, key, shape, inh_src, wdtype):
    c = net["conn"]
    jitter = 1.0 + c["weight_cv"] * jax.random.truncated_normal(
        key, -2.0, 2.0, shape)
    mag = jnp.where(inh_src, -c["g_balance"] * c["j_exc"], c["j_exc"])
    return (mag * jitter).astype(wdtype)


def local_weights(net: dict, col, wdtype=jnp.float32):
    """(N, N) [source, target] weights of one column: Bernoulli(p_local)
    without autapses, sign by the source's type, jittered magnitude."""
    n = net["neurons_per_column"]
    key = jax.random.fold_in(jax.random.PRNGKey(net["seed"]), col)
    k_mask, k_w = jax.random.split(key)
    mask = jax.random.bernoulli(k_mask, net["conn"]["p_local"], (n, n))
    mask = mask & ~jnp.eye(n, dtype=bool)
    inh = (jnp.arange(n) >= n_excitatory(net))[:, None]
    w = _magnitude(net, k_w, (n, n), inh, wdtype)
    return jnp.where(mask, w, 0).astype(wdtype)


def remote_synapses(net: dict, st: Stencil, col, wdtype=jnp.float32):
    """(N, K) source neuron and weight of each remote fan-in slot of one
    target column; slot ``k`` comes from offset ``st.slot_offset[k]``."""
    n = net["neurons_per_column"]
    key = jax.random.fold_in(
        jax.random.PRNGKey(net["seed"]) + jnp.uint32(0x9E3779B9), col)
    k_idx, k_w = jax.random.split(key)
    idx = jax.random.randint(k_idx, (n, st.k_total), 0, n, dtype=jnp.int32)
    inh = idx >= n_excitatory(net)
    return idx, _magnitude(net, k_w, (n, st.k_total), inh, wdtype)


def drive_counts(net: dict, t, cols):
    """(B, N) Poisson arrivals of step ``t`` on the external synapses."""
    lam = net["c_ext"] * net["nu_ext_hz"] * net["neuron"]["dt_ms"] * 1e-3
    base = jax.random.fold_in(jax.random.PRNGKey(net["seed"] + 0xE57), t)
    n = net["neurons_per_column"]
    return jax.vmap(lambda c: jax.random.poisson(
        jax.random.fold_in(base, c), lam, (n,)))(cols)


# ---------------------------------------------------------------------------
# Snapshots of the program's state, in global column order
# ---------------------------------------------------------------------------

class Snapshot(NamedTuple):
    """What the comparison reads of one program state. Arrays are (C, N)
    in global column order; ``frames[i]`` holds the spikes of step
    ``frame0 + i``."""
    t: int
    v: jax.Array
    c: jax.Array
    refrac: jax.Array
    frames: jax.Array
    frame0: int
    spikes: int                     # exact counter values
    events: int
    x_pre: Optional[jax.Array] = None
    x_post: Optional[jax.Array] = None
    w_local: Optional[jax.Array] = None   # (C, N, N)
    rem_w: Optional[jax.Array] = None     # (C, N, K)


def counter_value(pair) -> int:
    """Exact integer of one counter ``[hi, lo]`` or the sum of a stack."""
    a = np.asarray(pair, np.int64).reshape(-1, 2)
    return int((a[:, 0] << LO_BITS).sum() + a[:, 1].sum())


def chunk_frames(a: Snapshot, b: Snapshot, max_delay: int, k: int):
    """Spike frames of steps ``a.t - max_delay`` .. ``a.t + k - 1``: every
    input and output of the ``k`` steps after ``a``, as ``(frames,
    first)``. Steps before ``a.t`` come from ``a``'s history, later ones
    from ``b``'s; a step that ``b`` does not hold (a call that did not
    advance its state) emitted no spikes."""
    first = a.t - max_delay
    zero = np.zeros(a.v.shape, np.float32)
    out = []
    for step in range(first, a.t + k):
        src = a if step < a.t else b
        i = step - src.frame0
        if 0 <= i < src.frames.shape[0] and step < src.t:
            out.append(np.asarray(jax.device_get(src.frames[i]), np.float32))
        elif src is b:
            out.append(zero)
        else:
            raise ValueError(f"step {step} is not in the history of the "
                             f"state at t={a.t}")
    return np.stack(out), first


# ---------------------------------------------------------------------------
# One step of a block of target columns
# ---------------------------------------------------------------------------

def _neighbour_frame(frame, rows, cols, dy, dx, gh, gw):
    """(C, N) global frame -> (B, N): for each target column, the frame of
    the source column at offset (dy, dx); zero past the sheet's edge."""
    y, x = rows + dy, cols + dx
    inside = (y >= 0) & (y < gh) & (x >= 0) & (x < gw)
    src = jnp.clip(y, 0, gh - 1) * gw + jnp.clip(x, 0, gw - 1)
    return jnp.where(inside[:, None], frame[src], 0)


def _table(frames_at, rows, cols, st, net):
    """(B, O*N) table of what each offset delivers, offset-major."""
    gh, gw = net["grid_h"], net["grid_w"]
    per = [_neighbour_frame(frames_at(delay), rows, cols, dy, dx, gh, gw)
           for (dy, dx, _k, delay) in st.offsets]
    return jnp.stack(per, axis=1).reshape(rows.shape[0], -1)


def _lif(net, v, c, refrac, cur):
    nc = net["neuron"]
    dt = nc["dt_ms"]
    decay_v = jnp.exp(-dt / nc["tau_m_ms"]).astype(v.dtype)
    decay_c = jnp.exp(-dt / nc["tau_c_ms"]).astype(v.dtype)
    drive = cur - nc["g_c"] * c
    v1 = nc["v_rest"] + (v - nc["v_rest"]) * decay_v + drive * (
        1.0 - decay_v) * (nc["tau_m_ms"] / dt)
    refractory = refrac > 0
    v1 = jnp.where(refractory, nc["v_reset"], v1)
    fired = (v1 >= nc["v_threshold"]) & ~refractory
    spk = fired.astype(v.dtype)
    v2 = jnp.where(fired, nc["v_reset"], v1)
    c2 = c * decay_c + nc["alpha_c"] * spk
    r2 = jnp.where(fired, jnp.int32(round(nc["tau_arp_ms"] / dt)),
                   jnp.maximum(refrac - 1, 0))
    return v2, c2, r2, spk


def _stdp(net, st, rows, cols, x_pre0_all, x_pre0, x_post0, spk, wl, rw,
          rem_src, exc):
    """One STDP update of a block: traces advanced by this step's spikes,
    dense local pair rule, remote rule through the previous step's
    pre-trace table (one-step lag)."""
    s = net["stdp_cfg"]
    dt = net["neuron"]["dt_ms"]
    dp = jnp.exp(-dt / s["tau_plus_ms"]).astype(x_pre0.dtype)
    dm = jnp.exp(-dt / s["tau_minus_ms"]).astype(x_pre0.dtype)
    x_pre = x_pre0 * dp + spk
    x_post = x_post0 * dm + spk
    w_max = s["w_max_factor"] * net["conn"]["j_exc"]
    pot = jnp.einsum("cs,ct->cst", x_pre * exc, spk, precision=HIGHEST)
    dep = jnp.einsum("cs,ct->cst", spk * exc, x_post, precision=HIGHEST)
    dw = s["lr"] * (s["a_plus"] * pot - s["a_minus"] * dep)
    wl = jnp.where(wl > 0, jnp.clip(wl + dw, 0.0, w_max), wl)
    table = _table(lambda _d: x_pre0_all, rows, cols, st, net)
    b, n, k = rem_src.shape
    pre_tr = jnp.take_along_axis(table, rem_src.reshape(b, n * k),
                                 axis=1).reshape(b, n, k)
    dw_r = s["lr"] * (s["a_plus"] * pre_tr * spk[:, :, None]
                      - s["a_minus"] * pre_tr * x_post[:, :, None] * 0.5)
    rw = jnp.where(rw > 0, jnp.clip(rw + dw_r, 0.0, w_max), rw)
    return x_pre, x_post, wl, rw


def _block_synapses(net, st, cols, wdtype):
    wl0 = jax.vmap(lambda c: local_weights(net, c))(cols)
    idx, rw0 = jax.vmap(lambda c: remote_synapses(net, st, c))(cols)
    n = net["neurons_per_column"]
    rem_src = jnp.asarray(st.slot_offset)[None, None, :] * n + idx
    outdeg = (wl0 != 0).sum(axis=-1).astype(jnp.int32)
    return wl0.astype(wdtype), rem_src, rw0.astype(wdtype), outdeg, wl0, rw0


def _step(net, st, ids, t, frames_at, v, c, r, wl, rw, rem_src, outdeg):
    """One step of a block from the given spike history: currents (local
    delivery at f32 precision, remote fan-in lists, drive), then LIF+SFA.
    Returns the new state, the spikes and the synaptic events of the step
    (each spike to its realized local out-degree plus K remote targets,
    each drive arrival once)."""
    s_loc = frames_at(net["conn"]["min_delay_steps"])[ids].astype(v.dtype)
    local = jnp.einsum("cs,cst->ct", s_loc, wl, precision=HIGHEST,
                       preferred_element_type=jnp.float32).astype(v.dtype)
    gw = net["grid_w"]
    table = _table(frames_at, ids // gw, ids % gw, st, net).astype(v.dtype)
    b, n, k = rem_src.shape
    gathered = jnp.take_along_axis(table, rem_src.reshape(b, n * k),
                                   axis=1).reshape(b, n, k)
    remote = (gathered * rw).sum(axis=-1).astype(v.dtype)
    counts = drive_counts(net, t, ids)
    ext = counts.astype(v.dtype) * net["conn"]["j_ext"]
    v, c, r, spk = _lif(net, v, c, r, local + remote + ext)
    events = ((spk.astype(jnp.int32) * (outdeg + st.k_total)).sum()
              + counts.sum())
    return v, c, r, spk, events


# ---------------------------------------------------------------------------
# The comparison (program's call against the reference)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("net_key", "k", "block"))
def _compare_block(net_key, k, block, col0, t_a, frames, frame0, v_a, c_a,
                   r_a, v_b, c_b, plastic):
    net = json.loads(net_key)
    st = stencil(net)
    gw = net["grid_w"]
    cols = col0 + jnp.arange(block, dtype=jnp.int32)
    rows, xs = cols // gw, cols % gw
    take = lambda x: jax.lax.dynamic_slice_in_dim(x, col0, block, axis=0)
    # the weights rounded once to the configuration's weight_dtype, as the
    # program stores them; the arithmetic stays float32
    wl, rem_src, rw, outdeg, wl0, rw0 = _block_synapses(
        net, st, cols, jnp.dtype(net["weight_dtype"]))
    wl, rw = wl.astype(jnp.float32), rw.astype(jnp.float32)
    v, c, r = take(v_a), take(c_a), take(r_a)
    stdp = plastic is not None
    if stdp:
        x_pre_all, x_post_all, wl_a, rw_a, x_pre_b, x_post_b, wl_b, rw_b = \
            plastic
        x_pre, x_post = take(x_pre_all), take(x_post_all)
        wl, rw = take(wl_a), take(rw_a)
    flips = jnp.int32(0)
    agree = jnp.ones(v.shape, bool)
    events = jnp.int32(0)
    for j in range(k):
        t = t_a + j
        at = lambda d, t=t: jax.lax.dynamic_index_in_dim(
            frames, t - d - frame0, keepdims=False)
        mine = at(0)[cols]
        v, c, r, spk, ev = _step(net, st, cols, t, at, v, c, r, wl, rw,
                                 rem_src, outdeg)
        events = events + ev
        differ = spk != mine
        flips = flips + differ.sum().astype(jnp.int32)
        agree = agree & ~differ
        if stdp:
            x_pre, x_post, wl, rw = _stdp(
                net, st, rows, xs, x_pre_all, x_pre, x_post, mine, wl, rw,
                rem_src, (jnp.arange(net["neurons_per_column"])
                          < n_excitatory(net)).astype(v.dtype))
    gap = lambda x, y, m: jnp.max(jnp.where(m, jnp.abs(x - y), 0.0))
    out = {"v_gap_mV": gap(v, take(v_b), agree),
           "c_gap": gap(c, take(c_b), agree),
           "spike_flips": flips, "events": events}
    if stdp:
        everywhere = jnp.ones(v.shape, bool)
        out["trace_gap"] = jnp.maximum(gap(x_pre, take(x_pre_b), everywhere),
                                       gap(x_post, take(x_post_b), everywhere))
        wl_b, rw_b = take(wl_b).astype(jnp.float32), take(rw_b).astype(
            jnp.float32)
        out["w_gap"] = jnp.maximum(jnp.abs(wl - wl_b).max(),
                                   jnp.abs(rw - rw_b).max())
        out["w_fixed_gap"] = jnp.maximum(
            jnp.max(jnp.where(wl0 <= 0, jnp.abs(wl_b - wl0), 0.0)),
            jnp.max(jnp.where(rw0 <= 0, jnp.abs(rw_b - rw0), 0.0)))
    return out


def net_key(net: dict) -> str:
    """The network's numbers as a hashable static jit argument."""
    return json.dumps(net, sort_keys=True)


BLOCK_BYTES = 512 << 20     # synapses of one reference block


def block_size(n_columns: int, neurons: int, k_total: int) -> int:
    """Columns per reference block: the largest divisor of the column count
    whose synapses (generated and compared) fit ``budget`` bytes."""
    per = 4 * neurons * (4 * neurons + 4 * k_total)
    fit = max(1, BLOCK_BYTES // per)
    return max(d for d in range(1, min(fit, n_columns) + 1)
               if n_columns % d == 0)


def compare(net: dict, a: Snapshot, b: Snapshot, devices, k: int) -> dict:
    """Every compared number of the call of ``k`` steps from ``a`` to
    ``b``."""
    stdp = net["stdp"]
    if stdp and net["weight_dtype"] != "float32":
        raise ValueError(
            f"weight_dtype {net['weight_dtype']!r}: a plastic network is "
            "compared at float32 weights only (the reference does not round "
            "the weights after each update)")
    st = stencil(net)
    frames, frame0 = chunk_frames(a, b, st.max_delay, k)
    n_cols = net["grid_h"] * net["grid_w"]
    blk = block_size(n_cols, net["neurons_per_column"], st.k_total)
    key = net_key(net)
    if stdp and k != 1:
        raise ValueError("a plastic cell is compared one step per call: the "
                         "remote rule reads the previous step's traces")
    placed = []
    for dev in devices:
        put = lambda x, dev=dev: None if x is None else jax.device_put(x, dev)
        plastic = None
        if stdp:
            plastic = tuple(put(x) for x in (a.x_pre, a.x_post, a.w_local,
                                             a.rem_w, b.x_pre, b.x_post,
                                             b.w_local, b.rem_w))
        placed.append((put(frames), put(a.v), put(a.c), put(a.refrac),
                       put(b.v), put(b.c), plastic))
    outs = []
    for i, col0 in enumerate(range(0, n_cols, blk)):
        fr, v_a, c_a, r_a, v_b, c_b, plastic = placed[i % len(devices)]
        outs.append(_compare_block(
            key, k, blk, jnp.int32(col0), jnp.int32(a.t), fr,
            jnp.int32(frame0), v_a, c_a, r_a, v_b, c_b, plastic))
    outs = jax.device_get(outs)
    res = {}
    for name in outs[0]:
        vals = [o[name] for o in outs]
        res[name] = (int(np.sum(vals, dtype=np.int64))
                     if name in ("spike_flips", "events")
                     else float(np.max(vals)))
    events_ref = res.pop("events")
    res["event_gap"] = abs((b.events - a.events) - events_ref)
    return res


# ---------------------------------------------------------------------------
# The control: the reference in bfloat16, in the program's place
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("net_key", "block", "dtype"))
def _control_block(net_key, block, dtype, col0, t, frames, frame0, v, c, r,
                   plastic):
    net = json.loads(net_key)
    st = stencil(net)
    gw = net["grid_w"]
    dtype = jnp.dtype(dtype)
    cols = col0 + jnp.arange(block, dtype=jnp.int32)
    rows, xs = cols // gw, cols % gw
    take = lambda x: jax.lax.dynamic_slice_in_dim(x, col0, block, axis=0)
    wl, rem_src, rw, outdeg, _, _ = _block_synapses(net, st, cols, dtype)
    if plastic is not None:
        x_pre_all, x_post_all, wl_a, rw_a = plastic
        wl, rw = take(wl_a).astype(dtype), take(rw_a).astype(dtype)
    at = lambda d: jax.lax.dynamic_index_in_dim(
        frames, t - d - frame0, keepdims=False).astype(dtype)
    v, c, r, spk, ev = _step(net, st, cols, t, at, take(v).astype(dtype),
                             take(c).astype(dtype), take(r), wl, rw, rem_src,
                             outdeg)
    out = {"v": v, "c": c, "r": r, "spk": spk, "events": ev}
    if plastic is not None:
        exc = (jnp.arange(net["neurons_per_column"])
               < n_excitatory(net)).astype(dtype)
        x_pre, x_post, wl, rw = _stdp(
            net, st, rows, xs, x_pre_all.astype(dtype),
            take(x_pre_all).astype(dtype), take(x_post_all).astype(dtype),
            spk, wl, rw, rem_src, exc)
        out.update(x_pre=x_pre, x_post=x_post, wl=wl, rw=rw)
    return out


def control_chunk(net: dict, a: Snapshot, k: int, devices,
                  dtype: str = "bfloat16") -> Snapshot:
    """``k`` steps of the reference computed in ``dtype`` from ``a``: the
    state a program of that precision would return."""
    st = stencil(net)
    n_cols = net["grid_h"] * net["grid_w"]
    blk = block_size(n_cols, net["neurons_per_column"], st.k_total)
    key = net_key(net)
    hist = [np.asarray(jax.device_get(a.frames[i]), np.float32)
            for i in range(a.frames.shape[0])]
    v, c, r = a.v, a.c, a.refrac
    plastic = None
    if net["stdp"]:
        plastic = (a.x_pre, a.x_post, a.w_local, a.rem_w)
    spikes = a.spikes
    events = a.events
    for j in range(k):
        t = a.t + j
        f0 = t - st.max_delay
        frames = np.stack(hist[f0 - a.frame0:])
        placed = []
        for dev in devices:
            put = lambda x, dev=dev: jax.device_put(x, dev)
            placed.append((put(frames), put(v), put(c), put(r),
                           None if plastic is None
                           else tuple(put(x) for x in plastic)))
        outs = []
        for i, col0 in enumerate(range(0, n_cols, blk)):
            fr, pv, pc, pr, pp = placed[i % len(devices)]
            outs.append(_control_block(
                key, blk, dtype, jnp.int32(col0), jnp.int32(t), fr,
                jnp.int32(f0), pv, pc, pr, pp))
        outs = jax.device_get(outs)
        cat = lambda name: np.concatenate([o[name] for o in outs])
        v, c, r = cat("v"), cat("c"), cat("r")
        spk = cat("spk").astype(np.float32)
        hist.append(spk)
        spikes += int(spk.sum())
        events += int(sum(int(o["events"]) for o in outs))
        if plastic is not None:
            plastic = (cat("x_pre"), cat("x_post"), cat("wl"), cat("rw"))
    frames = np.stack(hist)
    out = Snapshot(t=a.t + k, v=v, c=c, refrac=r, frames=frames,
                   frame0=a.frame0, spikes=spikes, events=events)
    if plastic is not None:
        out = out._replace(x_pre=plastic[0], x_post=plastic[1],
                           w_local=plastic[2], rem_w=plastic[3])
    return out
