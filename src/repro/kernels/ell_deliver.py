"""Remote ELL delivery over bit-packed spike words (Pallas TPU kernel).

A target neuron ``n`` of column ``c`` reads its ``K`` remote synapses
``(rem_flat[c, n, k], rem_w[c, n, k])`` from the column's ``(O*N,)``
neighbour-spike table (``core/network.deliver_remote_ref``). The table is
binary, so it is passed packed: offset ``o``'s ``N`` spikes are
``W = ceil(N / 32)`` words, bit ``i`` of word ``j`` being neuron
``32 j + i`` (:func:`pack_spikes`). Every offset's words fit one row of
128 lanes, and a synapse's lookup becomes plain vector work:

* its slot ``k`` belongs to one offset ``o(k)`` (the stencil's slots are
  grouped by offset, ``StencilSpec.slot_offset``), static per lane;
* ``local = rem_flat - o(k) N`` splits into a word ``local >> 5`` and a
  bit ``local & 31``;
* the word's value is picked among offset ``o(k)``'s ``W`` words by a
  select tree on the word's bits (``W - 1`` selects), the candidates
  being the per-lane table ``words[o(k), j]`` built once per column;
* the synapse is on when that bit is set, and the current is
  ``sum_k where(on, w, 0)``, reduced over ``K`` in the same ``(rows, K)``
  layout as the reference, so interpreted on the CPU the kernel is
  bitwise equal to ``deliver_remote_ref``.

No vector gather is left: the tree is compares and selects. Every index
and weight is read once (1.42 GB a step at the paper's 24x24 grid),
where XLA's gather fetched a whole tile per element.

Layout. The grid is ``(C, N / TN)``; a grid step reads a ``(1, TN, K)``
block of indices and of weights, ``TN`` the largest divisor of ``N``
whose block fits ``BLOCK_BUDGET`` (a whole column at the paper's N =
1,240 for both K = 248 and K = 1,028). The step walks its block
``groups`` sublane tiles at a time: each candidate word, loaded for one
8-row tile, serves every tile of the inner step. Row sums leave the
loop along sublanes; one transpose at the end of the block lays them
along lanes for the ``(1, TN)`` output row. The per-lane word table is
built at the column's first row block into VMEM scratch and reused by
the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._padding import pad_to

WORD_BITS = 32
ROWS = 8                  # rows of one sublane tile
STEP_ELEMS = 64 << 10     # synapses of one inner step: 64 (8, 128) tiles
LANES = 128
BLOCK_BUDGET = 6 << 20    # bytes of one (TN, K) index or weight block
# scoped-VMEM headroom above the double-buffered blocks and the scratch
VMEM_HEADROOM = 4 << 20


def pack_spikes(s_flat: jax.Array, n_offsets: int) -> jax.Array:
    """(C, O*N) spike table -> (C, O, W) uint32 words, 32 spikes a word.

    Bit ``i`` of word ``j`` of offset ``o`` is ``s_flat[c, o*N + 32j + i]
    != 0``; the bits past ``N`` in an offset's last word are zero."""
    c = s_flat.shape[0]
    n = s_flat.shape[1] // n_offsets
    bits = (s_flat.reshape(c, n_offsets, n) != 0).astype(jnp.uint32)
    bits = pad_to(bits, 2, WORD_BITS).reshape(c, n_offsets, -1, WORD_BITS)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def row_block(n: int, k: int) -> int:
    """Target rows per grid step: the largest divisor of ``n`` that the
    TPU block rule allows (a multiple of 8, or ``n`` itself) whose
    ``(TN, K)`` int32 block, padded to 128 lanes, fits ``BLOCK_BUDGET``;
    else the smallest such divisor."""
    k_pad = -(-k // LANES) * LANES
    ok = [t for t in range(1, n + 1)
          if n % t == 0 and (t % ROWS == 0 or t == n)]
    fit = [t for t in ok if t * k_pad * 4 <= BLOCK_BUDGET]
    return max(fit) if fit else min(ok)


def vmem_limit(tn: int, k: int, n_words: int, step: int) -> int:
    """Scoped-VMEM limit for one grid step: the index and weight blocks
    double-buffered, the word table, the step's values and the row sums
    (rows padded to 8 sublanes, lanes to 128), plus ``VMEM_HEADROOM``.
    At ``K = 1,028`` a whole column's blocks take 22.9 MB, above the
    16 MiB v5e default, which is why the kernel states it."""
    k_pad = -(-k // LANES) * LANES
    tn_pad = -(-tn // LANES) * LANES
    step_pad = -(-step // ROWS) * ROWS
    scratch = (n_words * ROWS + step_pad) * k_pad + tn_pad * LANES
    return 4 * (2 * 2 * tn * k_pad + scratch) + VMEM_HEADROOM


def _row_steps(tn: int, k: int) -> tuple[int, int]:
    """``(groups, rows)``: the inner loop takes ``groups`` sublane groups
    of ``rows`` rows a step, as many as keep its ``(groups*rows, K)``
    operands within ``STEP_ELEMS`` and leave it two steps or more (XLA
    inlines a loop of one step, and interpreted the sum then fuses with
    its neighbours, off the reference's order). ``rows`` is the 8-row
    sublane tile, or for a column whose N is no multiple of 8 (test
    geometries) the largest divisor of TN below 8."""
    rows = max(d for d in range(1, ROWS + 1) if tn % d == 0)
    k_pad = -(-k // LANES) * LANES
    tiles = tn // rows
    groups = max(g for g in range(1, tiles + 1)
                 if tiles % g == 0 and (g == 1 or (
                     g * rows * k_pad <= STEP_ELEMS and tiles // g >= 2)))
    return groups, rows


def _make_kernel(slots: tuple[int, ...], n: int, tn: int, n_words: int,
                 sum_dtype, interpret: bool):
    k = sum(slots)
    groups, rows = _row_steps(tn, k)
    step = groups * rows
    # Mosaic takes a dynamic row offset only on the tiling, so off it the
    # compiled kernel unrolls the row loop; the interpreter keeps the loop,
    # which holds the reduction apart as the reference's own op is
    unrolled = rows % ROWS != 0 and not interpret
    tn_pad = -(-tn // LANES) * LANES
    bounds = []                       # (offset, first slot, end slot)
    lo = 0
    for o, ko in enumerate(slots):
        bounds.append((o, lo, lo + ko))
        lo += ko

    def kernel(words_ref, idx_ref, w_ref, out_ref, tab_ref, acc_ref, val_ref):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

        @pl.when(pl.program_id(1) == 0)
        def _table():
            # per-lane words: tab[j, :, k] = words[o(k), j], over one
            # sublane group; static lane masks per offset, no gather
            wt = words_ref[0]                               # (W, O)
            tab = jnp.zeros((n_words, k), jnp.int32)
            for o, a, b in bounds:
                tab = jnp.where((lane >= a) & (lane < b), wt[:, o:o + 1],
                                tab)
            for j in range(n_words):
                tab_ref[j] = jnp.broadcast_to(tab[j:j + 1], (rows, k))

        base = jnp.zeros((1, k), jnp.int32)                 # o(k) * N
        for o, a, b in bounds[1:]:
            base = jnp.where(lane >= a, o * n, base)
        zero = jnp.zeros((), sum_dtype)

        def body(i, carry):
            r0 = i * step if unrolled else pl.multiple_of(i * step, step)
            # (groups, rows, K): each candidate word, one sublane group
            # held in registers, serves every group of the step
            idx = idx_ref[0, pl.ds(r0, step), :].reshape(groups, rows, k)
            local = idx - base
            word = local >> 5
            cand = [tab_ref[j] for j in range(n_words)]
            level = 0
            while len(cand) > 1:
                hi = (word & (1 << level)) != 0
                cand = [jnp.where(hi, cand[j + 1], cand[j])
                        if j + 1 < len(cand) else cand[j]
                        for j in range(0, len(cand), 2)]
                level += 1
            value = jnp.broadcast_to(cand[0], local.shape)  # W may be 1
            on = (jax.lax.shift_right_logical(value, local & 31) & 1) != 0
            w = w_ref[0, pl.ds(r0, step), :].astype(sum_dtype)
            # through VMEM, so that interpreted the sum is an op of its
            # own over a loaded array, as the reference's is
            val_ref[...] = jnp.where(on.reshape(step, k), w, zero)
            cur = val_ref[...].sum(axis=-1, keepdims=True)
            acc_ref[pl.ds(r0, step), :] = jnp.broadcast_to(
                cur.astype(jnp.float32), (step, LANES))
            return carry

        if unrolled:
            for i in range(tn // step):
                body(i, 0)
        else:
            jax.lax.fori_loop(0, tn // step, body, 0)
        # row sums lie along sublanes; lay them along lanes
        out_ref[0, 0] = acc_ref[...].T[0:1, :tn]

    return kernel, rows, step, tn_pad


@functools.partial(jax.jit,
                   static_argnames=("slots", "out_dtype", "interpret"))
def ell_deliver(words: jax.Array, rem_flat: jax.Array, rem_w: jax.Array, *,
                slots: tuple[int, ...], out_dtype=jnp.float32,
                interpret: bool) -> jax.Array:
    """Remote currents ``(C, N)`` from packed spike words.

    * ``words``    (C, O, W) uint32 (:func:`pack_spikes`)
    * ``rem_flat`` (C, N, K) int32 indices into the ``(O*N,)`` table,
      slot ``k`` of offset ``o(k)`` as ``slots`` lays them out
    * ``rem_w``    (C, N, K) weights
    * ``slots``    static: the slot count of each offset, in slot order
      (``K_o`` of ``StencilSpec.offsets``; they sum to ``K``)

    ``out_dtype`` is the spike table's dtype: the sum runs in its
    promotion with the weights' and is cast to it, as the reference does.
    """
    nc, n, k = rem_flat.shape
    n_words = words.shape[2]
    if sum(slots) != k or len(slots) != words.shape[1]:
        raise ValueError(f"slots {slots} do not lay out K={k} over "
                         f"{words.shape[1]} offsets")
    sum_dtype = jnp.result_type(out_dtype, rem_w.dtype)
    tn = row_block(n, k)
    kernel, rows, step, tn_pad = _make_kernel(slots, n, tn, n_words,
                                              sum_dtype, interpret)
    # (C, W, O): an offset's words down the sublanes, for lane broadcast
    words_t = jax.lax.bitcast_convert_type(words, jnp.int32).transpose(
        0, 2, 1)
    block = pl.BlockSpec((1, tn, k), lambda c, t: (c, t, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nc, n // tn),
        in_specs=[
            pl.BlockSpec((1, n_words, len(slots)), lambda c, t: (c, 0, 0)),
            block, block,
        ],
        out_specs=pl.BlockSpec((1, 1, 1, tn), lambda c, t: (c, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, n // tn, 1, tn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_words, rows, k), jnp.int32),
                        pltpu.VMEM((tn_pad, LANES), jnp.float32),
                        pltpu.VMEM((step, k), sum_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(tn, k, n_words, step)),
        interpret=interpret,
        name="ell_deliver",
    )(words_t, rem_flat, rem_w)
    return out.reshape(nc, n).astype(out_dtype)
