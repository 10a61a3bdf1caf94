"""Multi-process distributed runtime — the paper's MPI ranks, JAX-native.

The source paper's headline measurement distributes one network over
1..1024 *software processes* exchanging real messages (arXiv:1511.09325
Sec. 3); its lineage mini-app (arXiv:1310.8478) stresses that scaling
numbers only count when ranks are OS processes, not threads sharing an
address space. Everything below turns the existing single-process
shard_map engine into exactly that:

* each **rank** is one OS process (spawned by
  ``launch/launch_distributed.py``, or by any cluster launcher that sets
  the coordinator env) owning one local device;
* :func:`init_worker` wires the rank into ``jax.distributed`` — a
  coordinator service for topology discovery plus, on the CPU backend,
  **gloo TCP collectives** so cross-process ``ppermute``/``psum``
  execute as real network messages (the MPI-analogue transport);
* :func:`make_process_mesh` assembles the **global** 2-D device mesh
  across processes with **process-major placement**: rank r owns tile
  ``(r // rx, r % rx)`` of the column grid (``partition.process_grid``
  factorization), so every halo ppermute crosses at most one process
  boundary per ring — the same nearest-neighbour traffic pattern the
  paper engineered for its MPI exchange;
* :func:`worker_run` then runs the **unmodified** distributed step —
  multi-ring halo exchange, trace halo, STDP, bit-packed payloads — on
  that mesh. No branch in `core/` distinguishes processes from devices:
  determinism-per-column-id makes the multi-process trajectory bitwise
  equal to the single-process one (asserted by the launcher and CI);
* with ``--ranks-per-node g`` the same devices assemble into the
  **hierarchical** 4-axis mesh ('ndata','data','nmodel','model'):
  consecutive process-major ranks group into node groups
  (``partition.make_node_spec``) and every halo exchange runs
  two-level — intra-node all-gather, ONE inter-node message per
  neighbour-node pair per ring, per-ring wire format — still bitwise
  equal to the flat run (DESIGN.md §Hierarchy).

Run one rank by hand (the launcher does this N times):

    PYTHONPATH=src python -m repro.runtime.multiprocess \
        --rank 0 --nranks 4 --coordinator 127.0.0.1:9300 \
        --grid 8x8 --neurons 64 --steps 100
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Optional

RESULT_TAG = "DPSNN-RESULT "  # rank 0 prints this + one JSON object


def init_worker(rank: int, n_ranks: int, coordinator: str) -> None:
    """Join the jax.distributed job as process ``rank`` of ``n_ranks``.

    Must run before any other JAX API touches the backend. Ranks emulate
    the paper's MPI processes on the CPU (the launcher starts them with
    ``JAX_PLATFORMS=cpu``): the collectives implementation is switched to
    gloo (TCP) — the stock CPU client refuses multi-process computations
    outright. On a TPU host the chip path is instead ONE process driving
    all local chips (``core/exchange.make_distributed_run`` on a mesh of
    ``jax.devices()``).
    """
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=n_ranks,
        process_id=rank,
    )


def make_process_mesh(n_ranks: Optional[int] = None,
                      ranks_per_node: int = 0):
    """Global mesh over all processes' devices, process-major.

    Devices sort by (process_index, id) and reshape onto the
    closest-to-square ``(ry, rx)`` process grid, axes ('data', 'model')
    — the same axis names the single-process engine uses, so
    ``make_distributed_run`` works unchanged. With one device per
    process (the CPU default) rank r is the shard at
    ``(r // rx, r % rx)``; with k local devices each process's devices
    extend its row contiguously (still process-major: halo neighbours
    differ by at most one process hop).

    With ``ranks_per_node`` the process grid additionally factors into
    node groups of that many *consecutive* ranks
    (``partition.make_node_spec``) and the mesh becomes the
    hierarchical ('ndata','data','nmodel','model') convention of
    DESIGN.md §Hierarchy: the same devices in the same process-major
    order, reshaped ``(nodes_y, group_h, nodes_x, group_w)`` — so the
    flat and hierarchical meshes place every rank on the same tile and
    results compare bitwise.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.partition import make_node_spec, process_grid

    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    if n_ranks is None:
        n_ranks = jax.process_count()
    local = len(devices) // n_ranks
    if n_ranks * local != len(devices):
        raise ValueError(
            f"{len(devices)} global devices do not split evenly over "
            f"{n_ranks} processes"
        )
    ry, rx = process_grid(n_ranks)
    grid = np.array(devices).reshape(ry, rx * local)
    # process-major invariant: every row-block of the device grid is
    # owned by consecutive ranks (halo pairs are 1 process hop apart)
    for r in range(ry):
        for c in range(rx * local):
            expect = r * rx + c // local
            got = grid[r, c].process_index
            if got != expect:
                raise AssertionError(
                    f"device grid ({r},{c}) owned by process {got}, "
                    f"expected {expect} — placement is not process-major"
                )
    if not ranks_per_node:
        return Mesh(grid, ("data", "model"))
    if local != 1:
        raise ValueError(
            f"--ranks-per-node assumes one device per process (the CPU "
            f"rank runtime); got {local} local devices per rank")
    node = make_node_spec(ry, rx, ranks_per_node)
    hier = grid.reshape(node.nodes_y, node.group_h,
                        node.nodes_x, node.group_w)
    return Mesh(hier, ("ndata", "data", "nmodel", "model"))


def make_batched_process_mesh(batch_shards: int,
                              n_ranks: Optional[int] = None):
    """Global ``('batch','data','model')`` mesh for the batched service
    (DESIGN.md §Service): the tenant axis shards over process groups,
    each group replicating the spatial column mesh of
    :func:`make_process_mesh`.

    Placement is batch-major process-major: ranks ``[k*S, (k+1)*S)`` form
    batch shard k over the ``S = n_ranks / batch_shards`` spatial ranks,
    so halo ppermutes stay nearest-neighbour *within* a batch shard and
    the tenant axis never appears in a spike collective at all (tenants
    are independent — 'batch' only carries psums of per-tenant metrics).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.partition import process_grid

    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    if n_ranks is None:
        n_ranks = jax.process_count()
    if batch_shards < 1 or n_ranks % batch_shards:
        raise ValueError(
            f"{n_ranks} ranks do not split over {batch_shards} batch "
            f"shards — pick batch_shards dividing the rank count")
    local = len(devices) // n_ranks
    if n_ranks * local != len(devices):
        raise ValueError(
            f"{len(devices)} global devices do not split evenly over "
            f"{n_ranks} processes")
    spatial = n_ranks // batch_shards
    ry, rx = process_grid(spatial)
    grid = np.array(devices).reshape(batch_shards, ry, rx * local)
    return Mesh(grid, ("batch", "data", "model"))


def worker_run_batched(cfg, n_steps: int, *, batch: int,
                       batch_shards: int = 1, impl: str = "ref",
                       compress: bool = True, timed_reps: int = 1) -> dict:
    """Batched multi-tenant distributed run on the global process mesh
    (``exchange.make_batched_distributed_run``): B tenants with seeds
    ``cfg.seed + i`` share one connectivity table; per-tenant totals are
    replicated to every rank so the launcher can check each tenant
    bitwise against its dedicated single-process run.

    Same timing protocol as :func:`worker_run` (one untimed warm-up,
    min of ``timed_reps``); throughput rows add ``batch_size`` /
    ``batch_shards`` / per-tenant columns (compare.py keys on
    ``batch_size``, absent == 1).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import exchange

    mesh = make_batched_process_mesh(batch_shards)
    run, spec = exchange.make_batched_distributed_run(
        cfg, mesh, n_steps=n_steps, batch=batch, impl=impl,
        compress=compress)
    seeds = cfg.seed + jnp.arange(batch, dtype=jnp.int32)
    res = run(seeds)
    res.rate_hz.block_until_ready()  # compile + warm-up, untimed
    walls = []
    for _ in range(timed_reps):
        t0 = time.perf_counter()
        res = run(seeds)
        res.rate_hz.block_until_ready()
        walls.append(time.perf_counter() - t0)
    wall_s = min(walls)
    per_spikes = [float(s) for s in res.spikes]
    per_events = [float(e) for e in res.events]
    events = sum(per_events)
    from repro.runtime.compression import halo_payload_bytes

    payload = halo_payload_bytes(cfg, spec, compress=compress)
    return {
        "rank_count": jax.process_count(),
        "batch_size": batch,
        "batch_shards": batch_shards,
        "process_grid": [mesh.shape["batch"], mesh.shape["data"],
                         mesh.shape["model"]],
        "grid": f"{cfg.grid_h}x{cfg.grid_w}",
        "neurons": cfg.n_neurons,
        "tile": f"{spec.tile_h}x{spec.tile_w}",
        "steps": n_steps,
        "wall_s": wall_s,
        "step_ms": wall_s / n_steps * 1e3,
        "spikes": sum(per_spikes),
        "events": events,
        "events_per_s": events / max(wall_s, 1e-12),
        "events_per_s_per_tenant": events / max(wall_s, 1e-12) / batch,
        "per_tenant_spikes": per_spikes,
        "per_tenant_events": per_events,
        "tenant_seeds": [int(s) for s in seeds],
        "impl": impl,
        "compress": compress,
        "guard": cfg.guard.enabled,
        "pipelined": cfg.exchange.pipelined,
        "exchange_mode": cfg.conn.exchange_mode,
        "halo_payload_bytes_per_step": payload["bytes_per_step"],
        "aer_saturated_steps": int(res.aer_saturated.sum()),
    }


def _write_heartbeat(hb_dir: str, rank: int, step: int, *,
                     step_ewma_s: Optional[float] = None,
                     straggler: bool = False) -> None:
    """Atomically publish this rank's progress (ckpt_dir/hb/rank<r>.json).
    The supervisor reads these to compute ``lost_steps`` after a death —
    write-then-rename so a SIGKILL mid-write never leaves torn JSON.
    ``step_ewma_s``/``straggler`` publish the StragglerWatchdog verdict so
    an operator (or the supervisor) can spot a slow rank from the
    heartbeat files alone."""
    os.makedirs(hb_dir, exist_ok=True)
    path = os.path.join(hb_dir, f"rank{rank}.json")
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"rank": rank, "step": step, "pid": os.getpid(),
                   "wall": time.time(), "step_ewma_s": step_ewma_s,
                   "straggler": bool(straggler)}, f)
    os.replace(tmp, path)


def worker_run_supervised(cfg, total_steps: int, *, checkpoint_every: int,
                          ckpt_dir: str, impl: str = "ref",
                          compress: bool = True, chaos_kill_rank: int = -1,
                          chaos_at_step: int = -1) -> dict:
    """Supervised distributed run: chunked stepping with periodic
    checkpoints, heartbeats, and deterministic fault injection
    (DESIGN.md §Elasticity).

    The run advances in chunks whose boundaries are the multiples of
    ``checkpoint_every`` (plus ``chaos_at_step`` and ``total_steps``) —
    identical on every rank. Between chunks the full stacked state is
    **replicated** to every rank (``replicate_state=True`` runners), so
    rank 0 can save it whole and ANY surviving rank set can restore it:
    if the checkpoint was written by a different-size mesh the worker
    re-tiles it through ``checkpointer.reshard`` before resuming. Spike /
    event / ISI counters live in the scan carry as exact integer-valued
    partial sums, so the totals a resumed (even resized) run reports are
    bitwise what the uninterrupted run reports — the launcher keeps its
    single-process equality gate in supervised mode.

    ``chaos_kill_rank``/``chaos_at_step``: that rank SIGKILLs itself at
    that chunk boundary, after publishing its heartbeat and before any
    checkpoint is written — the supervisor's restart path is exercised
    with a deterministic ``lost_steps`` (boundary minus last multiple of
    ``checkpoint_every``).

    Integrity guard (``cfg.guard.enabled``, DESIGN.md §Integrity): the
    in-band GuardState rides the scan carry and the replicated stacked
    state, so corruption latches the exact step it occurred even though
    the host only *observes* it at chunk boundaries. A tripped guard
    aborts with :data:`integrity.GUARD_EXIT_CODE` **before** any
    checkpoint of the poisoned range is written — the last checkpoint on
    disk is always clean, and the supervisor's restart (which strips the
    chaos flags) rolls the run back to it. The chaos-injection steps get
    their own chunk boundary so detection-to-abort latency is one step.

    A :class:`StragglerWatchdog` observes each chunk's per-step wall time
    (EWMA); the verdict is published in every heartbeat row and the
    final metrics (``straggler_steps`` / ``step_ewma_s``).
    """
    import jax
    import numpy as np

    from repro.checkpoint import checkpointer as ckpt
    from repro.core import counters, exchange
    from repro.core.partition import make_tile_spec
    from repro.runtime import integrity
    from repro.runtime.fault_tolerance import (CheckpointPolicy,
                                               StragglerWatchdog)

    mesh = make_process_mesh()
    rank = jax.process_index()
    n_ranks = jax.process_count()
    spec = make_tile_spec(cfg, mesh.shape["data"], mesh.shape["model"])
    hb_dir = os.path.join(ckpt_dir, "hb")
    meta = {"mesh": [spec.tiles_y, spec.tiles_x], "n_ranks": n_ranks,
            "grid": [cfg.grid_h, cfg.grid_w], "stdp": cfg.stdp,
            "total_steps": total_steps}

    # ---- restore (possibly across a mesh resize) ----------------------
    start, resumed_from, stacked = 0, -1, None
    saved_step = ckpt.latest_step(ckpt_dir)
    if saved_step is not None:
        man = ckpt.load_manifest(ckpt_dir, saved_step)
        saved_ranks = man["meta"]["n_ranks"]
        tpl, saved_spec, _ = exchange.stacked_state_template(cfg, saved_ranks)
        if tuple(man["meta"]["mesh"]) == (spec.tiles_y, spec.tiles_x):
            stacked, start = ckpt.restore(
                ckpt_dir, tpl, saved_step,
                expect_mesh=(spec.tiles_y, spec.tiles_x))
        else:
            # restore for the WRITER's tiling, then re-tile for ours
            stacked, start = ckpt.restore(ckpt_dir, tpl, saved_step)
            stacked = ckpt.reshard(stacked, saved_spec, spec)
        resumed_from = start
    if stacked is None:
        init_run, _ = exchange.make_distributed_run(
            cfg, mesh, n_steps=0, impl=impl, compress=compress,
            with_state=True, replicate_state=True)
        _, stacked = init_run()
        stacked = jax.tree_util.tree_map(np.asarray, stacked)

    # ---- chunk schedule (identical on every rank) ---------------------
    bounds = set(range(checkpoint_every, total_steps, checkpoint_every))
    if start < chaos_at_step < total_steps:
        bounds.add(chaos_at_step)
    gcfg = cfg.guard
    if gcfg.enabled:
        # give each injection step its own boundary: the guard latches
        # in-band at the corrupt step, the host aborts one step later
        for cs in (gcfg.chaos_flip_step, gcfg.chaos_nan_at_step):
            if start <= cs < total_steps:
                bounds.add(cs + 1)
    bounds.add(total_steps)
    bounds = [b for b in sorted(bounds) if b > start]

    runners = {}

    def chunk_runner(n: int):
        if n not in runners:
            runners[n] = exchange.make_distributed_resume(
                cfg, mesh, n_steps=n, impl=impl, compress=compress,
                replicate_state=True)[0]
        return runners[n]

    policy = CheckpointPolicy(ckpt_dir, every_steps=checkpoint_every,
                              async_save=False, meta=meta)
    watchdog = StragglerWatchdog()
    wall0 = time.perf_counter()
    cur = start
    _write_heartbeat(hb_dir, rank, cur)
    for b in bounds:
        t0 = time.perf_counter()
        _, stacked = chunk_runner(b - cur)(stacked)
        stacked = jax.tree_util.tree_map(np.asarray, stacked)
        straggler = watchdog.observe(
            b, (time.perf_counter() - t0) / max(b - cur, 1))
        cur = b
        _write_heartbeat(hb_dir, rank, cur, step_ewma_s=watchdog.ewma,
                         straggler=straggler)
        # guard verdict gates the save: a tripped guard means some state
        # in [last clean checkpoint, cur] is poisoned — abort with the
        # dedicated exit code so the supervisor rolls back instead of
        # adopting the corrupt range. Every rank sees the same replicated
        # stacked guard, so all abort consistently.
        if gcfg.enabled and bool(np.any(np.asarray(stacked.guard.tripped))):
            if rank == 0:
                rep = integrity.guard_report(stacked.guard)
                print("DPSNN-GUARD " + json.dumps(rep, sort_keys=True),
                      file=sys.stderr, flush=True)
            sys.exit(integrity.GUARD_EXIT_CODE)
        if rank == chaos_kill_rank and cur == chaos_at_step:
            import signal

            os.kill(os.getpid(), signal.SIGKILL)
        if rank == 0:
            if not policy.maybe_save(cur, stacked) and cur == total_steps:
                os.makedirs(ckpt_dir, exist_ok=True)
                ckpt.save(ckpt_dir, cur, stacked, meta=meta)
    wall_s = time.perf_counter() - wall0

    # ---- metrics from the replicated final state ----------------------
    # counters are cumulative per-shard partial sums since t=0 (they ride
    # the checkpoint), so the totals cover the WHOLE run, not this
    # worker's chunks. No step_ms key: a supervised run's wall time
    # includes checkpoint IO, so it must not enter the bench gate
    # (benchmarks/compare.py keys on step_ms).
    spikes = float(counters.value(np.asarray(stacked.spike_count).sum(0)))
    events = float(counters.value(np.asarray(stacked.event_count).sum(0)))
    isi_n = float(np.sum(np.asarray(stacked.isi_count, np.float64)))
    isi_mean = float(np.sum(np.asarray(stacked.isi_sum, np.float64)))
    isi_mean = isi_mean / isi_n if isi_n else 0.0
    isi_sq = float(np.sum(np.asarray(stacked.isi_sumsq, np.float64)))
    isi_var = max(isi_sq / isi_n - isi_mean ** 2, 0.0) if isi_n else 0.0
    isi_cv = (isi_var ** 0.5) / isi_mean if isi_mean else 0.0
    sim_s = total_steps * cfg.neuron.dt_ms * 1e-3
    guard_row = {"guard": gcfg.enabled,
                 "straggler_steps": watchdog.stragglers,
                 "step_ewma_s": watchdog.ewma or 0.0}
    if gcfg.enabled:
        guard_row.update(integrity.guard_report(stacked.guard))
    return {
        **guard_row,
        "rank_count": n_ranks,
        "process_grid": [mesh.shape["data"], mesh.shape["model"]],
        "grid": f"{cfg.grid_h}x{cfg.grid_w}",
        "neurons": cfg.n_neurons,
        "tile": f"{spec.tile_h}x{spec.tile_w}",
        "steps": total_steps,
        "wall_s": wall_s,
        "spikes": spikes,
        "events": events,
        "rate_hz": spikes / (cfg.n_neurons * sim_s),
        "isi_mean_steps": isi_mean,
        "isi_cv": isi_cv,
        "resumed_from_step": resumed_from,
        "checkpoint_every": checkpoint_every,
        "supervised": True,
        "impl": impl,
        "compress": compress,
        "pipelined": cfg.exchange.pipelined,
        "exchange_mode": cfg.conn.exchange_mode,
    }


def worker_run(cfg, n_steps: int, *, impl: str = "ref",
               compress: bool = True, timed_reps: int = 1,
               ranks_per_node: int = 0) -> dict:
    """Build + run the distributed simulation on the global process mesh;
    return the paper's metrics (spikes/events are psum'd, replicated, so
    every rank returns identical totals).

    Timing protocol: one untimed call compiles and warms the collectives;
    then ``timed_reps`` calls are timed individually end-to-end (all
    ranks block on the replicated result, so each wall time includes
    every cross-process message of every step) and the **minimum** is
    reported — the standard noise filter when ranks oversubscribe cores
    and any single rep can absorb a scheduler preemption.

    ``ranks_per_node`` switches the mesh (and therefore every halo
    exchange) to the hierarchical two-level scheme; the metrics row then
    carries the node grid and the exact inter-/intra-node byte split
    (runtime.compression.hier_payload_bytes).
    """
    import jax

    from repro.core import exchange

    mesh = make_process_mesh(ranks_per_node=ranks_per_node)
    run, spec = exchange.make_distributed_run(
        cfg, mesh, n_steps=n_steps, impl=impl, compress=compress
    )
    res = run()
    res.rate_hz.block_until_ready()  # compile + warm-up, untimed
    walls = []
    for _ in range(timed_reps):
        t0 = time.perf_counter()
        res = run()
        res.rate_hz.block_until_ready()
        walls.append(time.perf_counter() - t0)
    wall_s = min(walls)
    events = float(res.events)
    from repro.runtime.compression import halo_payload_bytes, \
        hier_payload_bytes

    _, _, node, row_shards, col_shards = exchange.mesh_layout(mesh)
    policy_auto = cfg.exchange.exchange_mode == "auto"
    acct_mode = "auto" if policy_auto else cfg.conn.exchange_mode
    hier_row = {}
    if node is not None:
        payload = hier_payload_bytes(cfg, spec, node, mode=acct_mode,
                                     compress=compress)
        hier_row = {
            "ranks_per_node": node.ranks_per_node,
            "node_grid": payload["node_grid"],
            "inter_node_bytes_per_node": payload[
                "inter_node_bytes_per_node"],
            "inter_node_messages_per_node": payload[
                "inter_node_messages_per_node"],
            "intra_node_bytes_per_rank": payload[
                "intra_node_bytes_per_rank"],
            "per_ring_modes": [
                {"phase": e["phase"], "ring": e["ring"],
                 "mode": e["mode"] if policy_auto else acct_mode}
                for e in payload["per_ring"]],
        }
    else:
        payload = halo_payload_bytes(cfg, spec, mode=acct_mode,
                                     compress=compress)
    return {
        "rank_count": jax.process_count(),
        "process_grid": [row_shards, col_shards],
        **hier_row,
        "grid": f"{cfg.grid_h}x{cfg.grid_w}",
        "neurons": cfg.n_neurons,
        "syn_equiv": cfg.total_equivalent_synapses,
        "tile": f"{spec.tile_h}x{spec.tile_w}",
        "steps": n_steps,
        "wall_s": wall_s,
        "step_ms": wall_s / n_steps * 1e3,
        "spikes": float(res.spikes),
        "events": events,
        "events_per_s": events / max(wall_s, 1e-12),
        "rate_hz": float(res.rate_hz),
        "state_checksum": float(res.state_checksum),
        "impl": impl,
        "compress": compress,
        "guard": cfg.guard.enabled,
        "pipelined": cfg.exchange.pipelined,
        # "auto" marks the per-ring policy; uniform runs report the
        # conn wire format as before (benchmarks/compare.py keys on it)
        "exchange_mode": acct_mode,
        "halo_payload_bytes_per_step": payload["bytes_per_step"],
        # steps on which some rank's AER send overflowed its capacity
        # (spikes truncated from the wire — degraded, flagged, never
        # silent); always 0 under dense_packed
        "aer_saturated_steps": int(res.aer_saturated.sum()),
    }


def build_cfg(args) -> "object":
    from repro.configs.base import DPSNNConfig
    from repro.configs.dpsnn import with_family, with_ranks

    gh, gw = (int(v) for v in args.grid.split("x"))
    cfg = DPSNNConfig(grid_h=gh, grid_w=gw,
                      neurons_per_column=args.neurons, seed=args.seed)
    if args.family != "gauss":
        cfg = with_family(cfg, args.family)
    if args.radius:
        cfg = dataclasses.replace(
            cfg, conn=dataclasses.replace(cfg.conn, radius=args.radius))
    # "auto" is a *selection policy* (ExchangeConfig), not a wire format:
    # conn.exchange_mode keeps its uniform-format meaning and the rate
    # bound still sizes the AER capacities auto-selected rings use
    if args.exchange_mode == "aer_sparse" or args.aer_rate_bound:
        conn_kw = {}
        if args.exchange_mode == "aer_sparse":
            conn_kw["exchange_mode"] = args.exchange_mode
        if args.aer_rate_bound:
            conn_kw["aer_rate_bound_hz"] = args.aer_rate_bound
        if args.aer_capacity_factor:
            conn_kw["aer_capacity_factor"] = args.aer_capacity_factor
        cfg = dataclasses.replace(
            cfg, conn=dataclasses.replace(cfg.conn, **conn_kw))
    if args.stdp:
        cfg = dataclasses.replace(cfg, stdp=True)
    if args.pipelined or args.exchange_mode == "auto":
        from repro.configs.base import ExchangeConfig
        cfg = dataclasses.replace(cfg, exchange=ExchangeConfig(
            pipelined=args.pipelined,
            exchange_mode=("auto" if args.exchange_mode == "auto"
                           else "inherit")))
    if args.weak:
        # --grid is the per-rank tile; the global grid scales with ranks
        cfg = with_ranks(cfg, args.nranks)
    if getattr(args, "guard", False):
        from repro.configs.base import GuardConfig
        cfg = dataclasses.replace(cfg, guard=GuardConfig(enabled=True))
    return cfg


def add_workload_args(ap: argparse.ArgumentParser) -> None:
    """Workload flags shared by the worker and the launcher CLIs."""
    ap.add_argument("--grid", default="8x8",
                    help="column grid HxW (with --weak: the per-rank tile)")
    ap.add_argument("--neurons", type=int, default=64)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--family", default="gauss",
                    choices=["gauss", "exp", "gauss_exp"])
    ap.add_argument("--radius", type=int, default=0,
                    help="override the family's stencil bound (0 = keep)")
    ap.add_argument("--stdp", action="store_true")
    ap.add_argument("--impl", default="ref",
                    choices=["ref", "pallas", "pallas_fused"])
    ap.add_argument("--pipelined", action="store_true",
                    help="cross-step pipelined halo exchange "
                         "(ExchangeConfig.pipelined, DESIGN.md §Fusion)")
    ap.add_argument("--no-compress", dest="compress", action="store_false")
    ap.add_argument("--exchange-mode", default="dense_packed",
                    choices=["dense_packed", "aer_sparse", "auto"],
                    help="spike-halo wire format (DESIGN.md §AER); "
                         "'auto' selects per ring from the exact byte "
                         "accounting (DESIGN.md §Hierarchy)")
    ap.add_argument("--ranks-per-node", type=int, default=0,
                    help="group this many consecutive ranks into node "
                         "groups and run the hierarchical two-level "
                         "halo exchange (0 = flat; DESIGN.md "
                         "§Hierarchy)")
    ap.add_argument("--aer-rate-bound", type=float, default=0.0,
                    help="AER capacity rate bound in Hz "
                         "(0 = config default)")
    ap.add_argument("--aer-capacity-factor", type=float, default=0.0,
                    help="AER capacity safety factor (0 = config default)")
    ap.add_argument("--weak", action="store_true",
                    help="weak scaling: --grid is one rank's tile, the "
                         "global grid is with_ranks(cfg, nranks)")
    ap.add_argument("--batch", type=int, default=0,
                    help="batched service mode: run this many tenants "
                         "with seeds seed..seed+B-1 (0 = single-tenant)")
    ap.add_argument("--batch-shards", type=int, default=1,
                    help="shard the tenant axis over this many process "
                         "groups (must divide --batch and the rank "
                         "count; DESIGN.md §Service)")
    ap.add_argument("--guard", action="store_true",
                    help="enable the in-band integrity guard: invariant "
                         "monitors + halo-frame checksums "
                         "(DESIGN.md §Integrity; bitwise-neutral on "
                         "healthy runs)")
    ap.add_argument("--timed-reps", type=int, default=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="one rank of the multi-process DPSNN runtime")
    ap.add_argument("--rank", type=int,
                    default=int(os.environ.get("DPSNN_RANK", "-1")))
    ap.add_argument("--nranks", type=int,
                    default=int(os.environ.get("DPSNN_NRANKS", "0")))
    ap.add_argument("--coordinator",
                    default=os.environ.get("DPSNN_COORDINATOR", ""))
    # supervised mode (launch_distributed.py --supervise passes these)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="supervised mode: checkpoint cadence in steps "
                         "(0 = plain unsupervised run)")
    ap.add_argument("--ckpt-dir", default="",
                    help="supervised mode: checkpoint + heartbeat dir")
    ap.add_argument("--chaos-kill-rank", type=int, default=-1,
                    help="fault injection: this rank SIGKILLs itself ...")
    ap.add_argument("--chaos-at-step", type=int, default=-1,
                    help="... at this chunk boundary (EXPERIMENTS.md "
                         "§Recovery)")
    # integrity chaos (worker-level, NOT in build_cfg: the launcher's
    # single-process reference must build the same cfg WITHOUT injection)
    ap.add_argument("--chaos-flip-bit", default="",
                    metavar="RING:STEP:WORD",
                    help="integrity chaos: XOR one bit into the received "
                         "payload of halo send ordinal RING at step STEP, "
                         "word WORD (requires --guard)")
    ap.add_argument("--chaos-nan-at-step", type=int, default=-1,
                    help="integrity chaos: poison one membrane voltage "
                         "with NaN at this step (requires --guard)")
    add_workload_args(ap)
    args = ap.parse_args(argv)
    if args.rank < 0 or args.nranks < 1 or not args.coordinator:
        ap.error("--rank/--nranks/--coordinator (or DPSNN_RANK/"
                 "DPSNN_NRANKS/DPSNN_COORDINATOR) are required")
    if args.checkpoint_every and not args.ckpt_dir:
        ap.error("--checkpoint-every requires --ckpt-dir")

    if args.ranks_per_node and (args.batch or args.checkpoint_every):
        ap.error("--ranks-per-node applies to the plain distributed run "
                 "only (not --batch / supervised mode)")

    init_worker(args.rank, args.nranks, args.coordinator)
    cfg = build_cfg(args)
    if args.chaos_flip_bit or args.chaos_nan_at_step >= 0:
        if not cfg.guard.enabled:
            ap.error("--chaos-flip-bit / --chaos-nan-at-step require "
                     "--guard")
        kw = {}
        if args.chaos_flip_bit:
            try:
                ring, fstep, word = (int(v) for v
                                     in args.chaos_flip_bit.split(":"))
            except ValueError:
                ap.error("--chaos-flip-bit wants RING:STEP:WORD "
                         "(three integers)")
            kw.update(chaos_flip_ring=ring, chaos_flip_step=fstep,
                      chaos_flip_word=word)
        if args.chaos_nan_at_step >= 0:
            kw["chaos_nan_at_step"] = args.chaos_nan_at_step
        cfg = dataclasses.replace(
            cfg, guard=dataclasses.replace(cfg.guard, **kw))
    if args.checkpoint_every:
        if args.batch:
            ap.error("supervised mode does not support --batch yet")
        out = worker_run_supervised(
            cfg, args.steps, checkpoint_every=args.checkpoint_every,
            ckpt_dir=args.ckpt_dir, impl=args.impl, compress=args.compress,
            chaos_kill_rank=args.chaos_kill_rank,
            chaos_at_step=args.chaos_at_step)
    elif args.batch:
        out = worker_run_batched(cfg, args.steps, batch=args.batch,
                                 batch_shards=args.batch_shards,
                                 impl=args.impl, compress=args.compress,
                                 timed_reps=args.timed_reps)
    else:
        out = worker_run(cfg, args.steps, impl=args.impl,
                         compress=args.compress,
                         timed_reps=args.timed_reps,
                         ranks_per_node=args.ranks_per_node)
    if args.rank == 0:
        print(RESULT_TAG + json.dumps(out, sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
