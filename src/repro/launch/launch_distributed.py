"""Spawn-N-processes launcher for the multi-process DPSNN runtime.

The single-machine analogue of the paper's ``mpirun -np N``: spawns N
CPU worker processes (``repro.runtime.multiprocess``, each started with
``JAX_PLATFORMS=cpu`` — ranks emulate MPI processes and never open a
chip; on a TPU host the chip path is one process driving all local
chips, ``chip_smoke.py --four-chips``), wires them to a
fresh ``jax.distributed`` coordinator on a free localhost port, waits
for the job, and — by default — re-runs the identical workload
single-process in-process and asserts the spike/event totals are
**bitwise equal** (the determinism-per-column-id contract that makes
every scaling measurement trustworthy).

Quickstart (README §Quickstart):

    PYTHONPATH=src python -m repro.launch.launch_distributed --ranks 4

Emits a one-line summary per run plus, with ``--json``, the worker's
full metrics row (the BENCH schema: rank_count / step_ms /
events_per_s / ...). ``--weak`` reinterprets ``--grid`` as the
per-rank tile (``configs.dpsnn.with_ranks``), the paper's Fig 3
protocol. Exit status is non-zero on worker failure or an equality
mismatch, so CI can gate on it directly.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from repro.runtime.multiprocess import RESULT_TAG, add_workload_args

SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker_argv(args) -> list:
    argv = ["--grid", args.grid, "--neurons", str(args.neurons),
            "--steps", str(args.steps), "--seed", str(args.seed),
            "--family", args.family, "--impl", args.impl,
            "--timed-reps", str(args.timed_reps),
            "--exchange-mode", args.exchange_mode]
    if args.radius:
        argv += ["--radius", str(args.radius)]
    if args.aer_rate_bound:
        argv += ["--aer-rate-bound", str(args.aer_rate_bound)]
    if args.aer_capacity_factor:
        argv += ["--aer-capacity-factor", str(args.aer_capacity_factor)]
    if args.stdp:
        argv.append("--stdp")
    if args.batch:
        argv += ["--batch", str(args.batch),
                 "--batch-shards", str(args.batch_shards)]
    if args.pipelined:
        argv.append("--pipelined")
    if getattr(args, "guard", False):
        argv.append("--guard")
    if args.ranks_per_node:
        argv += ["--ranks-per-node", str(args.ranks_per_node)]
    if not args.compress:
        argv.append("--no-compress")
    if args.weak:
        argv.append("--weak")
    return argv


def _hb_last_activity(hb_dir: str) -> float:
    """Newest heartbeat-file mtime under ``hb_dir`` (0.0 if none)."""
    latest = 0.0
    try:
        names = os.listdir(hb_dir)
    except FileNotFoundError:
        return latest
    for name in names:
        if name.startswith("rank") and name.endswith(".json"):
            try:
                latest = max(latest,
                             os.path.getmtime(os.path.join(hb_dir, name)))
            except FileNotFoundError:
                pass
    return latest


def _max_heartbeat_step(hb_dir: str) -> int:
    """Furthest chunk boundary ANY rank reported (0 if none)."""
    best = 0
    try:
        names = os.listdir(hb_dir)
    except FileNotFoundError:
        return best
    for name in names:
        if name.startswith("rank") and name.endswith(".json"):
            try:
                with open(os.path.join(hb_dir, name)) as f:
                    best = max(best, int(json.load(f).get("step", 0)))
            except (OSError, ValueError):
                pass
    return best


def launch(args, *, ranks=None, extra=None, hb_dir=None,
           hb_timeout=0) -> dict:
    """Spawn ``args.ranks`` workers, return rank 0's metrics row.

    Workers write stdout/stderr to temp files rather than pipes: an
    undrained 64KB pipe would block a chatty rank mid-collective and
    stall the whole gloo job into a bogus timeout.

    ``ranks``/``extra`` let the supervisor resize the mesh per attempt
    and pass the checkpoint/chaos flags; with ``hb_dir``+``hb_timeout``
    the poll loop also fails the job when no rank has advanced a chunk
    boundary for ``hb_timeout`` seconds (a hung-not-dead worker fails in
    heartbeat time instead of eating the full --timeout).
    """
    n_ranks = ranks or args.ranks
    coordinator = f"127.0.0.1:{args.port or free_port()}"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # each worker is a clean single-device CPU process (ranks are the
    # parallelism axis; forced host-device counts would nest two axes).
    # Ranks emulate MPI processes on the CPU and never open a chip: on a
    # TPU host a chip belongs to one process, and the chip path is one
    # process driving all local chips (make_distributed_run).
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    wargv = worker_argv(args) + list(extra or ())
    with tempfile.TemporaryDirectory(prefix="dpsnn-mp-") as tmp:
        procs = []
        first_failed = None   # (rank, returncode) of the first real death
        t0 = time.time()
        try:
            for rank in range(n_ranks):
                out_f = open(os.path.join(tmp, f"rank{rank}.out"), "w+")
                err_f = open(os.path.join(tmp, f"rank{rank}.err"), "w+")
                procs.append((subprocess.Popen(
                    [sys.executable, "-m", "repro.runtime.multiprocess",
                     "--rank", str(rank), "--nranks", str(n_ranks),
                     "--coordinator", coordinator, *wargv],
                    stdout=out_f, stderr=err_f, text=True, env=env,
                ), out_f, err_f))
            # poll ALL ranks: a crash anywhere wedges the survivors in
            # their collectives, so the first non-zero exit (not a rank-0
            # timeout 900s later) is the diagnosis — kill the rest then.
            deadline = time.monotonic() + args.timeout
            pending = set(range(n_ranks))
            while pending:
                for rank in sorted(pending):
                    p = procs[rank][0]
                    if p.poll() is not None:
                        pending.discard(rank)
                        if p.returncode != 0 and first_failed is None:
                            first_failed = (rank, p.returncode)
                if first_failed is not None:
                    break
                if pending and time.monotonic() > deadline:
                    raise RuntimeError(
                        f"ranks {sorted(pending)} timed out after "
                        f"{args.timeout}s")
                if pending and hb_dir and hb_timeout:
                    stalled = time.time() - max(_hb_last_activity(hb_dir),
                                                t0)
                    if stalled > hb_timeout:
                        raise RuntimeError(
                            f"heartbeat stalled: no rank advanced a chunk "
                            f"boundary for {stalled:.0f}s "
                            f"(> --heartbeat-timeout {hb_timeout}s)")
                if pending:
                    time.sleep(0.05)
            outs = []
            for p, out_f, err_f in procs:
                if p.poll() is None:   # survivors of a crashed peer
                    p.kill()
                    p.wait()
                out_f.seek(0)
                err_f.seek(0)
                outs.append((out_f.read(), err_f.read()))
        finally:
            for p, out_f, err_f in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
                out_f.close()
                err_f.close()
    if first_failed is not None:
        rank, code = first_failed
        out, err = outs[rank]
        raise RuntimeError(
            f"rank {rank}/{n_ranks} exited {code} (remaining ranks "
            f"killed):\n{out}\n{err}")
    for line in outs[0][0].splitlines():
        if line.startswith(RESULT_TAG):
            return json.loads(line[len(RESULT_TAG):])
    raise RuntimeError(
        f"rank 0 produced no {RESULT_TAG!r} line:\n{outs[0][0]}\n"
        f"{outs[0][1]}")


def supervise(args) -> dict:
    """Fault-tolerant driver around :func:`launch` (DESIGN.md
    §Elasticity): launch -> on worker death or heartbeat stall, sweep
    orphaned checkpoint stages, account the lost steps (furthest
    heartbeat minus last durable checkpoint), and relaunch on the same —
    or, with ``--restart-ranks``, a resized — rank set; the workers
    restore from the last checkpoint (resharding it if the mesh
    changed). Chaos flags are dropped after the first attempt so an
    injected fault fires exactly once. The returned row gains
    ``restarts`` / ``lost_steps`` / ``supervised_wall_s``.

    Integrity-chaos flags (``--chaos-flip-bit`` / ``--chaos-nan-at-step``,
    require ``--guard``) follow the same protocol: first attempt only.
    The worker detects the corruption in-band, refuses to checkpoint the
    poisoned range, and exits with the guard code — this path restarts it
    WITHOUT the injection, so the run rolls back to the last clean
    checkpoint and converges to the uncorrupted trajectory
    (EXPERIMENTS.md §Guard; rollback-on-corruption).
    """
    from repro.checkpoint import checkpointer as ckpt

    if not args.checkpoint_every:
        raise SystemExit("--supervise requires --checkpoint-every N")
    if ((args.chaos_flip_bit or args.chaos_nan_at_step >= 0)
            and not args.guard):
        raise SystemExit(
            "--chaos-flip-bit / --chaos-nan-at-step require --guard "
            "(nothing would detect the corruption)")
    if args.ranks_per_node:
        raise SystemExit(
            "--supervise cannot be combined with --ranks-per-node: the "
            "hierarchical exchange path has no checkpoint/reshard support "
            "yet (DESIGN.md §Hierarchy)")
    if args.restart_ranks and args.weak:
        raise SystemExit(
            "--restart-ranks cannot be combined with --weak: the weak-"
            "scaling grid is derived from the rank count, so a resized "
            "restart would change the network itself")
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="dpsnn-ckpt-")
    hb_dir = os.path.join(ckpt_dir, "hb")
    restarts, lost_steps = 0, 0
    ranks = args.ranks
    wall0 = time.monotonic()
    while True:
        ckpt.gc_stale_stages(ckpt_dir)   # orphans of a killed mid-save
        extra = ["--checkpoint-every", str(args.checkpoint_every),
                 "--ckpt-dir", ckpt_dir]
        if restarts == 0 and args.chaos_kill_rank >= 0:
            extra += ["--chaos-kill-rank", str(args.chaos_kill_rank),
                      "--chaos-at-step", str(args.chaos_at_step)]
        if restarts == 0 and args.chaos_flip_bit:
            extra += ["--chaos-flip-bit", args.chaos_flip_bit]
        if restarts == 0 and args.chaos_nan_at_step >= 0:
            extra += ["--chaos-nan-at-step", str(args.chaos_nan_at_step)]
        try:
            row = launch(args, ranks=ranks, extra=extra, hb_dir=hb_dir,
                         hb_timeout=args.heartbeat_timeout)
            break
        except RuntimeError as e:
            restarts += 1
            observed = _max_heartbeat_step(hb_dir)
            durable = ckpt.latest_step(ckpt_dir) or 0
            lost_steps += max(0, observed - durable)
            if restarts > args.max_restarts:
                raise RuntimeError(
                    f"supervisor giving up after {args.max_restarts} "
                    f"restarts (step {durable} durable): {e}") from e
            if args.restart_ranks:
                ranks = args.restart_ranks
            print(f"SUPERVISOR restart {restarts}/{args.max_restarts}: "
                  f"resuming from step {durable} on {ranks} ranks "
                  f"({observed - durable} steps lost) — "
                  f"{str(e).splitlines()[0]}", flush=True)
    if args.chaos_kill_rank >= 0 and restarts == 0:
        raise RuntimeError(
            f"chaos kill of rank {args.chaos_kill_rank} at step "
            f"{args.chaos_at_step} was requested but the run finished "
            f"with no restart — the fault never fired")
    if (args.chaos_flip_bit or args.chaos_nan_at_step >= 0) \
            and restarts == 0:
        raise RuntimeError(
            "integrity chaos was requested (--chaos-flip-bit/"
            "--chaos-nan-at-step) but the run finished with no restart — "
            "the corruption was never detected")
    row["restarts"] = restarts
    row["lost_steps"] = lost_steps
    row["supervised_wall_s"] = time.monotonic() - wall0
    return row


def single_process_reference(args) -> dict:
    """The identical workload, single-process single-shard (in-process).

    Batched mode (``--batch B``): B dedicated single-tenant runs, one per
    tenant seed — the reference each batch slot must match bitwise
    (tenants share connectivity, differ in state/drive seed)."""
    import jax.numpy as jnp

    from repro.core import simulation as sim
    from repro.runtime.multiprocess import build_cfg

    ns = argparse.Namespace(**vars(args))
    ns.nranks = args.ranks  # --weak scales the grid by the rank count
    cfg = build_cfg(ns)
    if args.batch:
        per_spikes, per_events = [], []
        params, _ = sim.build(cfg)
        for i in range(args.batch):
            seed = jnp.int32(cfg.seed + i)
            state = sim.build(cfg, seed=seed)[1]
            res = sim.run(cfg, params, state, args.steps, impl=args.impl,
                          seed=seed)
            per_spikes.append(float(res.spikes))
            per_events.append(float(res.events))
        return {"spikes": sum(per_spikes), "events": sum(per_events),
                "per_tenant_spikes": per_spikes,
                "per_tenant_events": per_events}
    params, state = sim.build(cfg)
    res = sim.run(cfg, params, state, args.steps, impl=args.impl)
    return {"spikes": float(res.spikes), "events": float(res.events)}


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="spawn N local ranks of the multi-process DPSNN "
                    "runtime (the paper's mpirun analogue)")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port (0 = pick a free one)")
    ap.add_argument("--timeout", type=int, default=900,
                    help="per-job wall limit, seconds")
    ap.add_argument("--json", default="",
                    help="append the metrics row to this JSON-lines file "
                         "('-' prints the row to stdout)")
    ap.add_argument("--no-check-single", dest="check_single",
                    action="store_false",
                    help="skip the bitwise single-process equality check")
    # fault-tolerant supervisor mode (README §Recovery quickstart)
    ap.add_argument("--supervise", action="store_true",
                    help="supervised run: periodic checkpoints, heartbeat "
                         "monitoring, automatic restart from the last "
                         "checkpoint on worker death")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint cadence in steps (required with "
                         "--supervise)")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory (default: a fresh temp "
                         "dir; pass an existing one to resume a run)")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0,
                    help="restart when no rank advances a chunk boundary "
                         "for this many seconds")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--restart-ranks", type=int, default=0,
                    help="relaunch on this many ranks after a failure "
                         "(0 = same size; the checkpoint is resharded "
                         "through the global coordinate system)")
    ap.add_argument("--chaos-kill-rank", type=int, default=-1,
                    help="fault injection: SIGKILL this rank at "
                         "--chaos-at-step on the FIRST attempt "
                         "(EXPERIMENTS.md §Recovery; used by the chaos "
                         "CI tier)")
    ap.add_argument("--chaos-at-step", type=int, default=-1,
                    help="chunk boundary at which the chaos kill fires")
    ap.add_argument("--chaos-flip-bit", default="",
                    metavar="RING:STEP:WORD",
                    help="integrity chaos (requires --guard --supervise): "
                         "flip one bit in a halo payload on the FIRST "
                         "attempt; the guard detects it, refuses the "
                         "checkpoint, and the restart rolls back clean")
    ap.add_argument("--chaos-nan-at-step", type=int, default=-1,
                    help="integrity chaos (requires --guard --supervise): "
                         "poison one membrane voltage with NaN at this "
                         "step on the FIRST attempt")
    add_workload_args(ap)
    return ap


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    # the in-process single-process reference runs where the ranks run:
    # on the CPU, so the bitwise comparison compares like with like
    import jax
    jax.config.update("jax_platforms", "cpu")

    if args.ranks_per_node and args.batch:
        raise SystemExit(
            "--ranks-per-node cannot be combined with --batch: the "
            "batched service runs on the flat row-major mesh "
            "(DESIGN.md §Hierarchy)")
    if args.supervise:
        row = supervise(args)
        print(f"ranks={row['rank_count']} grid={row['grid']} "
              f"tile={row['tile']} neurons={row['neurons']} "
              f"steps={row['steps']} spikes={row['spikes']:.0f} "
              f"rate={row['rate_hz']:.2f}Hz isi_cv={row['isi_cv']:.3f} "
              f"restarts={row['restarts']} lost_steps={row['lost_steps']} "
              f"resumed_from={row['resumed_from_step']} "
              f"wall={row['supervised_wall_s']:.1f}s")
    else:
        row = launch(args)
        print(f"ranks={row['rank_count']} grid={row['grid']} "
              f"tile={row['tile']} neurons={row['neurons']} "
              f"steps={row['steps']} step_ms={row['step_ms']:.2f} "
              f"events/s={row['events_per_s']:.3e} "
              f"spikes={row['spikes']:.0f} "
              f"wire={row['exchange_mode']} "
              f"({row['halo_payload_bytes_per_step']} B/step/rank)")

    status = 0
    if row.get("aer_saturated_steps"):
        # truncated-but-flagged AER sends: the run is degraded and the
        # bitwise check below is expected to fail — say why first
        print(f"AER-SATURATED on {row['aer_saturated_steps']}/"
              f"{row['steps']} steps: event lists overflowed the "
              f"capacity bound (raise --aer-rate-bound)")
    if args.check_single:
        ref = single_process_reference(args)
        if args.batch:
            # per-tenant: every batch slot must match its dedicated
            # single-tenant single-process run bitwise
            ok = (row["per_tenant_spikes"] == ref["per_tenant_spikes"]
                  and row["per_tenant_events"] == ref["per_tenant_events"])
        else:
            ok = (row["spikes"] == ref["spikes"]
                  and row["events"] == ref["events"])
        row["single_process_match"] = ok
        if ok and args.batch:
            print(f"BITWISE-EQUAL vs {args.batch} single-tenant "
                  f"single-process runs (per-tenant spikes="
                  f"{ref['per_tenant_spikes']})")
        elif ok:
            print(f"BITWISE-EQUAL vs single-process "
                  f"(spikes={ref['spikes']:.0f}, events={ref['events']:.0f})")
        elif args.batch:
            print(f"MISMATCH vs single-tenant runs: multi per-tenant "
                  f"spikes={row['per_tenant_spikes']} != "
                  f"single {ref['per_tenant_spikes']}")
            status = 1
        else:
            print(f"MISMATCH vs single-process: multi "
                  f"spikes={row['spikes']} events={row['events']} != "
                  f"single spikes={ref['spikes']} events={ref['events']}")
            status = 1

    if args.json == "-":
        print(json.dumps(row, sort_keys=True))
    elif args.json:
        with open(args.json, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    return status


if __name__ == "__main__":
    sys.exit(main())
