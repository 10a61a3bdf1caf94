"""Paper Figs 1-3: speed-up, strong scaling, weak scaling + realtime.

Two data sources, reported side by side:

* **measured** — wall-clock runs of this JAX implementation on this host
  (single CPU core; multi-"device" points use forced host devices and
  share the core, so they measure overhead, not speed-up — labelled
  as such).
* **modelled** — the TPU-v5e roofline model fed by the dry-run artifacts
  (per-device FLOPs/bytes/collective bytes), which is what the paper's
  1024-core curves map onto for this port. The serial anchor is the
  measured single-core seconds-per-synaptic-event, directly comparable
  to the paper's 2.75e-7 s/event single-core figure (Fig 2).

Both **connectivity families** report side by side (EXPERIMENTS.md
§Families): the 2015 paper's Gaussian short-range stencil and the
lineage papers' Gaussian+exponential long-range profile
(arXiv:1512.05264 / arXiv:1803.08833), whose wider halo exercises the
multi-ring exchange (DESIGN.md §2).

**Rank sweep** (``--mode sweep``, in ``all``): the paper's actual
experiment — N OS processes exchanging real messages. Ranks 1/2/4(/8)
run for real through ``launch/launch_distributed.py`` (jax.distributed
+ gloo, one process per rank); the 16→1024 points are modelled from the
**measured comm/compute split** of those runs applied to the paper's
Tables 1–2 geometry (``RANK_TILE_PAPER``: ~11M neurons / ~20G synapses
at 1024 ranks). Every sweep row carries the stable BENCH schema
``{rank_count, mode, step_ms, events_per_s, efficiency}`` that
``benchmarks/compare.py`` gates on (EXPERIMENTS.md §Scaling-1024),
plus ``exchange_mode`` since PR 4; ``--exchange-mode both`` (the
nightly pipeline) runs the measured points once per spike-halo wire
format (dense bit-packed vs AER sparse, DESIGN.md §AER).

**Payload mode** (``--mode payload``, in ``all``): dense-vs-AER wire
bytes across firing rates and rank counts — the measured rate comes
from driving the network harder (``nu_ext_hz`` sweep), the bytes from
the exact accounting in ``runtime/compression.py``, and the predicted
dense/AER crossover rate is *reported*, not guessed
(EXPERIMENTS.md §Payload).

**Kernels mode** (``--mode kernels``, in ``all``): per-kernel
microbenchmark on the bench-smoke geometry — the four unfused stage
kernels (lif / matmul / gather / stdp, plus the jnp trace update)
timed individually against the fused column-step megakernel
(``kernels/fused_step.py``, DESIGN.md §Fusion), with a summary row
comparing the fused time to the sum of the stages it replaces
(EXPERIMENTS.md §Kernels). Since PR 5 the measured sweep also threads
``--impl`` (ref / pallas / pallas_fused) and ``--pipelined`` so fused
vs unfused rows land side by side in the nightly trajectory artifact;
``benchmarks/compare.py`` keys rows on ``impl``.

**Topology mode** (``--mode topology``, in ``all``): flat vs
hierarchical two-level halo exchange (DESIGN.md §Hierarchy) — measured
4-rank flat-vs-``--ranks-per-node 2`` step times on the wide-halo
gauss_exp family across a radius (ring-count) sweep, next to the exact
node-seam byte/message accounting (``runtime/compression.
internode_totals``), then the paper's 16..1024-rank problem modelled
with inter-node rings charged at datacenter-network cost and
intra-node traffic at chip-interconnect cost; every row embeds the
per-ring dense/AER selection table behind ``--exchange-mode auto``
(EXPERIMENTS.md §Topology).

**Batch mode** (``--mode batch``, in ``all``): the multi-tenant
amortization sweep (DESIGN.md §Service) — B tenant networks in
lockstep under one vmap of the single-shard step, sharing one
weights/ELL read per column tile. Rows carry ``batch_size`` (the new
compare.py key, absent == 1), the amortized events/s/tenant, the
per-tenant-step HBM-read accounting, and the B=1 row's bitwise-parity
bit against the plain single-tenant path (EXPERIMENTS.md §Batched).

Run:  PYTHONPATH=src python -m benchmarks.scaling --mode all --quick
      [--json BENCH_scaling.json]   # machine-readable rows (CI artifact)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

from repro.configs.base import DPSNNConfig  # noqa: E402
from repro.configs.dpsnn import with_family  # noqa: E402

PEAK = 197e12
HBM = 819e9
ICI = 50e9

#: families reported side by side (name -> ConnectivityConfig)
BENCH_FAMILIES = ("gauss", "gauss_exp")

#: collected machine-readable rows ({"mode", "family", ...}); --json dumps
ROWS: list = []


def emit(mode: str, text: str, **row):
    print(text)
    if row:
        ROWS.append({"mode": mode, **row})


def _stencil_radius(cfg: DPSNNConfig) -> int:
    from repro.core.connectivity import build_stencil
    return build_stencil(cfg).radius


def measure_single(cfg: DPSNNConfig, steps: int = 200, impl="ref"):
    """Single-shard wall time + paper metrics on this host.

    Honors ``cfg.stdp``: a plastic run measures the full STDP update
    (trace decay + dense outer products + remote gather-update) riding
    every step, the configuration benchmarked by the DPSNN-STDP lineage
    papers (arXiv:1310.8478, EURETILE D7.3).
    """
    from repro.core import metrics as M
    from repro.core import simulation as sim

    params, state = sim.build(cfg)
    # warm with the SAME steps value: n_steps is a static jit arg, so a
    # different warm-up length would leave the compile inside the timing
    r = sim.run(cfg, params, state, steps, impl=impl)
    r.rate_hz.block_until_ready()
    t0 = time.perf_counter()
    r = sim.run(cfg, params, state, steps, impl=impl)
    r.rate_hz.block_until_ready()
    dt = time.perf_counter() - t0
    events = float(r.events)
    return {
        "grid": f"{cfg.grid_h}x{cfg.grid_w}",
        "neurons": cfg.n_neurons,
        "syn_equiv": cfg.total_equivalent_synapses,
        "steps": steps,
        "wall_s": dt,
        "rate_hz": float(r.rate_hz),
        "events": events,
        "s_per_event": dt / max(events, 1),
        "events_per_s": events / max(dt, 1e-12),
        "realtime_factor": M.realtime_factor(dt, steps, cfg.neuron.dt_ms),
        "bytes_per_syn": M.bytes_per_synapse(cfg, params, r.state),
    }


def roofline_model_step_time(cfg: DPSNNConfig, p_cores: int,
                             rate_hz: float = 4.0, plastic: bool = False):
    """Per-step time model on the TPU target for P devices (1-D..2-D tile
    decomposition as in core/partition.py).

    compute: dense local delivery 2*C*N^2 + remote 2*C*N*K + neuron ~20*C*N
    memory:  weights read once per step (dominant) + state
    collective: bit-packed halo (perimeter columns x N/8 bytes), message
    count = 2 rings per direction per axis (multi-ring when the tile is
    thinner than the stencil radius, DESIGN.md §2). The halo radius is
    the *active-stencil* radius, not the conn.radius bounding box.

    With ``plastic`` (STDP on, EXPERIMENTS.md §Perf): the dense update
    adds two rank-1 outer products + clip (~4*C*N^2 FLOPs), the remote
    update a K-way gather-update (~4*C*N*K), weights are *written back*
    every step (2x weight bytes), and the f32 pre-trace halo strips ride
    the same messages (32x the bit-packed spike bytes).
    """
    n = cfg.neurons_per_column
    c_tot = cfg.n_columns
    c = c_tot / p_cores
    flops = 2 * c * n * n + 2 * c * n * cfg.remote_fanin + 20 * c * n
    wbytes = 2 * c * n * n + 6 * c * n * cfg.remote_fanin   # bf16 + ELL
    sbytes = 16 * c * n
    # tile perimeter (same closest-to-square 2-D factorization the
    # multi-process runtime places ranks with)
    from repro.core.partition import process_grid
    py, px = process_grid(p_cores)
    th, tw = cfg.grid_h / py, cfg.grid_w / px
    r = _stencil_radius(cfg)
    halo_cols = 2 * r * (th + tw + 2 * r)
    halo_bytes = halo_cols * (n / 8)                        # bit-packed
    if plastic:
        flops += 4 * c * n * n + 4 * c * n * cfg.remote_fanin
        wbytes *= 2                                         # read + write
        sbytes += 8 * c * n                                 # pre/post traces
        halo_bytes += halo_cols * 4 * n                     # f32 traces
    # chained rings serialize: each ring pays a hop latency, and a tile
    # thinner than the radius needs ceil(r/tile) rings per direction
    rings = (math.ceil(r / max(th, 1e-9)) + math.ceil(r / max(tw, 1e-9)))
    n_msgs = 2 * rings
    lat = n_msgs * 1e-6                                     # ~1us per hop
    return {
        "compute": flops / PEAK,
        "memory": (wbytes + sbytes) / HBM,
        "collective": halo_bytes / ICI + lat,
    }


def model_speedup(cfg: DPSNNConfig, cores_list, plastic: bool = False):
    t1 = roofline_model_step_time(cfg, 1, plastic=plastic)
    base = max(t1.values())
    rows = []
    for p in cores_list:
        t = roofline_model_step_time(cfg, p, plastic=plastic)
        step = max(t["compute"], t["memory"]) + t["collective"]
        rows.append({"cores": p, "step_s": step,
                     "speedup": base / step,
                     "terms": t})
    return rows


def _family_cfg(base: DPSNNConfig, family: str) -> DPSNNConfig:
    cfg = with_family(base, family)
    if base.grid_h <= 12:
        # test-host grids: shrink the exponential tail's stencil bound to
        # keep the laptop measurement tractable (same profile family)
        conn = dataclasses.replace(cfg.conn, radius=min(cfg.conn.radius, 3))
        cfg = dataclasses.replace(cfg, conn=conn)
    return cfg


def mode_strong(args):
    print("grid,family,cores,s_per_event,speedup,source")
    # measured single-core anchors (reduced grids sized for this host),
    # static and plastic side by side — the paper lineage benchmarks both
    # configurations (arXiv:1310.8478 reports the STDP-on numbers)
    grids = [(8, 8, 64), (12, 12, 64)] if args.quick else \
        [(8, 8, 64), (12, 12, 64), (24, 24, 1240)]
    anchors = {}
    for gh, gw, n in grids:
        base = DPSNNConfig(grid_h=gh, grid_w=gw, neurons_per_column=n)
        steps = 100 if n > 500 else 300
        for family in BENCH_FAMILIES:
            cfg = _family_cfg(base, family)
            m = measure_single(cfg, steps=steps)
            m["family"] = family
            m["halo_radius"] = _stencil_radius(cfg)
            anchors[(m["grid"], family)] = m
            emit("strong",
                 f"{m['grid']},{family},1,{m['s_per_event']:.3e},1.0,"
                 f"measured-host",
                 source="measured-host", cores=1, **m)
            mp = measure_single(dataclasses.replace(cfg, stdp=True),
                                steps=steps)
            emit("strong",
                 f"{m['grid']},{family},1,{mp['s_per_event']:.3e},1.0,"
                 f"measured-host-stdp",
                 source="measured-host-stdp", cores=1, family=family,
                 **{k: v for k, v in mp.items() if k != "family"})
            print(f"# {m['grid']}/{family} events/s: "
                  f"static {m['events_per_s']:.3e}, "
                  f"plastic {mp['events_per_s']:.3e} "
                  f"({mp['events_per_s']/max(m['events_per_s'],1e-12):.2f}x)")
    # modelled TPU curves for the paper's grids (static + plastic)
    for grid, gh in (("24x24", 24), ("48x48", 48), ("96x96", 96)):
        for family in BENCH_FAMILIES:
            cfg = with_family(DPSNNConfig(grid_h=gh, grid_w=gh), family)
            rate = 4.0
            ev_per_step = (cfg.recurrent_synapses * rate
                           + cfg.n_neurons * cfg.c_ext * cfg.nu_ext_hz) * 1e-3
            cores = [1, 4, 16, 64, 96, 256, 1024]
            for plastic, tag in ((False, "modelled-v5e"),
                                 (True, "modelled-v5e-stdp")):
                for row in model_speedup(cfg, cores, plastic=plastic):
                    spe = row["step_s"] / ev_per_step
                    emit("strong",
                         f"{grid},{family},{row['cores']},{spe:.3e},"
                         f"{row['speedup']:.1f},{tag}",
                         source=tag, grid=grid, family=family,
                         cores=row["cores"], s_per_event=spe,
                         speedup=row["speedup"], terms=row["terms"],
                         syn_equiv=cfg.total_equivalent_synapses,
                         halo_radius=_stencil_radius(cfg))
    if ("24x24", "gauss") in anchors:
        ours = anchors[("24x24", "gauss")]["s_per_event"]
        print(f"# paper single-core 24x24: 2.75e-07 s/event; "
              f"ours (1 CPU core, JAX): {ours:.2e}")


def mode_weak(args):
    """Fixed load/core: grid side scales with sqrt(P)."""
    print("cores,grid,family,s_per_event_per_core,source")
    n = 64
    for family in BENCH_FAMILIES:
        base = None
        for p, side in [(1, 6), (4, 12), (16, 24)]:
            cfg = with_family(
                DPSNNConfig(grid_h=side, grid_w=side, neurons_per_column=n),
                family)
            t = roofline_model_step_time(cfg, p)
            step = max(t["compute"], t["memory"]) + t["collective"]
            rate = 4.0
            ev = (cfg.recurrent_synapses * rate
                  + cfg.n_neurons * cfg.c_ext * cfg.nu_ext_hz) * 1e-3
            v = step / (ev / p)
            base = base or v
            emit("weak",
                 f"{p},{side}x{side},{family},{v:.3e},modelled-v5e "
                 f"(ideal flat: {v/base:.2f}x)",
                 source="modelled-v5e", cores=p, grid=f"{side}x{side}",
                 family=family, s_per_event_per_core=v, flatness=v / base)


def mode_realtime(args):
    for family in BENCH_FAMILIES:
        cfg = with_family(DPSNNConfig(grid_h=96, grid_w=96), family)
        for p in (256, 512, 1024):
            t = roofline_model_step_time(cfg, p)
            step = max(t["compute"], t["memory"]) + t["collective"]
            rt = step / (cfg.neuron.dt_ms * 1e-3)
            emit("realtime",
                 f"96x96/{family} @ {p} chips: {rt:.2f}x realtime "
                 f"(paper: ~11x at 1024 Xeon cores)",
                 family=family, cores=p, realtime_factor=rt,
                 source="modelled-v5e")


# ---------------------------------------------------------------------------
# Rank sweep: real multi-process runs + modelled 16..1024 extension
# ---------------------------------------------------------------------------

#: modelled rank counts extending the measured sweep to the paper's range
MODEL_RANKS = (16, 32, 64, 128, 256, 512, 1024)

#: the AER capacity rate bound used for benchmark runs: generous enough
#: that the reduced benchmark networks (~10-20 Hz) never saturate, so
#: measured AER rows time the true wire format, not truncation
BENCH_AER_RATE_BOUND = 100.0


def _launch_ranks(ranks: int, grid: str, neurons: int, steps: int,
                  weak: bool, timed_reps: int = 5,
                  exchange_mode: str = "dense_packed",
                  impl: str = "ref", pipelined: bool = False,
                  family: str = "gauss", radius: int = 0,
                  ranks_per_node: int = 0, guard: bool = False) -> dict:
    """One real multi-process point via the launcher, in-process (the
    launcher spawns the fresh worker interpreters + coordinator itself;
    the equality check is CI's job, not the bench's)."""
    from repro.launch.launch_distributed import launch, make_parser

    argv = ["--ranks", str(ranks), "--grid", grid,
            "--neurons", str(neurons), "--steps", str(steps),
            "--no-check-single", "--timed-reps", str(timed_reps),
            "--exchange-mode", exchange_mode, "--impl", impl,
            "--family", family]
    if radius:
        argv += ["--radius", str(radius)]
    if ranks_per_node:
        argv += ["--ranks-per-node", str(ranks_per_node)]
    if exchange_mode in ("aer_sparse", "auto"):
        argv += ["--aer-rate-bound", str(BENCH_AER_RATE_BOUND)]
    if pipelined:
        argv.append("--pipelined")
    if guard:
        argv.append("--guard")
    if weak:
        argv.append("--weak")
    return launch(make_parser().parse_args(argv))


def _halo_bytes_per_step(cfg: DPSNNConfig, ranks: int,
                         exchange_mode: str = "dense_packed",
                         rate_bound_hz: float | None = None) -> float:
    """Per-rank halo wire bytes per step under the 2-D process-grid
    tiling (the collective term of the measured split) — the exact
    accounting from runtime/compression.py, per wire format.

    ``rate_bound_hz`` must match what the run being normalized/modelled
    actually ships: the *measured* bench points run at
    ``BENCH_AER_RATE_BOUND`` (saturation-proof for the fast reduced
    nets), while the modelled paper-geometry points represent the
    ~7.5 Hz cortical operating regime and are priced at the config's
    default bound (None)."""
    from repro.core.partition import make_rank_tile_spec
    from repro.runtime.compression import halo_payload_bytes

    spec = make_rank_tile_spec(cfg, ranks)
    return float(halo_payload_bytes(
        cfg, spec, mode=exchange_mode, rate_bound_hz=rate_bound_hz
    )["bytes_per_step"])


def _events_per_step(cfg: DPSNNConfig, rate_hz: float = 4.0) -> float:
    return (cfg.recurrent_synapses * rate_hz
            + cfg.n_neurons * cfg.c_ext * cfg.nu_ext_hz) * 1e-3


def _sweep_exchange_modes(args) -> list:
    if args.exchange_mode == "both":
        return ["dense_packed", "aer_sparse"]
    return [args.exchange_mode]


def mode_sweep(args):
    """Strong + weak rank sweep: measured 1/2/4(/8) real-process points,
    then the paper's 16..1024 points modelled from the measured split —
    once per spike-halo wire format with ``--exchange-mode both``.

    Split protocol: the 1-rank run fixes the serial per-event compute
    cost; each multi-rank run's excess over perfect division
    (``t_P - t_1/P`` strong, ``t_P - t_1`` weak) is attributed to the
    process-spanning halo exchange and normalized per halo byte. The
    modelled points apply those two measured coefficients to the paper
    geometry (strong: the full Table 1 grid; weak: RANK_TILE_PAPER per
    rank — ~11M neurons / ~20G synapses at 1024).
    """
    from repro.configs.dpsnn import RANK_TILE_PAPER, with_ranks

    # steps are sized so each timed rep runs long enough (hundreds of ms)
    # that scheduler noise doesn't dominate; min-of-reps in the worker
    # (runtime/multiprocess.worker_run) filters the rest
    measured_ranks = [1, 2, 4] if args.quick else [1, 2, 4, 8]
    gh, gw, neurons, steps = ((8, 8, 48, 150) if args.quick
                              else (12, 12, 64, 250))
    tile_h, tile_w, tile_n, weak_steps = ((4, 4, 48, 300) if args.quick
                                          else (6, 6, 64, 400))

    print("mode,rank_count,grid,step_ms,events_per_s,efficiency,source,"
          "exchange_mode,impl")

    def sweep(mode: str, weak: bool, xmode: str):
        from repro.core.partition import process_grid

        base = None
        rows = []
        for p in measured_ranks:
            ry, rx = process_grid(p)
            if not weak and (gh % ry or gw % rx):
                continue
            g = f"{tile_h}x{tile_w}" if weak else f"{gh}x{gw}"
            n = tile_n if weak else neurons
            row = _launch_ranks(p, g, n, weak_steps if weak else steps,
                                weak, exchange_mode=xmode,
                                impl=args.impl, pipelined=args.pipelined)
            base = base or row
            if weak:
                eff = base["step_ms"] / row["step_ms"]
            else:
                eff = base["step_ms"] / (p * row["step_ms"])
            emit(mode,
                 f"{mode},{p},{row['grid']},{row['step_ms']:.3f},"
                 f"{row['events_per_s']:.3e},{eff:.3f},measured-mp,{xmode},"
                 f"{args.impl}",
                 source="measured-mp", rank_count=p, grid=row["grid"],
                 neurons=row["neurons"], syn_equiv=row["syn_equiv"],
                 step_ms=row["step_ms"], events_per_s=row["events_per_s"],
                 efficiency=eff, spikes=row["spikes"],
                 events=row["events"], steps=row["steps"],
                 exchange_mode=xmode, impl=args.impl,
                 pipelined=args.pipelined,
                 halo_bytes=row["halo_payload_bytes_per_step"],
                 aer_saturated_steps=row.get("aer_saturated_steps", 0))
            rows.append(row)
        return rows

    for xmode in _sweep_exchange_modes(args):
        strong_rows = sweep("strong", weak=False, xmode=xmode)
        sweep("weak", weak=True, xmode=xmode)

        # ---- measured comm/compute split -> paper 16..1024 points
        t1 = strong_rows[0]
        s_per_event = (t1["step_ms"] * 1e-3) / (t1["events"] / t1["steps"])
        meas_cfg = DPSNNConfig(grid_h=gh, grid_w=gw,
                               neurons_per_column=neurons, seed=0)
        comm_samples = []
        for row in strong_rows[1:]:
            p = row["rank_count"]
            comm_s = max(row["step_ms"] - t1["step_ms"] / p, 0.0) * 1e-3
            # normalize by the bytes the measured runs ACTUALLY shipped
            # (they ran at the saturation-proof BENCH_AER_RATE_BOUND)
            comm_samples.append(comm_s / _halo_bytes_per_step(
                meas_cfg, p, xmode,
                rate_bound_hz=(BENCH_AER_RATE_BOUND
                               if xmode == "aer_sparse" else None)))
        s_per_halo_byte = (sorted(comm_samples)[len(comm_samples) // 2]
                           if comm_samples else 0.0)
        emit("sweep-split",
             f"# measured split [{xmode}/{args.impl}]: {s_per_event:.3e} "
             f"s/event compute, {s_per_halo_byte:.3e} s/halo-byte comm",
             source="measured-mp", s_per_event=s_per_event,
             s_per_halo_byte=s_per_halo_byte, exchange_mode=xmode,
             impl=args.impl, pipelined=args.pipelined)

        # strong @ paper grid: fixed 96x96x1240 problem over P ranks
        paper_cfg = with_ranks(RANK_TILE_PAPER, 1024)  # 96x96 Table 1 run
        ev_step = _events_per_step(paper_cfg)
        t1_model = ev_step * s_per_event
        for p in MODEL_RANKS:
            step_s = (t1_model / p
                      + _halo_bytes_per_step(paper_cfg, p, xmode)
                      * s_per_halo_byte)
            eff = t1_model / (p * step_s)
            emit("strong",
                 f"strong,{p},{paper_cfg.grid_h}x{paper_cfg.grid_w},"
                 f"{step_s * 1e3:.3f},{ev_step / step_s:.3e},{eff:.3f},"
                 f"modelled-from-measured,{xmode},{args.impl}",
                 source="modelled-from-measured", rank_count=p,
                 grid=f"{paper_cfg.grid_h}x{paper_cfg.grid_w}",
                 neurons=paper_cfg.n_neurons,
                 syn_equiv=paper_cfg.total_equivalent_synapses,
                 step_ms=step_s * 1e3, events_per_s=ev_step / step_s,
                 efficiency=eff, exchange_mode=xmode, impl=args.impl,
                 pipelined=args.pipelined)

        # weak @ paper tile: RANK_TILE_PAPER per rank, grid grows with P
        t1_tile = _events_per_step(RANK_TILE_PAPER) * s_per_event
        for p in MODEL_RANKS:
            cfg_p = with_ranks(RANK_TILE_PAPER, p)
            step_s = (t1_tile
                      + _halo_bytes_per_step(cfg_p, p, xmode)
                      * s_per_halo_byte)
            eff = t1_tile / step_s
            emit("weak",
                 f"weak,{p},{cfg_p.grid_h}x{cfg_p.grid_w},"
                 f"{step_s * 1e3:.3f},"
                 f"{_events_per_step(cfg_p) / step_s:.3e},{eff:.3f},"
                 f"modelled-from-measured,{xmode},{args.impl}",
                 source="modelled-from-measured", rank_count=p,
                 grid=f"{cfg_p.grid_h}x{cfg_p.grid_w}",
                 neurons=cfg_p.n_neurons,
                 syn_equiv=cfg_p.total_equivalent_synapses,
                 step_ms=step_s * 1e3,
                 events_per_s=_events_per_step(cfg_p) / step_s,
                 efficiency=eff, exchange_mode=xmode, impl=args.impl,
                 pipelined=args.pipelined)


# ---------------------------------------------------------------------------
# Kernels mode: per-stage microbenchmark, unfused stages vs the megakernel
# ---------------------------------------------------------------------------

def _bench_call(fn, *a, iters: int = 10):
    import jax
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def mode_kernels(args):
    """Per-kernel microbenchmark on the bench-smoke geometry: the
    unfused per-step stage kernels (lif_step / synapse_matmul /
    stdp_dense_update, plus the jnp trace update) timed individually
    against one fused column-step megakernel call (kernels/fused_step.py)
    on the SAME warm state. The remote ELL gather is the same XLA gather
    in both schedules and is timed once, as its own row.

    On a CPU host every Pallas kernel runs in interpret mode, so the
    absolute microseconds are not TPU predictions — but the comparison
    is apples-to-apples (same mode, same inputs) and measures exactly
    what the fusion removes: per-kernel dispatch and the (C, N)
    state/spike round-trips between stages (EXPERIMENTS.md §Kernels has
    the table and the TPU-side HBM-traffic argument).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import network as net
    from repro.core import simulation as sim_mod
    from repro.core.connectivity import build_stencil, neuron_types
    from repro.kernels import ops

    gh, gw, n = (8, 8, 48) if args.quick else (12, 12, 64)
    cfg = DPSNNConfig(grid_h=gh, grid_w=gw, neurons_per_column=n, seed=0,
                      stdp=True)
    scfg = cfg.stdp_cfg
    params, state0 = sim_mod.build(cfg)
    warm = sim_mod.run(cfg, params, state0, 25, impl="ref")
    state, params = warm.state, warm.params
    stencil = build_stencil(cfg)
    col_ids = jnp.arange(cfg.n_columns, dtype=jnp.int32)
    d = state.hist.shape[0]
    s_loc = jnp.take(state.hist,
                     (state.t - cfg.conn.min_delay_steps) % d, axis=0)
    s_flat = net.neighbour_table_single(state.hist, state.t, stencil,
                                        (gh, gw))
    ext, _ = net.external_drive(cfg, state.t, col_ids)
    remote = jax.jit(net.deliver_remote_ref)
    rem = remote(s_flat, params.rem_flat, params.rem_w)
    currents = net.deliver_local_ref(s_loc, params.w_local) + rem + ext
    lif, st = state.lif, state.stdp
    exc = (~neuron_types(cfg)).astype(s_loc.dtype)
    dp = jnp.exp(-cfg.neuron.dt_ms / scfg.tau_plus_ms).astype(s_loc.dtype)
    dm = jnp.exp(-cfg.neuron.dt_ms / scfg.tau_minus_ms).astype(s_loc.dtype)

    @jax.jit
    def trace_update(x_pre, x_post, spikes):
        return x_pre * dp + spikes, x_post * dm + spikes

    iters = 5 if args.quick else 10
    geom = dict(grid=f"{gh}x{gw}", neurons=cfg.n_neurons,
                syn_equiv=cfg.total_equivalent_synapses)
    print("kernel,impl,us_per_call")
    stages = {}
    for name, impl, fn, a in [
        ("lif_step", "pallas", lambda: ops.lif_step(
            cfg.neuron, lif.v, lif.c, lif.refrac, currents), ()),
        ("synapse_matmul", "pallas", lambda: ops.synapse_matmul(
            s_loc, params.w_local), ()),
        ("remote_gather", "xla", lambda: remote(
            s_flat, params.rem_flat, params.rem_w), ()),
        ("trace_update", "jnp", lambda: trace_update(
            st.x_pre, st.x_post, s_loc), ()),
        ("stdp_dense_update", "pallas", lambda: ops.stdp_dense_update(
            params.w_local, st.x_pre * exc[None, :], s_loc * exc[None, :],
            s_loc, st.x_post, a_plus=scfg.a_plus, a_minus=scfg.a_minus,
            lr=scfg.lr, w_max=scfg.w_max_factor * cfg.conn.j_exc), ()),
        ("fused_step", "pallas_fused", lambda: ops.fused_step(
            cfg.neuron, lif.v, lif.c, lif.refrac, s_loc, params.w_local,
            rem, ext, st.x_pre, st.x_post, scfg=scfg), ()),
    ]:
        us = _bench_call(fn, *a, iters=iters) * 1e6
        stages[name] = us
        emit("kernels", f"{name},{impl},{us:.0f}",
             source="measured-host-interpret", kernel=name, impl=impl,
             us_per_call=us, **geom)
    unfused = (stages["lif_step"] + stages["synapse_matmul"]
               + stages["trace_update"])
    speedup = unfused / max(stages["fused_step"], 1e-9)
    emit("kernels",
         f"# fused {stages['fused_step']:.0f} us vs unfused stage sum "
         f"{unfused:.0f} us -> {speedup:.2f}x "
         f"(lif+matmul+trace; the remote gather and stdp_dense_update "
         f"run in both schedules)",
         source="measured-host-interpret", kernel="fused_vs_unfused",
         impl="pallas_fused", fused_us=stages["fused_step"],
         unfused_sum_us=unfused, speedup=speedup, **geom)


# ---------------------------------------------------------------------------
# Batch mode: multi-tenant amortization sweep (DESIGN.md §Service)
# ---------------------------------------------------------------------------

def mode_batch(args):
    """Batched multi-tenant amortization sweep: events/s/tenant vs B.

    B tenants advance in lockstep under one vmap of the single-shard
    step (``core/batched.run_batched``), sharing one read of the
    weights + ELL connectivity per column tile. Each row reports the
    **amortized per-tenant throughput** — every tenant costs ``wall/B``
    seconds of machine time for its ``steps`` steps, so per-tenant
    events/s is total tenant events over the batch wall time; it
    improves with B exactly as the shared reads and per-step dispatch
    amortize (``amortization_x`` is the ratio to the B=1 row).

    The HBM accounting per tenant-step rides along: the shared
    weight/ELL bytes divide by B while per-tenant state bytes do not
    (EXPERIMENTS.md §Batched walks the arithmetic) — under ``--stdp``
    the weights are per-tenant copies and stop amortizing, which the
    ``shared_weight_bytes`` column makes visible.

    The B=1 row re-checks the bitwise guarantee against the plain
    ``simulation.run`` path (full final state compared leaf-wise) —
    the same contract tests/test_batched_service.py locks in.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import batched, counters
    from repro.core import simulation as sim

    gh, gw, n = (8, 8, 48) if args.quick else (12, 12, 64)
    steps = 100 if args.quick else 200
    batches = [1, 2, 4] if args.quick else [1, 2, 4, 8]
    cfg = DPSNNConfig(grid_h=gh, grid_w=gw, neurons_per_column=n, seed=0)
    params, state0 = sim.build(cfg)
    shared_bytes = sum(int(np.asarray(x).nbytes) for x in params)
    state_bytes = sum(int(np.asarray(x).nbytes)
                      for x in jax.tree_util.tree_leaves(state0))

    # the B=1 parity target: the plain single-tenant path, same seed
    ref = sim.run(cfg, params, state0, steps, impl=args.impl)
    jax.block_until_ready(ref.rate_hz)

    print("batch_size,impl,step_ms,events_per_s_per_tenant,"
          "amortization_x,hbm_bytes_per_tenant_step,b1_bitwise_match")
    base = None
    for b in batches:
        seeds = cfg.seed + jnp.arange(b, dtype=jnp.int32)
        bparams = batched.batch_params(cfg, params, b)
        bstate = batched.init_tenants(cfg, seeds)
        out = batched.run_batched(cfg, bparams, bstate, seeds, steps,
                                  args.impl)
        jax.block_until_ready(out.state.spike_count)   # compile + warm
        t0 = time.perf_counter()
        out = batched.run_batched(cfg, bparams, bstate, seeds, steps,
                                  args.impl)
        jax.block_until_ready(out.state.spike_count)
        wall = time.perf_counter() - t0
        per_spikes = [float(x) for x in counters.value(out.state.spike_count)]
        per_events = [float(x) for x in counters.value(out.state.event_count)]
        total_events = sum(per_events)
        # amortized per-tenant throughput: each tenant's run costs
        # wall/B machine-seconds -> mean_tenant_events / (wall/B)
        evps_t = total_events / max(wall, 1e-12)
        base = base or evps_t
        # per tenant-step HBM reads: shared weights/ELL divide by B
        # (they are per-tenant copies under stdp), state does not
        hbm = (shared_bytes * (1 if cfg.stdp else 1 / b)) + state_bytes
        b1 = None
        if b == 1:
            got = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                lambda x: np.asarray(x[0]), out.state))
            want = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                np.asarray, ref.state))
            b1 = bool(all(np.array_equal(g, w)
                          for g, w in zip(got, want)))
        emit("batch",
             f"{b},{args.impl},{wall / steps * 1e3:.3f},{evps_t:.3e},"
             f"{evps_t / base:.2f},{hbm:.0f},"
             f"{'' if b1 is None else int(b1)}",
             source="measured", batch_size=b, impl=args.impl,
             grid=f"{gh}x{gw}", neurons=cfg.n_neurons,
             syn_equiv=cfg.total_equivalent_synapses, steps=steps,
             wall_s=wall, step_ms=wall / steps * 1e3,
             tenant_step_ms=wall / steps / b * 1e3,
             events=total_events, per_tenant_spikes=per_spikes,
             per_tenant_events=per_events,
             events_per_s=total_events / max(wall, 1e-12),
             events_per_s_per_tenant=evps_t,
             amortization_x=evps_t / base,
             shared_weight_bytes=shared_bytes,
             tenant_state_bytes=state_bytes,
             hbm_bytes_per_tenant_step=hbm,
             b1_bitwise_match=b1)
    if ROWS and ROWS[-1].get("mode") == "batch":
        first = next(r for r in ROWS if r.get("mode") == "batch")
        if first.get("b1_bitwise_match") is False:
            print("# WARNING: B=1 batched run is NOT bitwise-equal to "
                  "the single-tenant path")


# ---------------------------------------------------------------------------
# Payload mode: dense vs AER wire bytes across firing rates x rank counts
# ---------------------------------------------------------------------------

def mode_payload(args):
    """Dense-vs-AER halo payload across firing rates and rank counts.

    The firing rate is swept via the external input drive
    (``nu_ext_hz``) and *measured* on a reduced single-shard run; for
    each measured rate the AER capacity is bounded at that rate (x the
    config safety factor) and the exact per-rank wire bytes of both
    formats come from ``runtime/compression.halo_payload_bytes`` on the
    paper-geometry tile of each rank count. The predicted crossover rate
    (where the AER event list stops beating 32x bit-packing,
    DESIGN.md §AER) is reported in every row — below it the AER rows
    must win, which the lineage payload measurements (arXiv:1310.8478,
    arXiv:1408.4587) show is exactly the cortical-rate regime.
    """
    from repro.configs.dpsnn import RANK_TILE_PAPER, with_ranks
    from repro.core.partition import make_rank_tile_spec
    from repro.runtime.compression import (aer_crossover_rate_hz,
                                           halo_payload_bytes)

    drives = [1.5, 3.0, 9.0] if args.quick else [1.5, 3.0, 6.0, 12.0, 24.0]
    ranks = [4, 64, 1024] if args.quick else [4, 16, 64, 256, 1024]
    meas_steps = 150 if args.quick else 300
    base = DPSNNConfig(grid_h=8, grid_w=8, neurons_per_column=48, seed=0)
    # the fixed problem every row decomposes: the paper's 96x96 Table 1
    # grid, strong-split — the per-rank tile (and with it the boundary
    # surface) shrinks as ranks grow: 48x48 at 4 ranks, 3x3 at 1024
    paper_cfg = with_ranks(RANK_TILE_PAPER, 1024)

    print("nu_ext_hz,rate_hz,rank_count,grid,dense_B,aer_B,ratio,"
          "crossover_hz,aer_wins")
    for nu in drives:
        cfg_m = dataclasses.replace(base, nu_ext_hz=nu)
        m = measure_single(cfg_m, steps=meas_steps)
        rate = m["rate_hz"]
        for p in ranks:
            spec = make_rank_tile_spec(paper_cfg, p)
            dense = halo_payload_bytes(paper_cfg, spec, mode="dense_packed")
            aer = halo_payload_bytes(paper_cfg, spec, mode="aer_sparse",
                                     rate_bound_hz=rate)
            cross = aer_crossover_rate_hz(paper_cfg, spec)
            ratio = aer["bytes_per_step"] / dense["bytes_per_step"]
            wins = aer["bytes_per_step"] < dense["bytes_per_step"]
            emit("payload",
                 f"{nu},{rate:.2f},{p},{paper_cfg.grid_h}x"
                 f"{paper_cfg.grid_w},"
                 f"{dense['bytes_per_step']},{aer['bytes_per_step']},"
                 f"{ratio:.3f},{cross:.2f},{int(wins)}",
                 source="measured-rate+exact-accounting",
                 nu_ext_hz=nu, rate_hz=rate, rank_count=p,
                 grid=f"{paper_cfg.grid_h}x{paper_cfg.grid_w}",
                 dense_bytes_per_step=dense["bytes_per_step"],
                 aer_bytes_per_step=aer["bytes_per_step"],
                 payload_ratio=ratio, crossover_rate_hz=cross,
                 aer_wins=bool(wins),
                 n_messages=dense["n_messages"])
    cross = aer_crossover_rate_hz(paper_cfg,
                                  make_rank_tile_spec(paper_cfg, 1024))
    print(f"# predicted dense/AER crossover @1024 ranks: {cross:.2f} Hz "
          f"(static 1/(32*factor*dt) = "
          f"{1.0 / (32 * paper_cfg.conn.aer_capacity_factor * 1e-3):.2f} "
          f"Hz; paper's ~7.5 Hz cortical rates sit below it)")


# ---------------------------------------------------------------------------
# Topology mode: flat vs hierarchical two-level exchange, per-ring modes
# ---------------------------------------------------------------------------

#: modelled interconnect split for the topology sweep: intra-node rings
#: ride the chip interconnect (ICI above), inter-node rings the
#: datacenter network — slower per byte AND per message, the asymmetry
#: the two-level exchange trades against (DESIGN.md §Hierarchy)
ETH = 12.5e9                       # 100 GbE node-to-node
LAT_ICI = 1e-6                     # per-message hop latency, intra-node
LAT_ETH = 5e-6                     # per-message hop latency, inter-node

#: node-group size for the modelled 16..1024 topology sweep (4 ranks
#: per node matches the measured 4-rank/2-per-node point's factoring
#: style: one node row, groups along the fast axis)
TOPOLOGY_RANKS_PER_NODE = 4


def mode_topology(args):
    """Flat vs hierarchical two-level halo exchange (DESIGN.md
    §Hierarchy): payload bytes and step time vs ring count, plus the
    per-ring wire-format table behind ``--exchange-mode auto``.

    Measured part: 4 real OS-process ranks on the gauss_exp family
    (the wide-halo profile), radius swept so the exchange goes from
    single-ring to multi-ring — each radius runs once flat and once
    with ``--ranks-per-node 2`` (two node groups), same seed, and the
    row carries both step times next to the exact byte accounting
    (``runtime/compression.internode_totals``): the bytes that cross a
    node seam per step MUST be strictly fewer under the hierarchical
    exchange once the radius reaches 3 (the vertical-phase corner
    columns cross once per node instead of once per rank).

    Modelled part: the paper's 96x96 Table 1 problem over 16..1024
    ranks at ``TOPOLOGY_RANKS_PER_NODE`` ranks per node, charging
    inter-node rings at datacenter-network cost (``ETH``/``LAT_ETH``)
    and intra-node traffic at chip-interconnect cost
    (``ICI``/``LAT_ICI``) — the regime where coalescing pays. Every
    row embeds the node-level ``ring_mode_table`` so the JSON artifact
    records which rings resolved dense vs AER (EXPERIMENTS.md
    §Topology maps the columns to the paper's figures).
    """
    from repro.configs.dpsnn import RANK_TILE_PAPER, with_family, with_ranks
    from repro.core.partition import (make_node_spec, make_rank_tile_spec,
                                      process_grid)
    from repro.runtime.compression import (halo_payload_bytes,
                                           hier_payload_bytes,
                                           internode_totals,
                                           ring_mode_table,
                                           ring_send_entries)

    # ---- measured: 4 ranks, flat vs 2 node groups, radius sweep ----
    radii = [2, 4] if args.quick else [2, 4, 6]
    gh, gw, neurons = 8, 8, 32
    steps = 40 if args.quick else 80
    ry, rx = process_grid(4)
    print("radius,rings_flat,rings_node,flat_step_ms,hier_step_ms,"
          "internode_flat_B,internode_hier_B,internode_msgs_flat,"
          "internode_msgs_hier,hier_fewer_bytes")
    seam_ok = True
    for rad in radii:
        base = with_family(DPSNNConfig(grid_h=gh, grid_w=gw,
                                       neurons_per_column=neurons, seed=0),
                           "gauss_exp")
        cfg = dataclasses.replace(
            base, conn=dataclasses.replace(base.conn, radius=rad))
        spec = make_rank_tile_spec(cfg, 4)
        node = make_node_spec(ry, rx, 2)
        flat = _launch_ranks(4, f"{gh}x{gw}", neurons, steps, False,
                             impl=args.impl, family="gauss_exp",
                             radius=rad)
        hier = _launch_ranks(4, f"{gh}x{gw}", neurons, steps, False,
                             impl=args.impl, family="gauss_exp",
                             radius=rad, ranks_per_node=2)
        i_flat = internode_totals(cfg, spec, node, hierarchical=False,
                                  mode="dense_packed")
        i_hier = internode_totals(cfg, spec, node, hierarchical=True,
                                  mode="dense_packed")
        table = ring_mode_table(cfg, spec, node)
        fewer = i_hier["bytes_per_step"] < i_flat["bytes_per_step"]
        if rad >= 3 and not fewer:
            seam_ok = False
        emit("topology",
             f"{spec.radius},{len(ring_send_entries(spec))},{len(table)},"
             f"{flat['step_ms']:.3f},{hier['step_ms']:.3f},"
             f"{i_flat['bytes_per_step']},{i_hier['bytes_per_step']},"
             f"{i_flat['messages_per_step']},{i_hier['messages_per_step']},"
             f"{int(fewer)}",
             source="measured-mp", rank_count=4, grid=f"{gh}x{gw}",
             family="gauss_exp", radius=spec.radius,
             ranks_per_node=2, node_grid=[node.nodes_y, node.nodes_x],
             rings_flat=len(ring_send_entries(spec)),
             rings_node=len(table),
             flat_step_ms=flat["step_ms"], hier_step_ms=hier["step_ms"],
             flat_bytes_per_step=halo_payload_bytes(
                 cfg, spec, mode="dense_packed")["bytes_per_step"],
             hier_bytes_per_step=hier_payload_bytes(
                 cfg, spec, node, mode="dense_packed")["bytes_per_step"],
             internode_flat_bytes=i_flat["bytes_per_step"],
             internode_hier_bytes=i_hier["bytes_per_step"],
             internode_flat_messages=i_flat["messages_per_step"],
             internode_hier_messages=i_hier["messages_per_step"],
             hier_fewer_internode_bytes=bool(fewer),
             per_ring=table, impl=args.impl)
    print(f"# check: hierarchical inter-node bytes strictly fewer than "
          f"flat at radius>=3: {'PASS' if seam_ok else 'FAIL'}")

    # ---- modelled: paper problem, 16..1024 ranks, 4 ranks/node ----
    g = TOPOLOGY_RANKS_PER_NODE
    paper_cfg = with_ranks(RANK_TILE_PAPER, 1024)  # fixed 96x96 problem
    print("rank_count,nodes,rings_flat,rings_node,flat_exchange_ms,"
          "hier_exchange_ms,internode_flat_B,internode_hier_B,"
          "hier_beats_flat")
    for p in MODEL_RANKS:
        spec = make_rank_tile_spec(paper_cfg, p)
        pry, prx = process_grid(p)
        try:
            node = make_node_spec(pry, prx, g)
        except ValueError:
            continue
        flat_pb = halo_payload_bytes(paper_cfg, spec, mode="auto")
        hier_pb = hier_payload_bytes(paper_cfg, spec, node, mode="auto")
        i_flat = internode_totals(paper_cfg, spec, node,
                                  hierarchical=False, mode="auto")
        i_hier = internode_totals(paper_cfg, spec, node,
                                  hierarchical=True, mode="auto")
        # per-node charge (nodes progress in parallel; the busiest node
        # seam bounds the step): seam bytes/messages at network cost,
        # everything else at chip-interconnect cost
        n_nodes = max(node.n_nodes, 1)
        f_inter_b = i_flat["bytes_per_step"] / n_nodes
        f_inter_m = i_flat["messages_per_step"] / n_nodes
        f_intra_b = max(flat_pb["bytes_per_step"] * g - f_inter_b, 0.0)
        f_intra_m = max(flat_pb["n_messages"] * g - f_inter_m, 0.0)
        t_flat = (f_inter_b / ETH + f_inter_m * LAT_ETH
                  + f_intra_b / ICI + f_intra_m * LAT_ICI)
        h_inter_b = hier_pb["inter_node_bytes_per_node"]
        h_inter_m = hier_pb["inter_node_messages_per_node"]
        h_intra_b = hier_pb["intra_node_bytes_per_rank"] * g
        h_intra_m = 2 * g   # all-gather in + broadcast out, per member
        t_hier = (h_inter_b / ETH + h_inter_m * LAT_ETH
                  + h_intra_b / ICI + h_intra_m * LAT_ICI)
        table = ring_mode_table(paper_cfg, spec, node)
        beats = t_hier < t_flat
        emit("topology",
             f"{p},{n_nodes},{len(ring_send_entries(spec))},{len(table)},"
             f"{t_flat * 1e3:.3f},{t_hier * 1e3:.3f},"
             f"{i_flat['bytes_per_step']},{i_hier['bytes_per_step']},"
             f"{int(beats)}",
             source="modelled-topology", rank_count=p,
             grid=f"{paper_cfg.grid_h}x{paper_cfg.grid_w}",
             ranks_per_node=g, nodes=n_nodes,
             node_grid=[node.nodes_y, node.nodes_x],
             rings_flat=len(ring_send_entries(spec)),
             rings_node=len(table),
             flat_exchange_ms=t_flat * 1e3,
             hier_exchange_ms=t_hier * 1e3,
             flat_bytes_per_step=flat_pb["bytes_per_step"],
             hier_bytes_per_step=hier_pb["bytes_per_step"],
             internode_flat_bytes=i_flat["bytes_per_step"],
             internode_hier_bytes=i_hier["bytes_per_step"],
             internode_flat_messages=i_flat["messages_per_step"],
             internode_hier_messages=i_hier["messages_per_step"],
             hier_beats_flat=bool(beats), per_ring=table)
    if not seam_ok:
        raise SystemExit("hierarchical exchange did not reduce "
                         "inter-node bytes at radius>=3")


# ---------------------------------------------------------------------------
# Recovery mode: supervisor restart cost + elastic reshard round-trip
# ---------------------------------------------------------------------------

def mode_recovery(args):
    """Fault-recovery cost of the supervised runtime (DESIGN.md
    §Elasticity): one supervised 2-rank run, then the same run with a
    deterministic chaos kill mid-way — the wall-time delta is what one
    worker death costs end-to-end (detection + relaunch + recompile +
    re-running the lost steps). Plus the elastic reshard round-trip row:
    a synthetic bench-geometry stacked state pushed R=4 -> R'=2 -> R=4
    through ``checkpointer.reshard`` must come back exactly (counters
    compare as totals — the reshard merges partial sums onto shard 0).

    Rows intentionally carry no ``step_ms`` key: a supervised wall time
    includes checkpoint IO and restart overhead, so compare.py's
    regression gate (keyed on step_ms) never sees them — they are
    trajectory/observability rows, in the nightly artifact.
    """
    import numpy as np

    from repro.launch.launch_distributed import make_parser, supervise

    if args.quick:
        grid, neurons, steps = "4x4", 16, 40
    else:
        grid, neurons, steps = "8x8", 48, 60
    every, kill_at = 10, 25
    print(f"# recovery: 2 ranks, {grid} grid, {neurons} n/col, "
          f"{steps} steps, checkpoint every {every}, kill at {kill_at}")
    rows = {}
    for tag, chaos in (("uninterrupted", False), ("killed", True)):
        import tempfile

        with tempfile.TemporaryDirectory(prefix="dpsnn-bench-ckpt-") as d:
            argv = ["--ranks", "2", "--grid", grid,
                    "--neurons", str(neurons), "--steps", str(steps),
                    "--no-check-single", "--supervise", "--ckpt-dir", d,
                    "--checkpoint-every", str(every)]
            if chaos:
                argv += ["--chaos-kill-rank", "1",
                         "--chaos-at-step", str(kill_at)]
            rows[tag] = supervise(make_parser().parse_args(argv))
    plain, killed = rows["uninterrupted"], rows["killed"]
    overhead = killed["supervised_wall_s"] - plain["supervised_wall_s"]
    stats_match = (killed["spikes"] == plain["spikes"]
                   and killed["rate_hz"] == plain["rate_hz"]
                   and killed["isi_cv"] == plain["isi_cv"])
    emit("recovery",
         f"recovery: restarts={killed['restarts']} "
         f"lost_steps={killed['lost_steps']} overhead={overhead:.1f}s "
         f"(uninterrupted {plain['supervised_wall_s']:.1f}s -> killed "
         f"{killed['supervised_wall_s']:.1f}s), stats_match={stats_match}",
         source="measured-recovery", rank_count=2, grid=grid,
         neurons=plain["neurons"], steps=steps, checkpoint_every=every,
         chaos_at_step=kill_at, restarts=killed["restarts"],
         lost_steps=killed["lost_steps"],
         uninterrupted_wall_s=plain["supervised_wall_s"],
         killed_wall_s=killed["supervised_wall_s"],
         recovery_overhead_s=overhead, stats_match=bool(stats_match))

    # ---- reshard round-trip (no processes needed: host-side numpy) ----
    import jax

    from repro.checkpoint.checkpointer import reshard
    from repro.core.exchange import stacked_state_template
    from repro.core.partition import make_rank_tile_spec

    gh, gw = (int(v) for v in grid.split("x"))
    cfg = DPSNNConfig(grid_h=gh, grid_w=gw, neurons_per_column=neurons,
                      seed=0)
    tpl, spec4, _ = stacked_state_template(cfg, 4)
    spec2 = make_rank_tile_spec(cfg, 2)
    rng = np.random.default_rng(0)

    def fill(path, leaf):
        name = path[-1].name if hasattr(path[-1], "name") else str(path[-1])
        if name == "t":   # the reshard asserts t agrees across shards
            return np.full(leaf.shape, 37, leaf.dtype)
        if np.issubdtype(leaf.dtype, np.floating):
            # counters must stay integer-valued (exact partial-sum merge)
            return rng.integers(0, 7, leaf.shape).astype(leaf.dtype)
        if leaf.dtype == np.bool_:
            return np.zeros(leaf.shape, leaf.dtype)
        return rng.integers(-1, 9, leaf.shape).astype(leaf.dtype)

    # identity reshard canonicalizes the random fill first (halo cells
    # must equal neighbour interiors — the invariant live states hold)
    state = reshard(jax.tree_util.tree_map_with_path(fill, tpl),
                    spec4, spec4)
    t0 = time.perf_counter()
    back = reshard(reshard(state, spec4, spec2), spec2, spec4)
    reshard_s = time.perf_counter() - t0
    totals = {"spike_count", "event_count", "isi_sum", "isi_sumsq",
              "isi_count", "aer_sat"}
    exact = True
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(state)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        name = pa[-1].name if hasattr(pa[-1], "name") else str(pa[-1])
        ok = (np.isclose(a.sum(dtype=np.float64), b.sum(dtype=np.float64))
              if name in totals else np.array_equal(a, b))
        if not ok:
            exact = False
            print(f"# reshard round-trip MISMATCH at "
                  f"{jax.tree_util.keystr(pa)}")
    emit("recovery",
         f"reshard round-trip 4->2->4 on {grid}x{neurons}: "
         f"exact={exact} ({reshard_s * 1e3:.0f} ms)",
         source="measured-reshard", rank_count=4, grid=grid,
         neurons=cfg.n_neurons, reshard_roundtrip_exact=bool(exact),
         reshard_s=reshard_s)
    if not exact:
        raise SystemExit("reshard round-trip is not exact")


def mode_guard(args):
    """Integrity-guard overhead (``--mode guard``, in ``all``): the same
    multi-process bench point measured guard-off and guard-on
    (DESIGN.md §Integrity — invariant monitors in the step + one
    checksum word per halo message). Both rows land in the artifact
    (compare.py keys on the ``guard`` field; old baselines read as
    guard-off), and the run asserts the guard is bitwise-neutral and
    reports the overhead against the <5% always-on budget.
    """
    ranks = 2 if args.quick else 4
    gh, gw, neurons, steps = ((8, 8, 48, 150) if args.quick
                              else (8, 8, 64, 250))
    grid = f"{gh}x{gw}"
    print(f"# guard overhead: {ranks} ranks, {grid} grid, "
          f"{neurons} n/col, {steps} steps, impl={args.impl}")
    rows = {}
    for guard in (False, True):
        r = _launch_ranks(ranks, grid, neurons, steps, weak=False,
                          impl=args.impl, guard=guard)
        rows[guard] = r
        emit("guard",
             f"guard={'on' if guard else 'off'}: "
             f"step_ms={r['step_ms']:.3f} "
             f"events/s={r['events_per_s']:.3e}",
             source="measured-mp", rank_count=ranks, grid=grid,
             neurons=r["neurons"], steps=steps, step_ms=r["step_ms"],
             events_per_s=r["events_per_s"],
             exchange_mode=r["exchange_mode"], impl=args.impl,
             guard=guard, spikes=r["spikes"])
    overhead = rows[True]["step_ms"] / rows[False]["step_ms"] - 1.0
    ok = overhead < 0.05
    emit("guard",
         f"guard overhead {overhead * 100:+.1f}% "
         f"({rows[False]['step_ms']:.3f} -> {rows[True]['step_ms']:.3f} "
         f"ms/step), bound 5%: {'OK' if ok else 'EXCEEDED'}",
         source="guard-overhead", rank_count=ranks, grid=grid,
         guard_overhead_frac=overhead, guard_overhead_ok=bool(ok))
    if rows[True]["spikes"] != rows[False]["spikes"]:
        raise SystemExit(
            f"guard-on spikes {rows[True]['spikes']} != guard-off "
            f"{rows[False]['spikes']} — the guard must be "
            f"bitwise-neutral on healthy runs")
    if not ok:
        print(f"warn: guard overhead {overhead * 100:.1f}% exceeds the "
              f"5% budget on this host (advisory outside CI's bench "
              f"gate — oversubscribed-core noise dominates small runs)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="all",
                    choices=["strong", "weak", "realtime", "speedup",
                             "sweep", "payload", "kernels", "batch",
                             "topology", "recovery", "guard", "all"])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--exchange-mode", default="dense_packed",
                    choices=["dense_packed", "aer_sparse", "both"],
                    help="spike-halo wire format for the measured rank "
                         "sweep ('both' = run it once per format — the "
                         "nightly pipeline)")
    ap.add_argument("--impl", default="ref",
                    choices=["ref", "pallas", "pallas_fused"],
                    help="step implementation for the measured rank sweep "
                         "(rows carry the value; compare.py keys on it — "
                         "the nightly matrix runs ref and pallas_fused)")
    ap.add_argument("--pipelined", action="store_true",
                    help="cross-step pipelined halo exchange for the "
                         "measured rank sweep (ExchangeConfig.pipelined)")
    ap.add_argument("--json", default="",
                    help="write machine-readable rows to this path "
                         "(the BENCH_*.json CI artifact)")
    args = ap.parse_args()
    if args.mode in ("strong", "speedup", "all"):
        mode_strong(args)
    if args.mode in ("weak", "all"):
        mode_weak(args)
    if args.mode in ("realtime", "all"):
        mode_realtime(args)
    if args.mode in ("sweep", "all"):
        mode_sweep(args)
    if args.mode in ("payload", "all"):
        mode_payload(args)
    if args.mode in ("kernels", "all"):
        mode_kernels(args)
    if args.mode in ("batch", "all"):
        mode_batch(args)
    if args.mode in ("topology", "all"):
        mode_topology(args)
    if args.mode in ("recovery", "all"):
        mode_recovery(args)
    if args.mode in ("guard", "all"):
        mode_guard(args)
    if args.json:
        doc = {
            "bench": "scaling",
            "quick": bool(args.quick),
            "families": list(BENCH_FAMILIES),
            "exchange_modes": _sweep_exchange_modes(args),
            "impl": args.impl,
            "pipelined": bool(args.pipelined),
            "rows": ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"# wrote {len(ROWS)} rows -> {args.json}")


if __name__ == "__main__":
    main()
