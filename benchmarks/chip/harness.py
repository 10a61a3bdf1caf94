"""One run of one cell: set-up, the measured window, the check of what the
window produced, and the result line.

* Set-up (``setup_s``, from the process's start to the window's first
  call): the first state built on the device from the seed, the window's
  program loaded from the persistent compile cache (or compiled), the
  traffic's settling steps, and one warm call.
* The window calls the program with ``steps_per_call`` steps at a time and
  carries the state, until ``--seconds`` have passed; the call that
  crosses the mark ends it. ``wall_s_per_sim_s`` is the window's wall time
  over the simulated time of every step in it. A compile inside the window
  fails the run.
* ``--trace 1`` traces the same window with the profiler and reports the
  per-layer metrics (``metrics/<name>.py``) instead of the end-to-end ones.
* After the window the peak memory is read, the program's static weights
  are dropped, and ``reference.compare`` checks the window's last call.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import tempfile
import time

import jax

import cell as cellmod
import entries
import leastwork
import reference
import scopes
import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))


def log(**kw) -> None:
    """One informational line on standard error."""
    print(json.dumps(kw), file=sys.stderr, flush=True)


class _CompileCounter:
    """Counts the compiles JAX reports while ``open`` is set."""

    def __init__(self):
        self.open = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.open and event.startswith("/jax/core/compile/"):
            self.count += 1


class LayerContext:
    """What a per-layer reader gets: the reduced trace of the window, the
    steps in it, the least work per step and the chip's peaks; ``scopes``,
    set where the window's program is known, maps each of its ops to its
    named scope (``scopes.op_scopes``)."""

    def __init__(self, red, steps, work, peak):
        self.red, self.steps, self.work, self.peak = red, steps, work, peak
        self.window_s = red.window[1] - red.window[0]
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


def read_layer_metric(name: str, ctx: LayerContext):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def device_info(devices, memory: list) -> dict:
    """The device as JAX reports it. ``memory_peak_bytes`` is the fullest
    chip's: the allocator's ``peak_bytes_in_use``, or, where that is less,
    the bytes of the window's program by ``memory_analysis()`` (``memory``,
    one entry per chip or one for all), since the allocator's peak on the
    chip can leave out the program's temporary buffers. The source of the
    number goes beside it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    in_use = max([p for p in peaks if p is not None], default=0)
    program = max(m["total_bytes"] for m in memory)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(in_use, program),
            "memory_peak_source": ("peak_bytes_in_use" if in_use >= program
                                   else "memory_analysis"),
            "_per_chip_peak": peaks}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, window_hook=None) -> dict:
    """Run ``cell`` once and return the result line's object.
    ``window_hook(entry)``, for tests, wraps the entry's call."""
    cfg = cellmod.program_config(cell)
    net = cell.network
    entry = entries.make(cell, cfg, devices)
    counter = _CompileCounter()
    s_state, t0 = entries.seed_words(seed)

    carry = entry.build(s_state, t0)
    t_built = time.perf_counter()
    entry.compile(carry)
    t_compiled = time.perf_counter()
    call = window_hook(entry) if window_hook else entry.call
    settle = int(cell.traffic.get("settle_steps", 0))
    for _ in range(-(-settle // cell.steps_per_call)):
        carry, done = call(carry)
        done.block_until_ready()
    carry, done = call(carry)                       # warm call
    done.block_until_ready()
    setup_s = time.perf_counter() - t_start
    log(phase="setup", setup_s=setup_s, build_s=t_built - t_start,
        compile_s=t_compiled - t_built, memory_analysis=entry.memory(),
        steps_per_call=cell.steps_per_call, seed=seed, t0=t0)

    tmp = tempfile.TemporaryDirectory() if trace else None
    c_start = entry.counters(carry)
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    calls = 0
    counter.open = True
    first = counter.count
    w_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            prev = carry
            with jax.profiler.TraceAnnotation("bench.call"):
                carry, done = call(carry)
            with jax.profiler.TraceAnnotation("bench.wait"):
                done.block_until_ready()
            calls += 1
            if time.perf_counter() - w_start >= seconds:
                break
    wall = time.perf_counter() - w_start
    counter.open = False
    compiles = counter.count - first
    if trace:
        jax.profiler.stop_trace()
    if compiles:
        raise RuntimeError(f"{compiles} compile(s) inside the window")
    steps = calls * cell.steps_per_call
    c_end = entry.counters(carry)
    dev = device_info(devices, entry.memory())
    log(phase="window", calls=calls, steps=steps, wall_s=wall,
        spikes=c_end[0] - c_start[0], events=c_end[1] - c_start[1],
        peak_bytes_in_use_per_chip=dev.pop("_per_chip_peak"))

    # the check: the window's last call, against the reference, once the
    # program's static weights are dropped
    a = entry.snapshot(entry.drop_params(prev))
    b = entry.snapshot(entry.drop_params(carry))
    del prev, carry, done
    t_ref = time.perf_counter()
    checks = reference.compare(net, a, b, devices,
                               cell.steps_per_call)
    log(phase="reference", reference_s=time.perf_counter() - t_ref)
    del a, b

    if trace:
        with tmp:
            profile = jax.profiler.ProfileData.from_file(
                tracereduce.find_xplane(tmp.name))
            metrics, extra = layer_metrics(cell, net, entry, profile, steps,
                                           c_start, c_end)
        dev.update(extra.pop("device"))
    else:
        sim_s = steps * net["neuron"]["dt_ms"] * 1e-3
        metrics = {"wall_s_per_sim_s": {"value": wall / sim_s, "unit": "s/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        extra = {}
    return result(cell, checks, steps, cell.steps_per_call, metrics, dev,
                  extra)


def layer_metrics(cell, net, entry, profile, steps, c_start, c_end):
    """The cell's per-layer metrics from the window's trace; also the
    device's busy and window seconds and the breakdown."""
    text = entry.exe.as_text()
    red = tracereduce.reduce(profile, tracereduce.op_kinds(text))
    st = reference.stencil(net)
    work = leastwork.step_work(net, st.k_total, len(st.offsets), steps,
                               c_end[1] - c_start[1], c_end[0] - c_start[0])
    ctx = LayerContext(red, steps, work,
                       leastwork.peaks(entry.device_kind))
    ctx.scopes = scopes.op_scopes(text)
    metrics = {}
    for m in cell.per_layer:
        value = read_layer_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = [tracereduce.busy(d) for d in red.devices]
    log(phase="layers", steps=steps, work_per_step=work._asdict(),
        busy_s_per_chip=busy, notes=ctx.notes,
        scope_ms=scopes.breakdown(red, ctx.scopes, steps),
        kinds={k: sum(1 for o in red.devices[0].ops if o.kind == k)
               for k in (tracereduce.KERNEL, tracereduce.GATHER,
                         tracereduce.COLLECTIVE, tracereduce.OTHER)})
    extra = {"device": {"busy_s": sum(busy) / len(busy),
                        "window_s": ctx.window_s},
             "breakdown": {"device_ops": tracereduce.top_ops(red),
                           "idle_gaps": tracereduce.idle_gaps(red)}}
    return metrics, extra


def result(cell, checks, steps, k, metrics, dev, extra) -> dict:
    """The result line: every compared number beside its limit, last."""
    unknown = sorted(set(checks) - set(cell.limits))
    if unknown:
        raise KeyError(f"no limit for compared number(s) {unknown}")
    compared, failed = {}, []
    for name, limit in cell.limits.items():
        value = checks.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            failed.append(name)
    out = {"correct": not failed, "attempted": steps,
           "failed": k if failed else 0, "metrics": metrics, "device": dev}
    out.update(extra)
    out["checks"] = compared
    return out
