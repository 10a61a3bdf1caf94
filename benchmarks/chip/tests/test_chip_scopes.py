"""The reduction by named scope (``scopes.py``): on hand-written HLO and
intervals, on the program compiled on the CPU at small sizes, where every
layer of the step has to carry its one scope, and on a trace recorded on
a TPU v5e (one call of g24-stdp's window: one plastic step of the
24x24x1240 grid).

To record ``data/g24-stdp-1call.*`` again, on a TPU v5e: record one call
of g24-stdp as ``test_chip_trace.py``'s docstring says for g24-static,
gzip the trace into ``g24-stdp-1call.xplane.pb.gz``, and write
``{"kinds": tracereduce.op_kinds(text), "scopes": scopes.op_scopes(text)}``
(``text = entry.exe.as_text()``), each cut to the ops named on the trace's
``XLA Ops`` line, as JSON into ``g24-stdp-1call.scopes.json``; then update
the sums below from the raw trace."""
import gzip
import json
import os
import re

import pytest

import harness
import scopes
import tracereduce as tr
from conftest import HERE, small_cell

DATA = os.path.join(HERE, "data")

HLO = """HloModule jit_run

%fused_gather (p: f32[4,8], i: s32[4,2]) -> f32[4] {
  %p = f32[4,8]{1,0} parameter(0)
  %gather.1 = f32[4,2]{1,0} gather(f32[4,8]{1,0} %p, s32[4,2] %i), metadata={op_name="jit(run)/while/body/closed_call/dpsnn.remote/jit(take_along_axis)/gather" stack_frame_id=4}
  ROOT %reduce.2 = f32[4]{0} reduce(%gather.1, %c), to_apply=%add, metadata={op_name="jit(run)/while/body/closed_call/dpsnn.remote/reduce_sum" stack_frame_id=4}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %fusion.7 = f32[4]{0:T(128)} fusion(%a, %b), kind=kLoop, calls=%fused_gather, metadata={op_name="jit(run)/while/body/closed_call/dpsnn.remote/reduce_sum" stack_frame_id=4}, backend_config={"flag_configs":[]}
  %poisson.3 = f32[4]{0} add(%x, %x), metadata={op_name="jit(run)/while/body/closed_call/dpsnn.drive/vmap(jit(_poisson))/jit(_poisson_knuth)/while/body/add"}
  %wrapped.4 = f32[4]{0} fusion(%x), kind=kLoop, calls=%w, metadata={op_name="jit(run)/vmap(dpsnn.ring)/dynamic_update_slice"}
  %add.5 = s32[2]{0} add(%x, %x), metadata={op_name="jit(run)/while/body/closed_call/add" stack_frame_id=2}
  %dynamic-update-slice.6 = s32[8]{0} dynamic-update-slice(%x, %y, %z), backend_config={"flag_configs":[]}
  %copy.8 = f32[4]{0} copy(%x), metadata={op_name="jit(run)/mydpsnn.remote/copy"}
}
"""


def test_op_scopes_from_hlo_text():
    got = scopes.op_scopes(HLO)
    # a fusion takes its own metadata (its root's), not its body's
    assert got["fusion.7"] == "dpsnn.remote"
    assert got["gather.1"] == "dpsnn.remote"
    # JAX's own components around the scope, and a transform around it
    assert got["poisson.3"] == "dpsnn.drive"
    assert got["wrapped.4"] == "dpsnn.ring"
    # no scope in the path, no metadata at all, a look-alike component
    assert got["add.5"] is None
    assert got["dynamic-update-slice.6"] is None
    assert got["copy.8"] is None
    assert got["p"] is None


def test_scope_components():
    op = "jit(f)/dpsnn.neuron/x/vmap(dpsnn.stdp)/y"
    assert scopes.scope_components(op) == ["dpsnn.neuron", "dpsnn.stdp"]
    assert scopes.scope_of(op) == "dpsnn.neuron"
    assert scopes.scope_of("jit(f)/while/body/add") is None


def _ctx(devices, steps, sc):
    red = tr.Reduced(devices, [("bench.window", 0, 10)], (0, 10))
    ctx = harness.LayerContext(red, steps, None, None)
    if sc is not None:
        ctx.scopes = sc
    return ctx


def test_scope_time_per_step_and_breakdown():
    sc = {"g": "dpsnn.remote", "k": "dpsnn.neuron", "w": "dpsnn.remote",
          "c": None}
    a = tr.Device("a", [tr.Op("w", tr.CONTROL, 0, 10),
                        tr.Op("g", tr.GATHER, 0, 4),
                        tr.Op("k", tr.KERNEL, 4, 5),
                        tr.Op("c", tr.OTHER, 5, 5.5),
                        tr.Op("x", tr.OTHER, 6, 6.25)])
    b = tr.Device("b", [tr.Op("g", tr.GATHER, 0, 6)])
    # the loop's span holds the others and counts towards no scope
    assert scopes.scope_time(a, sc, "dpsnn.remote") == 4
    assert scopes.scope_time(a, sc, None) == 0.75     # unnamed op too
    assert scopes.scope_time(a, sc, "dpsnn.remote", tr.KERNEL) == 0
    ctx = _ctx([a, b], 2, sc)
    assert scopes.per_step_ms(ctx, "dpsnn.remote") == 3000   # chip b
    assert scopes.per_step_ms(ctx, "dpsnn.neuron") == 500
    assert scopes.per_step_ms(ctx, "dpsnn.halo") is None
    assert scopes.per_step_ms(ctx, None) == 375
    assert scopes.per_step_ms(ctx, "dpsnn.stdp") is None
    assert scopes.breakdown(ctx.red, sc, 2) == [
        {"dpsnn.remote": {"gather": 2000}, "dpsnn.neuron": {"kernel": 500},
         "unscoped": {"other": 375}},
        {"dpsnn.remote": {"gather": 3000}}]


@pytest.mark.parametrize("sc", [None, {"g": None, "k": None}])
def test_without_scopes_the_readers_find_nothing(sc):
    """A harness that gives no scopes, or a program that names none."""
    dev = tr.Device("a", [tr.Op("g", tr.GATHER, 0, 4)])
    ctx = _ctx([dev], 1, sc)
    for scope in scopes.SCOPES + (None,):
        assert scopes.per_step_ms(ctx, scope) is None


# ---------------------------------------------------------------------------
# The program, compiled on the CPU
# ---------------------------------------------------------------------------

STEP = {"dpsnn.drive", "dpsnn.ring", "dpsnn.remote", "dpsnn.neuron"}


def _check(text, want):
    ops = scopes.op_names(text)
    nested = {n: op for n, op in ops.items()
              if len(scopes.scope_components(op)) > 1}
    assert not nested, f"nested scopes: {list(nested.items())[:3]}"
    found = {scopes.scope_of(op) for op in ops.values()} - {None}
    assert found == want


@pytest.mark.parametrize("impl, stdp", [("ref", False),
                                        ("pallas_fused", False),
                                        ("pallas_fused", True)])
def test_single_shard_run_carries_one_scope_per_layer(impl, stdp):
    from repro.configs.base import DPSNNConfig
    from repro.core import simulation as sim

    cfg = DPSNNConfig(grid_h=4, grid_w=4, neurons_per_column=128, seed=0,
                      stdp=stdp)
    params, state = sim.build(cfg)
    text = sim.run.lower(cfg, params, state, 2,
                         impl=impl).compile().as_text()
    _check(text, STEP | ({"dpsnn.stdp"} if stdp else set()))


def test_mesh_resume_carries_one_scope_per_layer():
    """g48-mesh4's window program at 8x8 columns of 128 on four CPU
    devices: the halo exchange and the params rebuild join the step's
    layers."""
    import jax

    import cell as cellmod
    import entries

    cell = small_cell("g48-mesh4")
    entry = entries.make(cell, cellmod.program_config(cell), jax.devices())
    entry.compile(entry.build(1, 0))
    _check(entry.exe.as_text(), STEP | {"dpsnn.halo", "dpsnn.params"})


# ---------------------------------------------------------------------------
# A recorded chip trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded_stdp():
    import jax

    with gzip.open(os.path.join(DATA, "g24-stdp-1call.xplane.pb.gz")) as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "g24-stdp-1call.scopes.json")) as f:
        named = json.load(f)
    return profile, named["kinds"], named["scopes"]


def _raw_by_scope(profile, kinds, sc, w0, w1):
    """ms of every (scope, kind) in the window, read plainly off the
    device's ``XLA Ops`` line."""
    dev = [p for p in profile.planes if p.name == "/device:TPU:0"][0]
    out = {}
    for line in dev.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            s = e.start_ns * 1e-9
            t = s + e.duration_ns * 1e-9
            if t > w0 and s < w1:
                name = re.match(r"%?([\w.\-]+) = ", e.name).group(1)
                key = (sc.get(name), kinds.get(name, tr.OTHER))
                out[key] = out.get(key, 0) + 1e3 * (min(t, w1) - max(s, w0))
    return out


def test_recorded_stdp_trace_reduces_by_scope(recorded_stdp):
    profile, kinds, sc = recorded_stdp
    red = tr.reduce(profile, kinds)
    ctx = harness.LayerContext(red, 1, None, None)
    ctx.scopes = sc
    got = {scope: scopes.per_step_ms(ctx, scope)
           for scope in scopes.SCOPES + (None,)}
    raw = _raw_by_scope(profile, kinds, sc, *red.window)
    for scope in ("dpsnn.remote", "dpsnn.stdp", "dpsnn.neuron",
                  "dpsnn.drive"):
        want = sum(v for (s, k), v in raw.items()
                   if s == scope and k != tr.CONTROL)
        assert got[scope] == pytest.approx(want, rel=1e-12)
    # the sums, checked by hand on the raw trace: remote delivery is one
    # gather and its weighting; STDP is the pre-trace gather, the dense
    # kernel and the elementwise rule; the drive's rejection loop is a
    # loop, so only its body's ops count
    assert got["dpsnn.remote"] == pytest.approx(2055.319388 + 15.92308,
                                                rel=1e-9)
    assert got["dpsnn.stdp"] == pytest.approx(2055.391199 + 38.076761
                                              + 11.874487, rel=1e-9)
    assert got["dpsnn.neuron"] == pytest.approx(4.834336 + 0.03487,
                                                rel=1e-9)
    assert got["dpsnn.drive"] == pytest.approx(0.368985, rel=1e-6)
    # one chip, no params rebuilt in the window: nothing to read
    assert got["dpsnn.halo"] is None and got["dpsnn.params"] is None
    # the scopes and the unscoped ops make up every op but the loops
    total = sum(o.end - o.start for o in red.devices[0].ops
                if o.kind != tr.CONTROL)
    assert sum(v for v in got.values() if v) == pytest.approx(1e3 * total,
                                                              rel=1e-12)
    # and they split the opcode metrics: both gathers, both kernels
    dev = red.devices[0]
    gathers = [1e3 * scopes.scope_time(dev, sc, s, tr.GATHER)
               for s in ("dpsnn.remote", "dpsnn.stdp")]
    kernels = [1e3 * scopes.scope_time(dev, sc, s, tr.KERNEL)
               for s in ("dpsnn.neuron", "dpsnn.stdp")]
    assert all(g > 2000 for g in gathers)
    assert sum(gathers) == pytest.approx(
        harness.read_layer_metric("gather_ms", ctx), rel=1e-12)
    assert sum(kernels) == pytest.approx(
        harness.read_layer_metric("kernel_ms", ctx), rel=1e-6)


# the scope readers (``metrics/<layer>_ms.py``) on the recorded trace: the
# scope each reads, and its ms in that one plastic step by the hand sums
# above (None: no op of the scope ran)
READERS = {"remote_ms": ("dpsnn.remote", 2055.319388 + 15.92308),
           "stdp_ms": ("dpsnn.stdp", 2055.391199 + 38.076761 + 11.874487),
           "neuron_ms": ("dpsnn.neuron", 4.834336 + 0.03487),
           "drive_ms": ("dpsnn.drive", 0.368985),
           "ring_ms": ("dpsnn.ring", 0.343388),
           "unscoped_ms": (None, 58.533277),
           "rebuild_ms": ("dpsnn.params", None),
           "halo_ms": ("dpsnn.halo", None)}


def _recorded_ctx(recorded_stdp):
    profile, kinds, sc = recorded_stdp
    ctx = harness.LayerContext(tr.reduce(profile, kinds), 1, None, None)
    ctx.scopes = sc
    return ctx


@pytest.mark.parametrize("metric", sorted(READERS))
def test_recorded_stdp_trace_through_the_scope_readers(recorded_stdp,
                                                       metric):
    profile, kinds, sc = recorded_stdp
    ctx = _recorded_ctx(recorded_stdp)
    scope, want = READERS[metric]
    got = harness.read_layer_metric(metric, ctx)
    if want is None:
        assert got is None
        return
    raw = _raw_by_scope(profile, kinds, sc, *ctx.red.window)
    assert got == pytest.approx(sum(v for (s, k), v in raw.items()
                                    if s == scope and k != tr.CONTROL),
                                rel=1e-12)
    assert got == pytest.approx(want, rel=1e-6)


def test_scope_readers_add_up_to_every_op_but_the_loops(recorded_stdp):
    """What the readers report in g24-stdp is every non-loop op of the
    step, each once."""
    import cell as cellmod

    ctx = _recorded_ctx(recorded_stdp)
    listed = {m["name"] for m in cellmod.load_cell("g24-stdp").per_layer}
    assert {"remote_ms", "stdp_ms", "neuron_ms", "drive_ms", "ring_ms",
            "unscoped_ms"} <= listed
    assert not {"rebuild_ms", "halo_ms"} & listed
    got = [harness.read_layer_metric(m, ctx) for m in READERS
           if m in listed]
    dev = ctx.red.devices[0]
    total = sum(o.end - o.start for o in dev.ops if o.kind != tr.CONTROL)
    assert sum(got) == pytest.approx(1e3 * total, rel=1e-12)
