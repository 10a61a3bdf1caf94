"""The trace reduction, on a trace recorded on a TPU v5e (one call of
g24-static's window: two steps of the 24x24x1240 grid, with the op kinds
of its compiled program) and on hand-made intervals and HLO.

To record ``data/`` again, on a TPU v5e: build g24-static's entry as
``harness.run_cell`` does (``entries.make``, ``build``, ``compile``), make
one warm call, then make one more inside ``jax.profiler.trace(<dir>)`` and
a ``jax.profiler.TraceAnnotation("bench.window")``, waiting on its scalar.
Gzip the file ``tracereduce.find_xplane(<dir>)`` names into
``g24-static-1call.xplane.pb.gz``, write
``tracereduce.op_kinds(entry.exe.as_text())`` as JSON into
``g24-static-1call.kinds.json``, and update ``SPIKES``, ``EVENTS`` and the
sums below from the counters and the raw trace."""
import gzip
import json
import os
import re

import pytest

import harness
import leastwork
import tracereduce as tr
from conftest import HERE, small_cell

DATA = os.path.join(HERE, "data")
STEPS = 2                       # one call of two steps
SPIKES, EVENTS = 50265, 64600129   # the counters' increments in that call


@pytest.fixture(scope="module")
def recorded():
    import jax

    with gzip.open(os.path.join(DATA, "g24-static-1call.xplane.pb.gz")) as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "g24-static-1call.kinds.json")) as f:
        kinds = json.load(f)
    return profile, kinds


def _raw_ops(profile):
    """(name, start_s, end_s) of every XLA op of the device, read plainly."""
    dev = [p for p in profile.planes if p.name == "/device:TPU:0"][0]
    out = []
    for line in dev.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                name = re.match(r"%?([\w.\-]+) = ", e.name).group(1)
                s = e.start_ns * 1e-9
                out.append((name, s, s + e.duration_ns * 1e-9))
    return out


def test_recorded_trace_reduces_to_its_raw_sums(recorded):
    profile, kinds = recorded
    red = tr.reduce(profile, kinds)
    assert [d.name for d in red.devices] == ["/device:TPU:0"]
    w0, w1 = red.window
    assert w1 - w0 == pytest.approx(4.181558335, abs=1e-9)
    raw = [o for o in _raw_ops(profile) if o[2] > w0 and o[1] < w1]
    dev = red.devices[0]
    for kind in (tr.GATHER, tr.KERNEL):
        want = sum(e - s for n, s, e in raw if kinds[n] == kind)
        assert tr.kind_time(dev, kind) == pytest.approx(want, rel=1e-12)
    # the remote ELL gather is one fusion, 4.11 s of the two steps
    assert tr.kind_time(dev, tr.GATHER) == pytest.approx(4.11080575,
                                                         rel=1e-9)
    assert tr.kind_time(dev, tr.COLLECTIVE) == 0
    assert 4.17 < tr.busy(dev) <= w1 - w0


def test_recorded_trace_layer_metrics(recorded):
    profile, kinds = recorded
    red = tr.reduce(profile, kinds)
    cell = small_cell("g24-static", grid=24, neurons=1240)
    net = cell.network
    work = leastwork.step_work(net, 248, 20, STEPS, EVENTS, SPIKES)
    ctx = harness.LayerContext(red, STEPS, work,
                               leastwork.peaks("TPU v5 lite"))
    got = {m["name"]: harness.read_layer_metric(m["name"], ctx)
           for m in cell.per_layer}
    # the cell lists no gather_ms since its delivery left the gather; the
    # reader still reduces the gather of this earlier program
    assert "gather_ms" not in got
    assert harness.read_layer_metric("gather_ms", ctx) == pytest.approx(
        4110.80575 / STEPS, rel=1e-9)
    busy = tr.busy(red.devices[0])
    assert got["idle_share"] == pytest.approx(
        100 * (1 - busy / ctx.window_s))
    assert 0 < got["kernel_ms"] < 10
    for share in ("step_mfu", "kernel_roofline"):
        assert 0 < got[share] <= 100
    assert ctx.notes["kernel_roofline_bound"] == "memory"
    # no collective runs on one chip: the readers find nothing to read
    for name in ("collective_ms", "collective_exposed_ms"):
        assert name not in got
        assert harness.read_layer_metric(name, ctx) is None
    gaps = tr.idle_gaps(red)
    assert gaps and all(name.startswith("bench.") for name, _ in gaps)
    assert [name for name, _ in tr.top_ops(red)[:1]] == ["fusion.52 [gather]"]


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]
    assert tr.measure([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert tr.minus([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == pytest.approx(7)
    assert tr.minus([(0, 1), (2, 3)], []) == 2
    dev = tr.Device("d", [tr.Op("w", tr.CONTROL, 0, 10),
                          tr.Op("c", tr.COLLECTIVE, 1, 4),
                          tr.Op("f", tr.OTHER, 2, 3)])
    # the loop's span holds everything and hides nothing
    assert tr.exposed(dev, tr.COLLECTIVE) == 2
    assert tr.kind_time(dev, tr.OTHER) == 1


HLO = """HloModule jit_run

%fused_gather (p: f32[4,8], i: s32[4,2]) -> f32[4,2] {
  %p = f32[4,8]{1,0} parameter(0)
  ROOT %gather.1 = f32[4,2]{1,0} gather(f32[4,8]{1,0} %p, s32[4,2] %i), offset_dims={}
}

%fused_ar (x: f32[4]) -> f32[4] {
  ROOT %all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %x), to_apply=%add
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %fusion.7 = f32[4,2]{1,0:T(8,128)} fusion(%a, %b), calls=%fused_gather
}

ENTRY %main (a: f32[4,8]) -> f32[4] {
  %custom-call.2 = f32[4] custom-call(%a), custom_call_target="tpu_custom_call"
  %collective-permute-start.1 = (f32[4], f32[4]) collective-permute-start(%x)
  %fusion.9 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop, calls=%fused_ar
  %while.122 = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %x)
  %while.3 = (s32[], f32[4]{0:T(1024)}) while(%t), condition=%c, body=%body
}
"""


def test_op_kinds_from_hlo_text():
    kinds = tr.op_kinds(HLO)
    assert kinds["fusion.7"] == tr.GATHER
    assert kinds["custom-call.2"] == tr.KERNEL
    assert kinds["collective-permute-start.1"] == tr.COLLECTIVE
    assert kinds["fusion.9"] == tr.COLLECTIVE
    # kinds follow the opcode, not the name
    assert kinds["while.122"] == tr.OTHER
    assert kinds["while.3"] == tr.CONTROL
