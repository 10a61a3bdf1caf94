"""Reduce a profiler trace of the measured window to per-layer times.

Device operations are told apart by what the trace and the compiled
program carry, with no change to the program: a Mosaic kernel is a
``custom-call`` to ``tpu_custom_call``; a gather is a ``gather`` or a fusion
whose computation holds one; a collective is a collective opcode
(``collective-permute``, ``all-reduce``, ... and their ``-start``/``-done``
halves) or a fusion holding one. The compiled HLO text of the window's
program maps each operation's name to its kind; the same text maps it to
the program's named scope, its layer (``scopes.py``).

On a TPU the trace's ``XLA Ops`` line of each ``/device:TPU:<i>`` plane
holds one event per executed operation, named by its HLO instruction text
(``%fusion.52 = f32[...] fusion(...)``), on the same clock as the host's
spans; a ``while`` loop's event spans the ops of its body.

The window is the benchmark's own host span ``bench.window``; the host
spans ``bench.call`` (dispatch of one call) and ``bench.wait`` (waiting on
its result) name what the host did during each idle gap of a device.
"""
from __future__ import annotations

import glob
import re
from typing import NamedTuple

KERNEL, GATHER, COLLECTIVE, OTHER = "kernel", "gather", "collective", "other"
# a loop or call: its span holds its body's ops, so it counts towards the
# device's busy time and towards no kind
CONTROL = "control"

_COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
                "reduce-scatter", "all-to-all", "collective-broadcast")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|async_computation)=%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_EVENT_OP = re.compile(r"^%?([\w.\-]+) = ")


def _own_kind(opcode: str, rhs: str) -> str:
    if opcode in ("while", "conditional", "call"):
        return CONTROL
    if opcode == "custom-call" and "tpu_custom_call" in rhs:
        return KERNEL
    if opcode == "gather":
        return GATHER
    if opcode.startswith(_COLLECTIVES):
        return COLLECTIVE
    return OTHER


def op_kinds(hlo_text: str) -> dict:
    """Instruction name -> kind, for every instruction of an HLO module;
    an instruction that calls computations takes the kind of what they
    hold (kernel, then collective, then gather, first found)."""
    comps: dict = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rhs = m.groups()
            op = _OPCODE.search(" " + rhs)
            cur.append((name, op.group(1) if op else "", rhs,
                        _CALLS.findall(rhs)))
    memo: dict = {}

    def comp_kind(c, seen=()):
        if c in memo:
            return memo[c]
        found = set()
        for name, opcode, rhs, calls in comps.get(c, ()):
            found.add(_own_kind(opcode, rhs))
            for sub in calls:
                if sub not in seen:
                    found.add(comp_kind(sub, seen + (c,)))
        kind = next((k for k in (KERNEL, COLLECTIVE, GATHER) if k in found),
                    OTHER)
        memo[c] = kind
        return kind

    kinds = {}
    for c, instrs in comps.items():
        for name, opcode, rhs, calls in instrs:
            kind = _own_kind(opcode, rhs)
            if kind == OTHER:
                inner = {comp_kind(sub) for sub in calls}
                kind = next((k for k in (KERNEL, COLLECTIVE, GATHER)
                             if k in inner), OTHER)
            kinds[name] = kind
    return kinds


class Op(NamedTuple):
    name: str
    kind: str
    start: float       # seconds on the trace's clock
    end: float


class Device(NamedTuple):
    name: str
    ops: list


class Reduced(NamedTuple):
    devices: list            # [Device]
    spans: list              # host spans [(name, start, end)]
    window: tuple            # (start, end) of ``bench.window``


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _device_planes(profile):
    planes = [p for p in profile.planes if _DEVICE_PLANE.match(p.name)]
    return sorted(planes, key=lambda p: int(_DEVICE_PLANE.match(p.name)[1]))


def reduce(profile, kinds: dict, span_prefix: str = "bench.") -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``; ``kinds`` maps the
    window program's op names to their kind (:func:`op_kinds`)."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(span_prefix):
                    s = e.start_ns * 1e-9
                    spans.append((e.name, s, s + e.duration_ns * 1e-9))
    windows = [s for s in spans if s[0] == span_prefix + "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one {span_prefix}window span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0][1:]
    devices = []
    for plane in _device_planes(profile):
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                t = s + e.duration_ns * 1e-9
                if t <= w0 or s >= w1:
                    continue
                m = _EVENT_OP.match(e.name)
                name = m.group(1) if m else e.name
                ops.append(Op(name, kinds.get(name, OTHER),
                              max(s, w0), min(t, w1)))
        devices.append(Device(plane.name, sorted(ops, key=lambda o: o.start)))
    if not devices:
        raise ValueError("the trace holds no device plane")
    return Reduced(devices, sorted(spans, key=lambda s: s[1]), (w0, w1))


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def minus(a, b) -> float:
    """Length of the union of ``a`` outside the union of ``b``."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while cur < e:
            if k < len(b) and b[k][0] < e:
                if b[k][0] > cur:
                    total += b[k][0] - cur
                cur = max(cur, b[k][1])
                k += 1
            else:
                total += e - cur
                cur = e
    return total


def busy(dev: Device) -> float:
    return measure((o.start, o.end) for o in dev.ops)


def kind_time(dev: Device, kind: str) -> float:
    return sum(o.end - o.start for o in dev.ops if o.kind == kind)


def exposed(dev: Device, kind: str) -> float:
    """Time of ``kind`` ops during which no op of another kind (loops and
    calls aside) runs."""
    return minus([(o.start, o.end) for o in dev.ops if o.kind == kind],
                  [(o.start, o.end) for o in dev.ops
                   if o.kind not in (kind, CONTROL)])


def top_ops(red: Reduced, n: int = 10) -> list:
    """The ``n`` ops that took most device time, mean over devices."""
    tot: dict = {}
    for dev in red.devices:
        for o in dev.ops:
            if o.kind == CONTROL:
                continue
            key = f"{o.name} [{o.kind}]"
            tot[key] = tot.get(key, 0.0) + (o.end - o.start)
    k = len(red.devices)
    return sorted(([name, t / k] for name, t in tot.items()),
                  key=lambda x: -x[1])[:n]


def idle_gaps(red: Reduced, n: int = 10) -> list:
    """The ``n`` longest idle gaps of any device in the window, each named by
    the innermost host span that covers its middle."""
    w0, w1 = red.window
    gaps = []
    for dev in red.devices:
        cur = w0
        for s, e in union((o.start, o.end) for o in dev.ops) + [[w1, w1]]:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [sp for sp in red.spans if sp[1] <= mid <= sp[2]]
        inner = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "host"
        named.append([inner, e - s])
    return sorted(named, key=lambda x: -x[1])[:n]
