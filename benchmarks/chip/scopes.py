"""Per-layer device time read by the program's named scopes.

The program wraps each layer of its step in one ``jax.named_scope`` named
``dpsnn.<layer>``, and the scopes do not nest:

========================  ===============================================
``dpsnn.drive``           Poisson variates and drive currents
``dpsnn.ring``            delayed spike table and history-ring writes
``dpsnn.halo``            halo exchange: packing, collectives, unpacking
``dpsnn.remote``          spike packing and the remote ELL delivery
``dpsnn.neuron``          local delivery and the LIF+SFA update
``dpsnn.stdp``            pre-trace table, pre-trace gather, STDP kernel
``dpsnn.params``          synapse generation (where a call rebuilds them)
========================  ===============================================

The compiled HLO keeps the scope in each instruction's ``op_name``
metadata, among JAX's own components
(``jit(run)/while/body/closed_call/dpsnn.stdp/jit(take_along_axis)/
gather``). A fusion carries metadata of its own, which XLA takes from its
root; that is the one the trace's op is named by. Counters, checksums,
the integrity guard and copies carry no scope, nor do ops that XLA adds
with no ``op_name`` (on a TPU, the loop that lays out a gather's index
array); they count as unscoped. The readers ``metrics/<layer>_ms.py`` and
``metrics/unscoped_ms.py`` report ``per_step_ms`` of one scope each; the
harness logs ``breakdown`` on its ``phase="layers"`` line.
"""
from __future__ import annotations

import re

from tracereduce import COLLECTIVE, CONTROL, GATHER, KERNEL, OTHER

PREFIX = "dpsnn."
SCOPES = tuple(PREFIX + s for s in ("drive", "ring", "halo", "remote",
                                    "neuron", "stdp", "params"))
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a scope is a whole component of the path, or the argument of a
# transform's component (``vmap(dpsnn.drive)``)
_SCOPE = re.compile(r"(?:^|[/(])(" + re.escape(PREFIX) + r"[\w\-]+)")


def scope_components(op_name: str) -> list:
    """Every ``dpsnn.<layer>`` component of an ``op_name``, in order."""
    return _SCOPE.findall(op_name)


def scope_of(op_name: str):
    """The first ``dpsnn.<layer>`` component of an ``op_name``, or None."""
    return next(iter(scope_components(op_name)), None)


def op_names(hlo_text: str) -> dict:
    """Instruction name -> its own ``op_name`` metadata ('' without)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            meta = _OP_NAME.search(m.group(2))
            out[m.group(1)] = meta.group(1) if meta else ""
    return out


def op_scopes(hlo_text: str) -> dict:
    """Instruction name -> the first ``dpsnn.<layer>`` component of its own
    ``op_name`` metadata, or None, for every instruction of an HLO module
    (the compiled window program's ``as_text()``)."""
    return {name: scope_of(op) for name, op in op_names(hlo_text).items()}


def scope_time(device, scopes: dict, scope, kind=None) -> float:
    """Device seconds of the ops of ``scope`` (None: ops of no scope) on
    one device of a ``tracereduce.Reduced``, loops and calls left out;
    ``kind`` keeps only ops of that kind."""
    return sum(o.end - o.start for o in device.ops
               if o.kind != CONTROL and scopes.get(o.name) == scope
               and (kind is None or o.kind == kind))


def per_step_ms(ctx, scope):
    """ms per step of ``scope`` on the chip with the most, from a
    ``harness.LayerContext`` that carries the window program's scopes
    (``ctx.scopes``). None where the context has no scopes, where the
    program names none (one built before its layers had scopes) or where
    no op of ``scope`` ran."""
    scopes = getattr(ctx, "scopes", None)
    if not scopes or not any(s is not None for s in scopes.values()):
        return None
    times = [scope_time(d, scopes, scope) for d in ctx.red.devices]
    if not any(times):
        return None
    return 1e3 * max(times) / ctx.steps


def breakdown(red, scopes: dict, steps: int) -> list:
    """ms per step of every scope (and of ``unscoped``) on each chip, split
    by op kind: ``[{scope: {kind: ms}}]``, one dict per chip, scopes and
    kinds with no time left out."""
    out = []
    for dev in red.devices:
        chip = {}
        for scope in SCOPES + (None,):
            kinds = {k: 1e3 * scope_time(dev, scopes, scope, k) / steps
                     for k in (GATHER, KERNEL, COLLECTIVE, OTHER)}
            kinds = {k: v for k, v in kinds.items() if v}
            if kinds:
                chip[scope or UNSCOPED] = kinds
        out.append(chip)
    return out
