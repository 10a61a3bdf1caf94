"""Public wrappers for the Pallas kernels, and the one interpret decision.

``impl='pallas'`` paths in core/network.py import these; the
``impl='pallas_fused'`` path uses :func:`fused_step` (the column-step
megakernel, DESIGN.md §Fusion). The kernels themselves take a required
``interpret`` flag; the wrappers here fill it from :func:`interpret_mode`,
so the same call sites compile the kernels on a TPU and interpret them on
the CPU (tests). A caller may still pass ``interpret=`` explicitly, as the
compile tests do for a described chip.

``pad_to`` is the one shared zero-padding helper (it lives in
``kernels/_padding.py`` so the kernels can import it without a cycle;
this module is its public home).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ell_deliver as _ell
from repro.kernels import fused_step as _fused
from repro.kernels import lif_step as _lif
from repro.kernels import stdp_update as _stdp
from repro.kernels import synapse_matmul as _matmul
from repro.kernels._padding import pad_to


def interpret_mode(backend: str | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode on ``backend``
    (default: JAX's default backend).

    The CPU interprets them (tests, small runs without a chip); a TPU
    compiles them with Mosaic. Any other backend is refused: running a
    kernel interpreted there would hide that the device never ran it.
    """
    backend = backend or jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}. Use impl='ref' there, or "
        f"set JAX_PLATFORMS=cpu to interpret the kernels.")


def _resolved(kernel):
    @functools.wraps(kernel)
    def call(*args, **kw):
        if kw.get("interpret") is None:
            kw["interpret"] = interpret_mode()
        return kernel(*args, **kw)
    return call


ell_deliver = _resolved(_ell.ell_deliver)
pack_spikes = _ell.pack_spikes
fused_step = _resolved(_fused.fused_step)
lif_step = _resolved(_lif.lif_step)
stdp_dense_update = _resolved(_stdp.stdp_dense_update)
synapse_matmul = _resolved(_matmul.synapse_matmul)

__all__ = ["synapse_matmul", "lif_step", "stdp_dense_update", "fused_step",
           "ell_deliver", "pack_spikes", "pad_to", "interpret_mode"]
