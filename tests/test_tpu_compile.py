"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, vector loads from ``ANY``-space refs, in-kernel
gathers, scoped-VMEM overflow). These tests compile every kernel a
normal entry point can select, at the paper's column size (N = 1,240),
with ``interpret=False`` for a described ``v5e:2x2`` topology, and the
jitted steps that call them — so the chip's compiler checks every change
at no chip time. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture, never at import (only one
process may load the TPU library; see the notes on running on a chip in
README.md). Code under test that asks ``jax.default_backend()`` still
sees the CPU here, so the step tests steer the interpret decision to
"compile" with a monkeypatch.
"""
import dataclasses
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import dpsnn
from repro.configs.base import GuardConfig, NeuronConfig, STDPConfig
from repro.kernels import ops

N = 1240          # the paper's neurons per column
C = 16            # columns in one kernel call
SMALL_GRID = 2    # columns per side of the compiled whole steps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


@pytest.fixture
def compiles_kernels(monkeypatch):
    """Make the one interpret decision answer 'compile' for this test."""
    monkeypatch.setattr(ops, "interpret_mode", lambda backend=None: False)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("variant,wdtype,nw", [
    pytest.param("static", jnp.float32, N, id="static"),
    pytest.param("stdp", jnp.float32, N, id="stdp"),
    pytest.param("guard", jnp.float32, N, id="guard"),
    pytest.param("static", jnp.bfloat16, N, id="static-bf16"),
    pytest.param("static", jnp.bfloat16, 1280, id="static-bf16-1280")])
def test_fused_step_compiles(one_chip, variant, wdtype, nw):
    """Every variant at float32 weights; bfloat16 blocks, whose 88-row
    last source slice at N = 1,240 is off bfloat16's 16-row tile, at
    their own width and lane-padded to 1,280 targets (the 48x48 grid on
    one chip stores them so)."""
    vec = _spec(one_chip, (C, N))
    args = [vec, vec, _spec(one_chip, (C, N), jnp.int32), vec,
            _spec(one_chip, (C, N, nw), wdtype), vec, vec]
    kw = {}
    if variant == "stdp":
        args += [vec, vec]
        kw["scfg"] = STDPConfig()
    if variant == "guard":
        kw["gcfg"] = GuardConfig(enabled=True)
    text = _compiled_text(lambda *a: ops.fused_step(
        NeuronConfig(), *a, interpret=False, **kw), *args)
    assert "tpu_custom_call" in text


def test_stdp_dense_update_compiles(one_chip):
    vec = _spec(one_chip, (C, N))
    text = _compiled_text(lambda *a: ops.stdp_dense_update(
        *a, a_plus=0.01, a_minus=0.012, lr=1.0, w_max=0.84,
        interpret=False), _spec(one_chip, (C, N, N)), vec, vec, vec, vec)
    assert "tpu_custom_call" in text


def test_synapse_matmul_compiles(one_chip):
    text = _compiled_text(
        lambda s, w: ops.synapse_matmul(s, w, interpret=False),
        _spec(one_chip, (C, N)), _spec(one_chip, (C, N, N)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("family,n,wdtype,kw", [
    pytest.param("gauss", N, jnp.float32, None, id="gauss-1240"),
    pytest.param("gauss_exp", N, jnp.float32, None, id="gauss_exp-1240"),
    pytest.param("gauss", 100, jnp.float32, None, id="gauss-100"),
    pytest.param("gauss", N, jnp.bfloat16, None, id="gauss-1240-bf16"),
    pytest.param("gauss", N, jnp.bfloat16, 256, id="gauss-1240-bf16-256"),
    pytest.param("gauss_exp", N, jnp.float32, 1152,
                 id="gauss_exp-1240-1152")])
def test_ell_deliver_compiles(one_chip, family, n, wdtype, kw):
    """Remote delivery over packed spike words at the paper's 24x24 grid
    (C = 576): the Gaussian stencil (K = 248) and the long-range one
    (K = 1,028, whose whole-column blocks need more than the default
    scoped VMEM); a column size off the 8-row tiling, whose row loop
    the kernel unrolls; bfloat16 weights, whose 248-row inner steps start
    off bfloat16's 16-row tile; and tables lane-padded to ``kw`` slots,
    the absent ones counted with the last offset's. No gather is left in
    the program."""
    from repro.core.connectivity import build_stencil

    cfg = dpsnn.with_family(
        dataclasses.replace(dpsnn.GRID_24, neurons_per_column=n), family)
    stencil = build_stencil(cfg)
    k, n_cols = stencil.k_total, cfg.n_columns
    if n == N:
        assert (n_cols, k) == (576, {"gauss": 248,
                                     "gauss_exp": 1028}[family])
    kw = kw or k
    slots = [ko for (_dy, _dx, ko, _d, _p) in stencil.offsets]
    slots[-1] += kw - k
    text = _compiled_text(
        lambda *a: ops.ell_deliver(*a, slots=tuple(slots), interpret=False),
        _spec(one_chip, (n_cols, stencil.n_offsets, -(-n // 32)),
              jnp.uint32),
        _spec(one_chip, (n_cols, n, kw), jnp.int32),
        _spec(one_chip, (n_cols, n, kw), wdtype))
    assert "tpu_custom_call" in text
    assert " gather(" not in text


def test_lif_step_compiles(one_chip):
    vec = _spec(one_chip, (C, N))
    text = _compiled_text(
        lambda *a: ops.lif_step(NeuronConfig(), *a, interpret=False),
        vec, vec, _spec(one_chip, (C, N), jnp.int32), vec)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("impl", ["pallas", "pallas_fused"])
def test_single_shard_step_compiles(one_chip, compiles_kernels, impl):
    """One jitted step of ``core/network.make_step_fn`` at N = 1,240 and
    the Gaussian stencil's full neighbour-table width, through the
    interpret decision an entry point takes."""
    from repro.core import network as net
    from repro.core import simulation as sim

    cfg = dataclasses.replace(dpsnn.GRID_24, grid_h=SMALL_GRID,
                              grid_w=SMALL_GRID)
    params, state = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: sim.build(cfg)))
    text = _compiled_text(net.make_step_fn(cfg, impl=impl), params, state)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_tpu_row_major_is_the_compilers_layout(topo, dtype):
    """``network.tpu_row_major`` says what the v5e's own default layout of
    synapse-table shapes is: grids of 1 to 96 columns a side (whole lane
    tiles of columns and not), column sizes on and off the 8-row tiling,
    widths of N, K = 24, 248 and 1,028, and whole lanes."""
    from jax.experimental.layout import Layout

    from repro.core import network as net

    dev = topo.devices[0]
    for g, n, w in itertools.product(
            (1, 2, 3, 4, 8, 16, 24, 32, 40, 48, 96), (20, 100, 136, 1240),
            (None, 24, 248, 256, 1028, 1152)):
        shape = (g * g, n, w or n)
        layout = Layout.from_pjrt_layout(
            dev.client.get_default_layout(jnp.dtype(dtype), shape, dev))
        assert net.tpu_row_major(shape, jnp.dtype(dtype).itemsize) == (
            layout.major_to_minor == (0, 1, 2)), shape


@pytest.mark.parametrize("grid", [32, 40, 48])
def test_static_window_copies_no_params(one_chip, compiles_kernels, grid):
    """A static ``sim.run`` window of two steps on one chip, with the
    bfloat16 tables the chip's build stores (lane-padded at 32x32 and
    48x48, as they are at 40x40): no temporary or output of the program
    is as large as the local weights, so nothing copies them."""
    from repro.core import simulation as sim

    cfg = dataclasses.replace(dpsnn.GRID_48_BF16, grid_h=grid, grid_w=grid)
    params, state = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: sim.build(cfg)))
    mem = sim.run.lower(cfg, params, state, 2,
                        impl="pallas_fused").compile().memory_analysis()
    w_bytes = params.w_local.size * params.w_local.dtype.itemsize
    assert mem.temp_size_in_bytes < w_bytes / 8
    assert mem.output_size_in_bytes < w_bytes / 8


def test_distributed_step_compiles_on_2x2(mesh, compiles_kernels):
    """``make_distributed_run`` over the four chips of a v5e 2x2: the
    halo exchange lowers to collective-permutes between chips."""
    from repro.core import exchange

    cfg = dataclasses.replace(dpsnn.GRID_24, grid_h=SMALL_GRID,
                              grid_w=SMALL_GRID)
    run, _ = exchange.make_distributed_run(cfg, mesh, n_steps=2,
                                           impl="pallas_fused")
    text = run.lower().compile().as_text()
    assert "collective-permute" in text
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# Named scopes on the chip's compiled program: the ops the benchmark's
# per-layer metrics read carry the scope of their layer
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_SCOPE = re.compile(r'metadata=\{[^}]*?op_name="[^"]*?(dpsnn\.[\w\-]+)')


def _instructions(text):
    """[(computation, name, rest of line)] of an HLO module's text."""
    out, comp = [], None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            out.append((comp, m.group(1), m.group(2)))
    return out


def _scope(rhs):
    m = _SCOPE.search(rhs)
    return m.group(1) if m else None


def test_single_shard_step_scopes(one_chip, compiles_kernels):
    """Remote delivery is its layer's Mosaic kernel, with no gather left
    in its scope, and the fused step kernel is the neuron layer's."""
    from repro.core import network as net
    from repro.core import simulation as sim

    cfg = dataclasses.replace(dpsnn.GRID_24, grid_h=SMALL_GRID,
                              grid_w=SMALL_GRID)
    params, state = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: sim.build(cfg)))
    instrs = _instructions(_compiled_text(
        net.make_step_fn(cfg, impl="pallas_fused"), params, state))
    caller = {re.search(r"calls=%?([\w.\-]+)", rhs).group(1): rhs
              for _, _, rhs in instrs if " fusion(" in rhs}
    # a gather in a fusion counts under the fusion's own scope, as the
    # trace names it
    gather_scopes = {_scope(caller.get(c, r)) for c, _, r in instrs
                     if " gather(" in r}
    assert "dpsnn.remote" not in gather_scopes
    kernels = sorted(_scope(r) for _, _, r in instrs
                     if "tpu_custom_call" in r)
    assert kernels == ["dpsnn.neuron", "dpsnn.remote"]


def test_plastic_run_kernel_scopes(one_chip, compiles_kernels):
    """Under STDP each Mosaic kernel is remote delivery's, the neuron
    layer's (fused step) or the plasticity layer's (dense STDP update)."""
    from repro.core import simulation as sim

    cfg = dataclasses.replace(dpsnn.GRID_24, grid_h=SMALL_GRID,
                              grid_w=SMALL_GRID, stdp=True)
    params, state = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: sim.build(cfg)))
    text = sim.run.lower(cfg, params, state, 1,
                         impl="pallas_fused").compile().as_text()
    kernels = [_scope(r) for _, _, r in _instructions(text)
               if "tpu_custom_call" in r]
    assert sorted(kernels) == ["dpsnn.neuron", "dpsnn.remote", "dpsnn.stdp"]


def test_distributed_step_scopes_on_2x2(mesh, compiles_kernels):
    """Every collective-permute between the four chips is the halo
    exchange's."""
    from repro.core import exchange

    cfg = dataclasses.replace(dpsnn.GRID_24, grid_h=SMALL_GRID,
                              grid_w=SMALL_GRID)
    run, _ = exchange.make_distributed_run(cfg, mesh, n_steps=2,
                                           impl="pallas_fused")
    permutes = [r for _, _, r in _instructions(run.lower().compile()
                                               .as_text())
                if re.search(r" collective-permute(-start|-done)?\(", r)]
    assert permutes and all(_scope(r) == "dpsnn.halo" for r in permutes)
