"""Sharded checkpointing with atomic manifests and async save.

Layout on disk::

    <dir>/step_000123/
        manifest.json        # tree structure, shapes, dtypes, step, digest
        arr_00000.npy ...    # one file per leaf (host-gathered)
    <dir>/LATEST             # atomic pointer (written last)

Restore is topology-agnostic: leaves are loaded on host and re-sharded by
the caller's in_shardings — a restart on a *different mesh* works, which
together with deterministic synapse/data regeneration gives the elastic
restart story (runtime/fault_tolerance.py).

Writes are crash-ATOMIC: every save stages into a fresh uniquely-named
temp dir (pid + in-process counter — a SIGKILLed save can never collide
with, or be half-adopted by, a retry of the same step), arrays and the
manifest are fsynced before the single ``os.replace`` into place, and
LATEST flips only after that — a rank killed at ANY instant leaves either
the previous checkpoint or the complete new one, never a torn "latest"
(tests/test_checkpoint.py kills a save mid-flight). Orphaned stage dirs
from killed saves are swept by the next successful save (and by
:func:`gc_stale_stages`, which the supervisor runs before restoring).

Elasticity (DESIGN.md §Elasticity): :func:`reshard` re-tiles a stacked
``DistState`` saved on an R-rank mesh for an R'-rank mesh by routing
every leaf through the global coordinate system in ``core/partition.py``
— bitwise on static nets, and exactly state-preserving under STDP (the
live weights/traces are per-column data and re-partition losslessly).
"""
from __future__ import annotations

import itertools
import json
import hashlib
import os
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

_STAGE_SEQ = itertools.count()


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(k) for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def save(ckpt_dir: str, step: int, tree: Any, *, blocking: bool = True,
         meta: Optional[dict] = None):
    """Save a pytree. With ``blocking=False`` the device->host transfer
    happens inline but file IO runs on a background thread (async save).

    ``meta`` (JSON-serializable) is stored in the manifest — used to
    record run provenance such as the plasticity switch: a plastic
    DistState carries live weights + STDP traces as extra leaves, so its
    tree is structurally incompatible with a static run's and restore
    will reject the mismatch; the recorded meta turns that into a
    diagnosable error (read it back with :func:`load_manifest`).
    """
    paths, leaves, _ = _flatten_with_paths(tree)
    host_leaves = [np.asarray(jax.device_get(x)) for x in leaves]

    def _write():
        # unique stage name: a save SIGKILLed mid-write leaves an orphan
        # that a RETRY of the same step can never open/adopt — the retry
        # stages fresh and the orphan is swept below / by gc_stale_stages
        stage = os.path.join(
            ckpt_dir,
            f"_tmp_step_{step:09d}.{os.getpid()}.{next(_STAGE_SEQ)}")
        final = os.path.join(ckpt_dir, f"step_{step:09d}")
        os.makedirs(stage)
        digest = hashlib.sha256()
        for i, arr in enumerate(host_leaves):
            p = os.path.join(stage, f"arr_{i:05d}.npy")
            with open(p, "wb") as f:
                np.save(f, arr)
                f.flush()
                os.fsync(f.fileno())
            digest.update(arr.tobytes()[:4096])
        manifest = {
            "step": step,
            "paths": paths,
            "shapes": [list(a.shape) for a in host_leaves],
            "dtypes": [str(a.dtype) for a in host_leaves],
            "digest": digest.hexdigest(),
            "meta": meta or {},
        }
        with open(os.path.join(stage, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(stage, final)
        latest_tmp = os.path.join(ckpt_dir, "LATEST.tmp")
        with open(latest_tmp, "w") as f:
            f.write(os.path.basename(final))
            f.flush()
            os.fsync(f.fileno())
        os.replace(latest_tmp, os.path.join(ckpt_dir, "LATEST"))
        # durability: persist the renames before reporting success
        dfd = os.open(ckpt_dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        gc_stale_stages(ckpt_dir, skip_pid=os.getpid())

    if blocking:
        _write()
        return None
    t = threading.Thread(target=_write, daemon=False)
    t.start()
    return t


def gc_stale_stages(ckpt_dir: str, *, skip_pid: Optional[int] = None) -> int:
    """Remove orphaned ``_tmp_step_*`` stage dirs left by saves that were
    killed mid-write (the supervisor calls this before restoring after a
    worker death; each successful save sweeps too). ``skip_pid`` protects
    the calling process's own concurrent async-save stages. Returns the
    number of stages removed; never touches completed ``step_*`` dirs."""
    removed = 0
    try:
        names = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return 0
    for name in names:
        if not name.startswith("_tmp_step_"):
            continue
        parts = name.split(".")
        if (skip_pid is not None and len(parts) >= 2
                and parts[1] == str(skip_pid)):
            continue
        shutil.rmtree(os.path.join(ckpt_dir, name), ignore_errors=True)
        removed += 1
    return removed


def latest_step(ckpt_dir: str) -> Optional[int]:
    try:
        with open(os.path.join(ckpt_dir, "LATEST")) as f:
            name = f.read().strip()
        return int(name.split("_")[-1])
    except (FileNotFoundError, ValueError):
        return None


def load_manifest(ckpt_dir: str, step: Optional[int] = None) -> dict:
    """Read a checkpoint's manifest (incl. ``meta``) without the arrays."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:09d}")
    with open(os.path.join(d, "manifest.json")) as f:
        return json.load(f)


def restore(ckpt_dir: str, tree_like: Any, step: Optional[int] = None,
            *, expect_mesh: Optional[tuple] = None):
    """Restore into the structure of ``tree_like``. Returns (tree, step).

    Verifies the manifest digest (detects torn/corrupt checkpoints) and —
    for every ``tree_like`` leaf that carries a shape (placeholder scalars
    are skipped) — that the saved leaf's shape and dtype match, naming
    the offending leaf path and both shapes in the error. This catches
    geometry drift (restoring a 4x4-grid checkpoint into an 8x8 run, or a
    B=4 batched service state into B=2 slots) *before* tree_unflatten
    scatters misshapen arrays into the state.

    ``expect_mesh`` — (tiles_y, tiles_x) of the restoring mesh. When the
    manifest records the writer's mesh (``meta["mesh"]``, written by the
    supervisor) and it differs, restore refuses with an error naming both
    mesh shapes: a stacked DistState is tiled for the mesh that wrote it
    and must go through :func:`reshard` first, not be sliced blindly."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:09d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    if expect_mesh is not None:
        saved_mesh = manifest.get("meta", {}).get("mesh")
        if saved_mesh is not None and tuple(saved_mesh) != tuple(expect_mesh):
            raise ValueError(
                f"checkpoint mesh mismatch: step {step} was saved on a "
                f"{saved_mesh[0]}x{saved_mesh[1]} tile mesh but this run "
                f"restores onto a {expect_mesh[0]}x{expect_mesh[1]} tile "
                f"mesh — re-tile the stacked state through reshard() "
                f"(DESIGN.md §Elasticity) instead of restoring directly")
    paths, want_leaves, treedef = _flatten_with_paths(tree_like)
    if manifest["paths"] != paths:
        raise ValueError(
            "checkpoint tree mismatch:\n saved: %s...\n want: %s..."
            % (manifest["paths"][:3], paths[:3]))
    for path, want, saved_shape, saved_dtype in zip(
            paths, want_leaves, manifest["shapes"], manifest["dtypes"]):
        if not hasattr(want, "shape"):   # placeholder leaf (e.g. int 0)
            continue
        if list(want.shape) != list(saved_shape):
            raise ValueError(
                f"checkpoint shape mismatch at leaf {path!r}: saved "
                f"{tuple(saved_shape)}, want {tuple(want.shape)} "
                f"(step {step} was written for a different geometry)")
        want_dtype = str(np.dtype(want.dtype))
        if want_dtype != saved_dtype:
            raise ValueError(
                f"checkpoint dtype mismatch at leaf {path!r}: saved "
                f"{saved_dtype}, want {want_dtype}")
    leaves = []
    digest = hashlib.sha256()
    for i in range(len(paths)):
        arr = np.load(os.path.join(d, f"arr_{i:05d}.npy"))
        digest.update(arr.tobytes()[:4096])
        leaves.append(arr)
    if digest.hexdigest() != manifest["digest"]:
        raise ValueError(f"checkpoint digest mismatch at step {step}")
    return jax.tree_util.tree_unflatten(treedef, leaves), step


# ---------------------------------------------------------------------------
# Elastic mesh resharding (DESIGN.md §Elasticity)
# ---------------------------------------------------------------------------
#
# A replicated stacked DistState (core/exchange.py, replicate_state=True)
# carries every leaf with a leading process-major shard axis S. reshard()
# re-tiles that host tree from the mesh that wrote it (from_spec) to any
# mesh of the same column grid (to_spec) by classifying each leaf from
# its field name and routing it through the global coordinate system:
#
#   column-major  (S, C, ...)            lif.v/c/refrac, w_local, rem_w,
#                                        x_pre/x_post, last_spike_t
#       -> global column-id order -> re-tile (lossless permutation)
#   tile frame    (S, th, tw, N)         pending
#       -> global (gh, gw, N) frame -> re-tile
#   extended frame (S[, D], th+2r, tw+2r, N)   hist_ext, trace_ext,
#                                        ext_pending
#       -> interior extracted, assembled globally, zero-padded by r, and
#          RE-WINDOWED for each new tile. Halo cells hold neighbour
#          interiors (zeros past the open sheet boundary), so the rebuilt
#          rings are bitwise what a run on the new mesh would hold — stale
#          ring buffers are never copied across meshes.
#   step counter  (S,)                   t — equal on every shard; verified
#   global sums   (S[, 2])               spike/event counts (int32
#       core/counters.py pairs) + ISI moments (integer-valued f32) —
#       partial per-shard sums whose psum is the observable; the total
#       moves to shard 0 (exact, order-independent)
#   per-step flag (S,)                   aer_sat — write-only scan output,
#       reset to False for the new mesh

_COLUMN_LEAVES = frozenset(
    {"v", "c", "refrac", "w_local", "rem_w", "x_pre", "x_post",
     "last_spike_t"})
_EXTENDED_LEAVES = frozenset({"trace_ext", "ext_pending"})
_SUM_LEAVES = frozenset(
    {"spike_count", "event_count", "isi_sum", "isi_sumsq", "isi_count"})
# Integrity-guard leaves (runtime/integrity.GuardState) are per-run
# diagnostic verdicts, not trajectory state: the supervisor only ever
# resumes from a CLEAN checkpoint (a tripped guard aborts the step range
# that would have saved it), so a resharded run starts with a fresh guard.
_GUARD_ZERO_LEAVES = frozenset(
    {"tripped", "trip_code", "sat_run", "checksum_fails"})


def _reshard_extended(x, from_spec, to_spec):
    """(S, th+2r, tw+2r, *rest) halo-extended frames -> re-tiled."""
    from repro.core import partition

    r = from_spec.radius
    interior = x[:, r:r + from_spec.tile_h, r:r + from_spec.tile_w]
    g = partition.tiles_to_global(np.ascontiguousarray(interior), from_spec)
    pad = [(r, r), (r, r)] + [(0, 0)] * (g.ndim - 2)
    gp = np.pad(g, pad)
    s_new = to_spec.tiles_y * to_spec.tiles_x
    th, tw = to_spec.tile_h, to_spec.tile_w
    out = np.empty((s_new, th + 2 * r, tw + 2 * r, *g.shape[2:]), x.dtype)
    for s in range(s_new):
        ty, tx = partition.shard_tile_coords(to_spec, s)
        out[s] = gp[ty * th:ty * th + th + 2 * r,
                    tx * tw:tx * tw + tw + 2 * r]
    return out


def _reshard_leaf(name: str, x, from_spec, to_spec):
    from repro.core import partition

    s_new = to_spec.tiles_y * to_spec.tiles_x
    if name in _COLUMN_LEAVES:
        g = partition.columns_to_global(x, from_spec)
        return partition.global_to_columns(g, to_spec)
    if name == "pending":
        g = partition.tiles_to_global(x, from_spec)
        return partition.global_to_tiles(g, to_spec)
    if name == "hist_ext":
        # (S, D, th+2r, tw+2r, N): re-window each delay slot of the ring
        return np.stack([_reshard_extended(x[:, d], from_spec, to_spec)
                         for d in range(x.shape[1])], axis=1)
    if name in _EXTENDED_LEAVES:
        return _reshard_extended(x, from_spec, to_spec)
    if name == "t":
        if not np.all(x == x.flat[0]):
            raise ValueError(
                f"cannot reshard: step counter 't' disagrees across "
                f"shards ({np.unique(x)}) — the checkpoint is not a "
                f"clean post-step snapshot")
        return np.full((s_new,), x.flat[0], x.dtype)
    if name in _SUM_LEAVES:
        # f32 ISI moments, or core/counters.py [hi, lo] int32 pairs
        # (summed word by word; the next add or value() carries lo)
        out = np.zeros((s_new,) + x.shape[1:], x.dtype)
        out[0] = x.sum(axis=0, dtype=np.float64).astype(x.dtype)
        return out
    if name == "aer_sat":
        return np.zeros((s_new,), x.dtype)
    if name in _GUARD_ZERO_LEAVES:
        return np.zeros((s_new,), x.dtype)
    if name == "trip_step":
        return np.full((s_new,), -1, x.dtype)
    raise ValueError(
        f"reshard does not know how to re-tile DistState leaf {name!r} "
        f"of shape {getattr(x, 'shape', None)} — a new DistState field "
        f"needs a mapping rule here (DESIGN.md §Elasticity)")


def reshard(tree: Any, from_spec, to_spec) -> Any:
    """Re-tile a replicated stacked DistState host tree from the mesh
    that wrote it to a different mesh of the SAME column grid.

    ``from_spec``/``to_spec`` are ``core.partition.TileSpec``s (derive
    them with ``make_rank_tile_spec(cfg, R)`` / ``(cfg, R')``). Returns a
    new host tree whose leading shard axis matches ``to_spec`` — feed it
    to ``make_distributed_resume(..., replicate_state=True)`` on the new
    mesh. Bitwise trajectory-preserving: static nets resume identically,
    and plastic runs carry their live weights/traces across (validated in
    tests/test_reshard.py and the chaos CI tier)."""
    gh_f = from_spec.tiles_y * from_spec.tile_h
    gw_f = from_spec.tiles_x * from_spec.tile_w
    gh_t = to_spec.tiles_y * to_spec.tile_h
    gw_t = to_spec.tiles_x * to_spec.tile_w
    if (gh_f, gw_f) != (gh_t, gw_t):
        raise ValueError(
            f"reshard requires the same global column grid: from_spec "
            f"covers {gh_f}x{gw_f}, to_spec covers {gh_t}x{gw_t}")
    if from_spec.radius != to_spec.radius:
        raise ValueError(
            f"reshard requires the same stencil radius (same cfg): "
            f"{from_spec.radius} != {to_spec.radius}")

    def leaf_fn(path, x):
        name = path[-1].name if hasattr(path[-1], "name") else str(path[-1])
        return _reshard_leaf(name, np.asarray(x), from_spec, to_spec)

    return jax.tree_util.tree_map_with_path(leaf_fn, tree)
