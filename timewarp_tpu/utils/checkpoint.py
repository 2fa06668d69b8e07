"""Checkpoint/resume to disk — SURVEY.md §5.4.

The reference has nothing here; the TPU build gets it almost for free
because every engine's complete simulation state is one pytree of
arrays (EngineState / EdgeState). Serialization is a plain ``.npz``
with a JSON tree-structure header — no framework dependency, stable
across hosts, and exact (integer state; the float leaves, if a
scenario adds any, round-trip bit-for-bit through npz).

Resume is ``engine.run(steps, state=load_state(path))`` — mid-run
trace-parity across a save/load boundary is pinned by
tests/test_checkpoint.py.

Format compatibility: checkpoints are tied to the engine-state pytree
of the code that wrote them; a state-layout change (e.g. round 3
removing the derived mb_valid/q_valid leaves) makes older .npz files
fail loudly at load ("checkpoint has N leaves / tree structure does
not match") rather than resume wrong state. There is no lossy or
structural migration — re-run from the scenario start or an on-format
checkpoint. The one sanctioned conversion is the **lossless int32 →
int64 widening** of a same-shape leaf (round 6: ``EngineState.
ev_count`` widened so event counts past ~2.1e9 cannot wrap — README
"Compatibility notes"); every int32 value is exactly representable in
int64, so a pre-widening checkpoint resumes bit-identically.

Engine interchange needs no conversion here: ``JaxEngine`` and its
sharded forms share ``EngineState`` bit-for-bit, so a checkpoint saved
under one resumes under another (tests/test_sharded.py) — unlike the
fused *ring* engine, whose packed layout needs its own
``to_edge_state`` / ``from_edge_state`` pair (fused_ring.py).

Batched (multi-world) states need nothing special either: the world
axis is a leading dim on every leaf, the template (the batched
engine's ``init_state()``) carries the same shapes, and the widening
rule above is shape-generic (tests/test_checkpoint.py batched leg).
A solo checkpoint will NOT load into a batched template (or vice
versa, or across different world counts) — the shape check fails
loudly, which is correct: there is no meaningful world-axis
migration. Store the seed fleet in ``meta`` (the CLI does) so resume
can refuse a mismatched fleet before the RNG streams diverge.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import zipfile
from typing import Any

import jax
import numpy as np

__all__ = ["save_state", "load_state", "load_world_state",
           "atomic_write"]

#: the layout every actionable corrupt-load error names
_LAYOUT = ("an .npz holding leaf_0..leaf_{n-1} state arrays plus "
           "__treedef__/__meta__/__n__/__leafsha__ headers, written "
           "by timewarp_tpu.utils.checkpoint.save_state")


def atomic_write(path: str, write_fn, mode: str = "wb") -> None:
    """Crash- and race-safe file replacement: ``write_fn(f)`` writes
    into a UNIQUE same-directory temp file (not merely per-pid — two
    threads saving the same path, e.g. a watchdog-abandoned sweep
    attempt racing its retry, must not truncate each other's bytes),
    which is fsync'd then ``os.replace``-d over ``path``. A reader or
    a crash sees the previous file or the new one, never a torn one.
    The one atomic-write idiom shared by checkpoints and the sweep
    journal's pack file."""
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(os.path.abspath(path)),
        prefix=os.path.basename(path) + ".tmp.")
    try:
        with os.fdopen(fd, mode) as f:
            write_fn(f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def save_state(path: str, state: Any, *, meta: dict = None) -> None:
    """Write a state pytree to ``path`` (.npz). ``meta`` (JSON-able)
    rides along — scenario name, seed, anything the loader wants to
    validate against.

    The write is **atomic**: the bytes go to a same-directory temp
    file, are fsync'd, then ``os.replace``-d over ``path`` — a crash
    (or concurrent reader) sees the previous checkpoint or the new
    one, never a torn file. This is what makes checkpoints safe to
    take every chunk in the sweep service's supervision loop (sweep/)."""
    leaves, treedef = jax.tree.flatten(state)
    arrays = {f"leaf_{i}": np.asarray(jax.device_get(x))
              for i, x in enumerate(leaves)}
    # per-leaf sha256 over the raw array bytes: load_state recomputes
    # and compares, so a state corrupted ON DISK (bit rot, external
    # truncation inside the zip's tolerance) fails loudly naming the
    # leaf instead of restoring garbage (integrity/, ISSUE 10
    # satellite — before this, the digests rode only in sweep meta
    # and nothing checked them at load)
    arrays["__leafsha__"] = np.frombuffer(json.dumps(
        [hashlib.sha256(arrays[f"leaf_{i}"].tobytes()).hexdigest()
         for i in range(len(leaves))]).encode(), dtype=np.uint8)
    arrays["__treedef__"] = np.frombuffer(
        str(treedef).encode(), dtype=np.uint8)
    arrays["__meta__"] = np.frombuffer(
        json.dumps(meta or {}).encode(), dtype=np.uint8)
    arrays["__n__"] = np.asarray(len(leaves))
    atomic_write(path, lambda f: np.savez(f, **arrays))


def _read_verified(path: str):
    """The shared raw read behind :func:`load_state` and
    :func:`load_world_state`: parse the .npz layout, verify every
    leaf's recorded sha256 (the at-rest integrity half of the
    detection law), and return ``(leaves, saved_treedef, meta)`` —
    all structure/shape policy stays with the caller."""
    try:
        with np.load(path) as z:
            n = int(z["__n__"])
            meta = json.loads(bytes(z["__meta__"].tobytes()).decode())
            saved_treedef = bytes(z["__treedef__"].tobytes()).decode()
            leaves = [z[f"leaf_{i}"] for i in range(n)]
            # pre-digest-chain checkpoints lack the header: loadable,
            # just unverified (there is nothing to verify against)
            leaf_sha = (json.loads(bytes(
                z["__leafsha__"].tobytes()).decode())
                if "__leafsha__" in z.files else None)
    except (FileNotFoundError, PermissionError, IsADirectoryError):
        # access problems are not corruption: relabeling EACCES as
        # "corrupt, delete it" would be destructive advice for an
        # intact file — let the real error name the real cause
        raise
    except (KeyError, ValueError, OSError, EOFError,
            zipfile.BadZipFile, json.JSONDecodeError) as e:
        # a raw unpickling/zip/shape error names neither the file nor
        # what a checkpoint is supposed to look like — make the
        # failure actionable (writes have been atomic since this
        # module grew os.replace, so a torn file means external
        # truncation/corruption, not a crashed writer); the raw error
        # stays chained for whoever needs the forensic detail
        raise ValueError(
            f"checkpoint {path!r} is truncated or corrupt "
            f"({type(e).__name__}: {e}); expected layout: {_LAYOUT}. "
            f"Delete the file and resume from an earlier checkpoint "
            f"or re-run from the scenario start.") from e
    if leaf_sha is not None:
        # verify the recorded digests BEFORE any widening/unflatten:
        # the shas cover the bytes as written, and a corrupt leaf must
        # never reach a resumed run (integrity/ detection law's
        # at-rest half). The error names file, leaf, and both digests
        # — enough to decide "restore an earlier checkpoint" without
        # forensic tooling.
        if len(leaf_sha) != n:
            raise ValueError(
                f"checkpoint {path!r} records {len(leaf_sha)} leaf "
                f"digests for {n} leaves; expected layout: {_LAYOUT}")
        for i, got in enumerate(leaves):
            actual = hashlib.sha256(
                np.ascontiguousarray(got).tobytes()).hexdigest()
            if actual != leaf_sha[i]:
                raise ValueError(
                    f"checkpoint {path!r} leaf {i} failed its "
                    f"recorded sha256 digest (expected "
                    f"{leaf_sha[i][:16]}…, actual {actual[:16]}…): "
                    "the state bytes were corrupted on disk — delete "
                    "the file and resume from an earlier verified "
                    "checkpoint (docs/integrity.md)")
    return leaves, saved_treedef, meta


def load_state(path: str, like: Any, *, expect_meta: dict = None):
    """Read a state pytree saved by :func:`save_state`. ``like`` is a
    template pytree with the same structure (e.g. ``engine.init_state()``)
    — the loaded leaves are checked against its shapes/dtypes, so a
    checkpoint from a different scenario config fails loudly instead of
    resuming garbage. Returns ``(state, meta)``."""
    leaves, saved_treedef, meta = _read_verified(path)
    n = len(leaves)
    t_leaves, treedef = jax.tree.flatten(like)
    if len(t_leaves) != n:
        raise ValueError(
            f"checkpoint has {n} leaves, template has {len(t_leaves)}")
    if saved_treedef != str(treedef):
        # leaf order is structure-dependent: same leaf count/shapes with
        # a different tree would resume with fields silently swapped
        raise ValueError(
            f"checkpoint tree structure does not match template:\n"
            f"  saved:    {saved_treedef}\n  template: {treedef}")
    for i, (got, want) in enumerate(zip(leaves, t_leaves)):
        w = np.asarray(want)
        if (got.shape == w.shape and got.dtype == np.int32
                and w.dtype == np.int64):
            # the sanctioned lossless widening (module docstring):
            # a leaf the state layout grew from int32 to int64 —
            # ev_count, round 6 — resumes exactly from an old file
            leaves[i] = got.astype(np.int64)
            continue
        if got.shape != w.shape or got.dtype != w.dtype:
            raise ValueError(
                f"checkpoint leaf {i}: {got.shape}/{got.dtype} does not "
                f"match template {w.shape}/{w.dtype}")
    if expect_meta:
        for k, v in expect_meta.items():
            if meta.get(k) != v:
                raise ValueError(
                    f"checkpoint meta mismatch: {k}={meta.get(k)!r}, "
                    f"expected {v!r}")
    state = jax.tree.unflatten(treedef, [jax.numpy.asarray(x)
                                         for x in leaves])
    return state, meta


def load_world_state(path: str, like: Any, world: int):
    """Read ONE world's slice of a *batched* checkpoint saved by
    :func:`save_state` — the counterfactual-forking loader
    (timewarp_tpu/search/fork.py, docs/search.md): snapshot a fleet
    mid-run, then continue just world ``world`` under K divergent
    fault suffixes without re-running the shared prefix.

    ``like`` is a SOLO-shaped template (e.g. world 0 of the fork
    engine's ``init_state()``); every checkpoint leaf must carry the
    template's shape behind one shared leading world axis. Two
    sanctioned conversions, both exact: the int32 → int64 widening
    :func:`load_state` already honors, and **fault-row growth** — a
    1-D bool leaf (the ``restart_done`` restart-consumption ledger)
    whose template grew MORE rows than the checkpoint holds pads with
    False, because a fork suffix may append crash events and new
    crash rows start with their restart un-consumed by definition
    (padding rows are inert until their window opens —
    faults/schedule.py FaultTables). Returns ``(state, meta)``, the
    state solo-shaped."""
    leaves, saved_treedef, meta = _read_verified(path)
    n = len(leaves)
    t_leaves, treedef = jax.tree.flatten(like)
    if len(t_leaves) != n:
        raise ValueError(
            f"checkpoint has {n} leaves, template has {len(t_leaves)}")
    if saved_treedef != str(treedef):
        raise ValueError(
            f"checkpoint tree structure does not match template:\n"
            f"  saved:    {saved_treedef}\n  template: {treedef}")
    if not leaves:
        raise ValueError(f"checkpoint {path!r} holds no state leaves")
    B = int(leaves[0].shape[0]) if leaves[0].ndim else 0
    if B < 1:
        raise ValueError(
            f"checkpoint {path!r} is not a batched state (leaf 0 has "
            f"no leading world axis) — load_world_state slices a "
            "world axis; solo checkpoints load via load_state")
    w = int(world)
    if not 0 <= w < B:
        raise ValueError(
            f"world {w} out of range for a {B}-world checkpoint "
            f"{path!r}")
    out = []
    for i, (got, want) in enumerate(zip(leaves, t_leaves)):
        tw = np.asarray(want)
        if got.ndim != tw.ndim + 1 or got.shape[0] != B:
            raise ValueError(
                f"checkpoint leaf {i}: {got.shape}/{got.dtype} is not "
                f"a [{B}, ...] world-stacked form of the solo "
                f"template {tw.shape}/{tw.dtype}")
        sl = got[w]
        if sl.shape == tw.shape and sl.dtype == np.int32 \
                and tw.dtype == np.int64:
            sl = sl.astype(np.int64)    # the sanctioned widening
        elif sl.dtype == np.bool_ and tw.dtype == np.bool_ \
                and sl.ndim == 1 and tw.ndim == 1 \
                and sl.shape[0] < tw.shape[0]:
            # fault-row growth (docstring): new rows start un-consumed
            grown = np.zeros(tw.shape, np.bool_)
            grown[:sl.shape[0]] = sl
            sl = grown
        if sl.shape != tw.shape or sl.dtype != tw.dtype:
            raise ValueError(
                f"checkpoint leaf {i} world {w}: {sl.shape}/{sl.dtype}"
                f" does not match template {tw.shape}/{tw.dtype}")
        out.append(sl)
    state = jax.tree.unflatten(treedef, [jax.numpy.asarray(x)
                                         for x in out])
    return state, meta
