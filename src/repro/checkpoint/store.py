"""Checkpoint store: atomic sharded save/restore with elastic resharding.

Layout (one directory per step)::

    <dir>/step_000120/
        manifest.json      # tree structure, shapes, dtypes, sha256s
        leaf_00000.npy ...

Fault-tolerance properties:
  * **atomic**: written to ``step_X.tmp-<pid>`` then ``os.rename``d --
    a crash mid-write never corrupts the latest checkpoint;
  * **verified**: every leaf carries a sha256 in the manifest, checked
    on restore (detects torn/bit-rotted files before they poison a run);
  * **keep-k**: old steps garbage-collected after a successful rename;
  * **elastic**: restore takes ``shardings`` for the *new* mesh -- leaves
    are loaded on host and ``jax.device_put`` resharded, so a job can
    come back on a different pod count / tiling than it crashed on;
  * **async**: ``AsyncCheckpointer.save`` takes a *device-side* snapshot
    (an async buffer copy) and returns; the device->host transfer and
    the file writes both happen on a daemon thread, so the blocking D2H
    overlaps the caller's next segment of compute (double-buffered
    segment handoff) instead of serializing with it.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import shutil
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _tree_paths(tree):
    flat, treedef = jax.tree.flatten(tree)
    return flat, treedef


def save_checkpoint(directory: str, step: int, tree, keep: int = 3,
                    meta: Optional[dict] = None) -> str:
    """``meta``: optional JSON-serializable dict stored inside the
    step's manifest -- atomic with the checkpoint itself (a sidecar
    file could describe a checkpoint that never got published)."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + f".tmp-{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    flat, treedef = _tree_paths(tree)
    manifest = {"step": step, "treedef": str(treedef),
                "meta": meta or {}, "leaves": []}
    for i, leaf in enumerate(flat):
        arr = np.asarray(jax.device_get(leaf))
        fn = f"leaf_{i:05d}.npy"
        np.save(os.path.join(tmp, fn), arr)
        with open(os.path.join(tmp, fn), "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        manifest["leaves"].append({
            "file": fn, "shape": list(arr.shape), "dtype": str(arr.dtype),
            "sha256": digest,
        })
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)                    # atomic publish
    _gc(directory, keep)
    return final


def _gc(directory: str, keep: int):
    steps = sorted(d for d in os.listdir(directory)
                   if d.startswith("step_") and ".tmp" not in d)
    for d in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(directory, d), ignore_errors=True)


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(directory)
             if d.startswith("step_") and ".tmp" not in d]
    return max(steps) if steps else None


def checkpoint_meta(directory: str, step: int) -> dict:
    """The ``meta`` dict stored with ``save_checkpoint`` (empty for
    checkpoints written without one)."""
    path = os.path.join(directory, f"step_{step:08d}", "manifest.json")
    with open(path) as f:
        return json.load(f).get("meta", {})


def refuse_meta_drift(meta: dict, mine: dict, keys, where: str):
    """Refuse to resume a checkpoint whose manifest meta disagrees with
    the current config on any of ``keys`` (keys absent from ``meta`` are
    skipped: pre-versioning manifests).  Shared by the drivers so every
    identity refusal carries the same actionable message.

    Analyzer-checked: repro-lint's ``meta-drift`` pass cross-references
    every meta key the sim driver writes against the keys validated
    here (or otherwise read on the restore path)."""
    for key in keys:
        if key in meta and meta[key] != mine[key]:
            raise ValueError(
                f"checkpoint in {where} was written with "
                f"{key}={meta[key]}, current config has "
                f"{key}={mine[key]} -- resuming would silently "
                "continue a different model")


def restore_checkpoint(directory: str, step: int, like,
                       shardings=None, verify: bool = True):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings``: optional matching pytree of
    NamedShardings for the *current* mesh (elastic restore)."""
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    flat_like, treedef = _tree_paths(like)
    if len(manifest["leaves"]) != len(flat_like):
        raise ValueError(
            f"checkpoint has {len(manifest['leaves'])} leaves, expected "
            f"{len(flat_like)} -- structure mismatch")
    leaves = []
    for meta, want in zip(manifest["leaves"], flat_like):
        fp = os.path.join(path, meta["file"])
        if verify:
            with open(fp, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            if digest != meta["sha256"]:
                raise IOError(f"checksum mismatch in {fp}")
        arr = np.load(fp)
        if tuple(arr.shape) != tuple(want.shape):
            raise ValueError(f"{meta['file']}: shape {arr.shape} != "
                             f"expected {want.shape}")
        want_dtype = np.dtype(want.dtype)
        if arr.dtype != want_dtype:
            raise ValueError(
                f"{meta['file']}: dtype {arr.dtype} != expected "
                f"{want_dtype} -- a drifted dtype would silently "
                "recompile or corrupt the jitted step")
        leaves.append(arr)
    tree = jax.tree.unflatten(treedef, leaves)
    if shardings is not None:
        tree = jax.device_put(tree, shardings)
    return tree


_THREAD_ASSERTS = False


def set_thread_asserts(enabled: bool):
    """Toggle the sanitizer's owning-thread assertion mode on every
    ``AsyncWriterThread`` (``--sanitize`` wires this on).  When on,
    subclasses' non-queue state mutations (``_assert_owner`` call
    sites: spool offset counters, checkpoint submission) raise if
    invoked off the constructing thread -- the PR 4 spool-offset race
    class, made loud instead of silently corrupting manifests."""
    global _THREAD_ASSERTS
    _THREAD_ASSERTS = enabled


def thread_asserts_enabled() -> bool:
    return _THREAD_ASSERTS


class AsyncWriterThread:
    """Daemon-thread work queue with deferred error surfacing.

    Shared writer machinery for everything that must stay off the hot
    path (checkpoints, spike-log spooling): ``_submit`` enqueues, the
    daemon thread calls ``_write(item)``, a failing write is latched and
    re-raised on the next ``_submit``/``wait`` (never swallowed),
    ``wait()`` drains pending work, ``close()`` shuts the thread down.

    Only the queue is thread-safe.  Everything else a subclass keeps
    (offset counters, snapshot buffers) is owned by the constructing
    thread; subclasses call ``_assert_owner`` before mutating such
    state, which raises under ``set_thread_asserts(True)``.
    """

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._err: Optional[BaseException] = None
        self._owner = threading.current_thread()
        self._t = threading.Thread(target=self._worker, daemon=True)
        self._t.start()

    def _assert_owner(self, what: str):
        """Sanitizer hook: non-queue state is single-owner by contract."""
        if _THREAD_ASSERTS and threading.current_thread() is not self._owner:
            raise AssertionError(
                f"{type(self).__name__}.{what} called from thread "
                f"{threading.current_thread().name!r} but this writer's "
                f"non-queue state is owned by {self._owner.name!r} -- "
                "offsets/manifests would race (run without --sanitize "
                "only if you know the access is synchronized)")

    def _write(self, item):
        raise NotImplementedError

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._write(item)
            except BaseException as e:   # surfaced on next submit/wait
                self._err = e
            finally:
                self._q.task_done()

    def _submit(self, item):
        if self._err:
            raise self._err
        self._q.put(item)

    def wait(self):
        self._q.join()
        if self._err:
            raise self._err

    def close(self):
        self.wait()
        self._q.put(None)
        self._t.join()


class AsyncCheckpointer(AsyncWriterThread):
    """Daemon-thread writer; ``save`` returns once the *device-side*
    snapshot is dispatched -- the device->host transfer runs in
    ``_write`` on the worker thread, overlapped with whatever the
    caller computes next.  ``wait()`` drains pending writes (call
    before exit).

    ``telemetry``: optional ``obs.telemetry.Telemetry``.  The caller's
    snapshot cost (``ckpt.snapshot``, the device-side buffer copy) and
    the worker's D2H transfer (``ckpt.d2h``) / file write
    (``ckpt.write``) become separate spans on their own threads, so the
    double-buffered overlap with segment compute is visible in the
    Chrome trace instead of inferred."""

    def __init__(self, directory: str, keep: int = 3, telemetry=None):
        self.directory = directory
        self.keep = keep
        if telemetry is None:
            # imported lazily: obs.spool imports this module, so a
            # top-level obs.telemetry import would cycle
            from ..obs.telemetry import NULL as telemetry
        self.tel = telemetry
        super().__init__()

    def _write(self, item):
        # the D2H transfer happens here, on the worker: it runs
        # concurrently with the caller's next segment instead of
        # blocking save().  Fetched explicitly (save_checkpoint's
        # per-leaf device_get is a no-op on host arrays) so transfer
        # and file write land in separate spans.
        step, tree, meta = item
        nbytes = (sum(a.nbytes for a in jax.tree.leaves(tree))
                  if self.tel.enabled else None)     # a span attribute
        with self.tel.span("ckpt.d2h", step=step, bytes=nbytes):
            host = jax.device_get(tree)
        with self.tel.span("ckpt.write", step=step):
            save_checkpoint(self.directory, step, host, self.keep,
                            meta=meta)

    def save(self, step: int, tree, meta: Optional[dict] = None):
        self._assert_owner("save")
        # Buffer copy, not host transfer: the caller's very next step
        # typically *donates* the live state to the jitted segment, so
        # the snapshot must not alias it -- but it can stay on device
        # until the worker drains it (double-buffered handoff).
        with self.tel.span("ckpt.snapshot", step=step):
            snap = jax.tree.map(jnp.copy, tree)
        self._submit((step, snap, meta))
