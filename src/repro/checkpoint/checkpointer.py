"""Checkpointing — the fault-tolerance contract between TonY and the ML job.

Pytrees are flattened to path-keyed host arrays. Each checkpoint is a
``step_<n>`` directory holding one file of raw bytes per leaf
(``leaf_<i>.bin``) plus a ``COMMIT`` marker written last, whose JSON is the
manifest: each leaf's key, file, shape, dtype and size. A step without its
marker is half-written (the writer was killed mid-checkpoint, exactly the
situation the chaos harness creates on purpose) and is invisible to
``latest_step`` / ``restore`` / garbage collection. Directory staging +
atomic rename means a mid-write kill never corrupts the latest checkpoint,
which is what the AM's ``resume_step`` relaunch path relies on.

A restore maps each leaf file copy-on-write and views it as an array of the
template's shape and dtype; no byte is copied until the caller reads it. A
step is restored seconds after its commit, so its pages still sit in the
page cache, and ``jax.device_put`` reads them from there: no copy into fresh
process memory, which runs at about 1 GB/s on a TPU v5e host. Each leaf is a
plain writable ``ndarray`` whose writes stay in the process and never reach
the file; dropping it unmaps the file, and removing the step directory (the
garbage collection does) leaves a live mapping readable. Nothing rewrites a
committed leaf file in place: the writer stages new files and renames. Older
layouts still restore, chosen by what the step holds: a step directory with
``arrays.npz`` (a path-keyed npz archive) and no leaf manifest, and the flat
``ckpt_<n>.npz`` files (atomic by rename alone).

``AsyncCheckpointer`` moves the write off the training critical path: the
caller's ``save`` only snapshots device arrays to host and hands them to a
single background writer thread (bounded, depth 1 — a second save while one
is in flight blocks, never queues unboundedly). The writer reuses the same
staged-dir + COMMIT + atomic-rename protocol, and the ``on_commit`` callback
fires *from the writer, after the rename* — so anything published off it
(``ctx.shared["ckpt_step"]``) can only ever name a committed step, keeping
the AM's ``resume_step`` contract byte-identical to the sync path.
"""
from __future__ import annotations

import json
import math
import mmap
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Callable

import jax
import numpy as np

from repro.core import tracing

_SEP = "|"
_STEP_DIR = re.compile(r"step_(\d{8})")
_LEGACY_FILE = re.compile(r"ckpt_(\d{8})\.npz")
# re-checkpointing an existing step renames the old committed dir aside
# under this pattern before the replace; until the replace lands, the aside
# copy still counts as committed (no window where the step is lost)
_ASIDE_DIR = re.compile(r"\.aside-step_(\d{8})-.*")
COMMIT_MARKER = "COMMIT"
ARRAYS_FILE = "arrays.npz"          # the step layout before one file per leaf
# the writer writes leaf files in pieces of at most CHUNK_BYTES, so that no
# one call holds the process's memory for a whole leaf (on a TPU v5e host,
# one write of a 1.24 GB leaf stalled the training thread's steps by up to
# 1.7 s)
CHUNK_BYTES = 16 << 20


def _keyed(tree) -> list[tuple[str, object]]:
    """``tree``'s leaves in flatten order, each under its path key."""
    return [(_SEP.join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _byte_view(arr: np.ndarray) -> np.ndarray:
    """A flat ``uint8`` view of a C-contiguous array; also of 0-d arrays and
    of ``ml_dtypes`` ones such as bfloat16, which export no buffer of their
    own dtype."""
    return arr.reshape(-1).view(np.uint8)


def _flatten(tree) -> dict[str, np.ndarray]:
    """The host snapshot a checkpoint writes: path-keyed host arrays."""
    items = _keyed(tree)
    with tracing.span("ckpt.snapshot") as snapshot:
        # start every device->host transfer before materializing any of
        # them, so the copies overlap instead of serializing one blocking
        # d2h at a time
        for _, leaf in items:
            if hasattr(leaf, "copy_to_host_async"):
                try:
                    leaf.copy_to_host_async()
                except Exception:  # noqa: BLE001 - committed buffers still readable
                    pass
        flat = {key: np.asarray(leaf) for key, leaf in items}
        snapshot.attrs["bytes"] = sum(a.nbytes for a in flat.values())
        return flat


def tree_nbytes(tree) -> int:
    """Total leaf bytes — the payload size a checkpoint of ``tree`` writes."""
    return int(sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree)))


def step_dir(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step:08d}")


def _aside_dirs(directory: str, step: int) -> list[str]:
    """Committed aside copies of ``step`` (old dir renamed out of the way by
    a re-checkpoint that hasn't finished — or was killed mid-swap)."""
    try:
        entries = os.listdir(directory)
    except OSError:
        return []
    return sorted(
        os.path.join(directory, e) for e in entries
        if (m := _ASIDE_DIR.fullmatch(e)) and int(m.group(1)) == step
        and os.path.exists(os.path.join(directory, e, COMMIT_MARKER)))


def is_committed(directory: str, step: int) -> bool:
    """A step counts only once its COMMIT marker exists (or it is a legacy
    flat file, which was atomic by rename)."""
    if os.path.exists(os.path.join(step_dir(directory, step), COMMIT_MARKER)):
        return True
    if _aside_dirs(directory, step):
        return True
    return os.path.exists(os.path.join(directory, f"ckpt_{step:08d}.npz"))


def save_pytree(tree, directory: str, step: int,
                pre_commit: Callable[[], None] | None = None) -> str:
    """Write one checkpoint: stage into a tmp dir, add the COMMIT marker,
    atomically rename into place. A concurrent reader never observes a
    committed-but-incomplete step.

    Re-checkpointing an existing step never opens a lost-step window: the
    old committed dir is renamed aside (where ``latest_step``/``restore``
    still recognize it) and removed only after the replace lands — a kill at
    any point leaves either the old or the new committed copy visible.

    ``pre_commit`` (used by the chaos harness) runs after the arrays are
    staged and before the COMMIT marker is written — the writer-window kill
    point.
    """
    return _write(_flatten(tree), directory, step, pre_commit)


def _write(flat: dict[str, np.ndarray], directory: str, step: int,
           pre_commit: Callable[[], None] | None = None) -> str:
    """``save_pytree``'s write of a host snapshot (see there)."""
    os.makedirs(directory, exist_ok=True)
    final = step_dir(directory, step)
    tmp = tempfile.mkdtemp(dir=directory, prefix=f".tmp-step_{step:08d}-")
    try:
        leaves = []
        for i, (key, arr) in enumerate(flat.items()):
            name = f"leaf_{i}.bin"
            _write_leaf(os.path.join(tmp, name), arr)
            leaves.append({"key": key, "file": name, "shape": list(arr.shape),
                           "dtype": arr.dtype.name, "nbytes": arr.nbytes})
        if pre_commit is not None:
            pre_commit()
        with open(os.path.join(tmp, COMMIT_MARKER), "w") as f:
            json.dump({"step": step, "arrays": len(flat), "leaves": leaves}, f)
        aside = None
        if os.path.isdir(final):          # re-checkpointing the same step
            aside = os.path.join(
                directory, f".aside-step_{step:08d}-{os.urandom(4).hex()}")
            os.rename(final, aside)
        os.replace(tmp, final)
        if aside is not None:
            shutil.rmtree(aside, ignore_errors=True)
    finally:
        if os.path.isdir(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
    return final


def _write_leaf(path: str, arr: np.ndarray) -> None:
    """One leaf's raw bytes, in writes of at most ``CHUNK_BYTES``."""
    view = _byte_view(np.ascontiguousarray(arr))
    with open(path, "wb") as f:
        for at in range(0, view.nbytes, CHUNK_BYTES):
            f.write(view[at:at + CHUNK_BYTES])


def _committed_steps(directory: str) -> list[int]:
    """All fully-written steps, tolerating junk: non-step entries, staging
    dirs and half-written (marker-less) steps are skipped, not errors.
    Committed aside copies (a re-checkpoint killed mid-swap) still count."""
    steps = set()
    try:
        entries = os.listdir(directory)
    except OSError:
        return []
    for entry in entries:
        if (m := _STEP_DIR.fullmatch(entry)):
            if os.path.exists(os.path.join(directory, entry, COMMIT_MARKER)):
                steps.add(int(m.group(1)))
        elif (m := _LEGACY_FILE.fullmatch(entry)):
            steps.add(int(m.group(1)))
        elif (m := _ASIDE_DIR.fullmatch(entry)):
            if os.path.exists(os.path.join(directory, entry, COMMIT_MARKER)):
                steps.add(int(m.group(1)))
    return sorted(steps)


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = _committed_steps(directory)
    return steps[-1] if steps else None


def restore_pytree(template, directory: str, step: int | None = None):
    """Restore into the structure of ``template``, whose shapes (and, for a
    step of leaf files, dtypes) are checked against the step's."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    src = step_dir(directory, step)
    if not os.path.exists(os.path.join(src, COMMIT_MARKER)):
        # a re-checkpoint killed mid-swap leaves the old committed copy
        # aside; fall back to it, then to the legacy flat layout
        asides = _aside_dirs(directory, step)
        legacy = os.path.join(directory, f"ckpt_{step:08d}.npz")
        if asides:
            src = asides[-1]
        elif os.path.exists(legacy):
            src = legacy
        else:
            raise FileNotFoundError(
                f"no committed checkpoint for step {step} in {directory}")
    with tracing.span("ckpt.restore.read") as read:
        keyed = _keyed(template)
        manifest = {}
        if os.path.isdir(src):
            with open(os.path.join(src, COMMIT_MARKER)) as f:
                manifest = json.load(f)
        if "leaves" in manifest:
            leaves = _read_leaves(src, manifest["leaves"], keyed)
            read.attrs.update(layout="leaves", files=len(leaves))
        else:
            if os.path.isdir(src):
                src = os.path.join(src, ARRAYS_FILE)
            leaves = _read_npz(src, keyed)
            read.attrs.update(layout="npz", files=1)
        nbytes = sum(a.nbytes for a in leaves)
        # the bytes served by mapping the leaf files
        read.attrs.update(bytes=nbytes,
                          mapped=nbytes if "leaves" in manifest else 0)
        treedef = jax.tree_util.tree_structure(template)
        return jax.tree_util.tree_unflatten(treedef, leaves)


def _read_npz(path: str, keyed) -> list[np.ndarray]:
    """The template's leaves from an npz archive (the older layouts)."""
    with np.load(path) as data:
        flat = dict(data)
    leaves = []
    for key, leaf in keyed:
        if key not in flat:
            raise KeyError(f"checkpoint missing {key}")
        if tuple(flat[key].shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: "
                             f"{flat[key].shape} vs {leaf.shape}")
        leaves.append(flat[key])
    return leaves


def _read_leaves(src: str, entries: list[dict], keyed) -> list[np.ndarray]:
    """The template's leaves from one raw file each, each file mapped
    copy-on-write; a leaf of zero bytes, which cannot be mapped, is a fresh
    empty array."""
    manifest = {e["key"]: e for e in entries}
    leaves = []
    for key, leaf in keyed:
        if key not in manifest:
            raise KeyError(f"checkpoint missing {key}")
        entry = manifest[key]
        shape, dtype = tuple(leaf.shape), np.dtype(leaf.dtype)
        if tuple(entry["shape"]) != shape:
            raise ValueError(f"shape mismatch for {key}: "
                             f"{tuple(entry['shape'])} vs {shape}")
        if entry["dtype"] != dtype.name:
            raise ValueError(f"dtype mismatch for {key}: "
                             f"{entry['dtype']} vs {dtype.name}")
        nbytes = math.prod(shape) * dtype.itemsize
        path = os.path.join(src, entry["file"])
        size = os.stat(path).st_size
        if size != nbytes:
            raise OSError(f"{path} holds {size} bytes, not {nbytes}")
        leaves.append(_map_leaf(path, nbytes, shape, dtype) if nbytes
                      else np.empty(shape, dtype))
    return leaves


def _map_leaf(path: str, nbytes: int, shape: tuple,
              dtype: np.dtype) -> np.ndarray:
    """``path``'s first ``nbytes`` mapped copy-on-write, as an array."""
    try:
        with open(path, "rb") as f:
            buf = mmap.mmap(f.fileno(), nbytes, access=mmap.ACCESS_COPY)
    except (OSError, ValueError) as e:    # ValueError: the file is shorter
        raise OSError(f"cannot map {path}: {e}") from e
    return np.frombuffer(buf, dtype).reshape(shape)


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep

    def save(self, tree, step: int) -> str:
        path = save_pytree(tree, self.directory, step)
        self._gc()
        return path

    def restore(self, template, step: int | None = None):
        return restore_pytree(template, self.directory, step)

    def latest_step(self) -> int | None:
        return latest_step(self.directory)

    def _gc(self) -> None:
        """Drop committed checkpoints beyond ``keep``, oldest first.

        Tolerates concurrent/partial state: entries that aren't ``step_*``
        (user files, staging dirs), half-written steps (no COMMIT marker)
        and races with other deleters are all skipped, never crashes.
        """
        if not os.path.isdir(self.directory):
            return
        for step in _committed_steps(self.directory)[:-self.keep]:
            victims = [step_dir(self.directory, step),
                       os.path.join(self.directory, f"ckpt_{step:08d}.npz")]
            victims += _aside_dirs(self.directory, step)
            for victim in victims:
                try:
                    if os.path.isdir(victim):
                        shutil.rmtree(victim)
                    elif os.path.exists(victim):
                        os.unlink(victim)
                except OSError:
                    pass  # lost a race with another gc/writer — fine
        # stale aside copies (re-checkpoint killed after the replace landed
        # but before cleanup) are redundant once the final dir is committed
        for step in _committed_steps(self.directory):
            if os.path.exists(os.path.join(step_dir(self.directory, step),
                                           COMMIT_MARKER)):
                for aside in _aside_dirs(self.directory, step):
                    shutil.rmtree(aside, ignore_errors=True)


class AsyncCheckpointer(Checkpointer):
    """Double-buffered checkpointing off the training critical path.

    ``save(tree, step)`` snapshots the pytree to host (overlapped d2h
    transfers) and hands the flat tree to a single background writer thread.
    The hand-off slot is depth 1: a second ``save`` while a write is in
    flight *blocks* until the writer commits — bounded memory, never an
    unbounded queue of snapshots.

    The writer reuses ``save_pytree``'s staged-dir + COMMIT + atomic-rename
    protocol and invokes ``on_commit(step, path, duration_s, nbytes)`` only
    after the rename lands — publishing ``ctx.shared["ckpt_step"]`` from
    that callback preserves the resume contract exactly: a kill mid-write
    resumes from the previous committed step.

    A writer-side failure (including a chaos kill injected via
    ``chaos_hook(step)``, which fires inside the writer window between
    staging and commit) is sticky: it re-raises from the next ``save`` or
    ``flush`` on the training thread, so the task dies and the AM's retry
    path takes over.
    """

    def __init__(self, directory: str, keep: int = 3,
                 on_commit: Callable[[int, str, float, int], None] | None = None,
                 chaos_hook: Callable[[int], None] | None = None):
        super().__init__(directory, keep)
        self.on_commit = on_commit
        self.chaos_hook = chaos_hook
        self._cond = threading.Condition()
        self._slot: tuple[dict[str, np.ndarray], int] | None = None
        self._busy = False
        self._closed = False
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._writer, daemon=True,
                                        name=f"ckpt-writer:{directory}")
        self._thread.start()

    # -- training-thread side ------------------------------------------
    def save(self, tree, step: int) -> None:
        """Snapshot now, write in the background. Blocks only while a
        previous write is still in flight (depth-1 backpressure)."""
        flat = _flatten(tree)          # host snapshot; safe to mutate tree after
        with tracing.span("ckpt.handoff"), self._cond:
            self._raise_pending_locked()
            while self._slot is not None or self._busy:
                self._cond.wait(0.05)
                self._raise_pending_locked()
            if self._closed:
                raise RuntimeError("AsyncCheckpointer is closed")
            self._slot = (flat, step)
            self._cond.notify_all()

    def flush(self) -> None:
        """Block until no write is pending or in flight; re-raise any
        deferred writer error on the calling thread."""
        with self._cond:
            while self._slot is not None or self._busy:
                self._cond.wait(0.05)
            self._raise_pending_locked()

    def close(self, timeout: float = 30.0) -> None:
        """Graceful shutdown: let the pending write (if any) commit, then
        stop the writer. Never raises — call ``flush`` first when deferred
        errors must surface."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout)

    def _raise_pending_locked(self) -> None:
        if self._error is not None:
            raise self._error

    # -- writer thread -------------------------------------------------
    def _writer(self) -> None:
        while True:
            with self._cond:
                while self._slot is None and not self._closed:
                    self._cond.wait(0.05)
                if self._slot is None:
                    return             # closed and drained
                flat, step = self._slot
                self._slot = None
                self._busy = True
                self._cond.notify_all()
            err: BaseException | None = None
            try:
                t0 = time.monotonic()
                pre = (lambda: self.chaos_hook(step)) if self.chaos_hook else None
                path = _write(flat, self.directory, step, pre_commit=pre)
                self._gc()
                if self.on_commit is not None:
                    self.on_commit(step, path, time.monotonic() - t0,
                                   tree_nbytes(flat))
            except BaseException as e:  # noqa: BLE001 - deferred to caller
                err = e
            with self._cond:
                if err is not None:
                    self._error = err
                self._busy = False
                self._cond.notify_all()
