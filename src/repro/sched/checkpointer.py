"""Sharded, atomic, versioned checkpointing (fault-tolerance substrate).

Layout:  <dir>/step_<n>/{manifest.json, arr_<i>.npy ...}; the manifest
records the pytree structure and leaf metadata.  Writes go to a temp dir
renamed into place (atomic on POSIX), so a crash never corrupts the latest
checkpoint.  ``restore`` re-shards onto whatever mesh/shardings the caller
provides — the primitive behind elastic re-scaling (elastic.py).
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import shutil
import threading
import time
import weakref
from typing import Any, Iterator, Optional, Tuple

import jax
import ml_dtypes
import numpy as np

# numpy's npy format cannot round-trip ml_dtypes (bf16 loads as void);
# such arrays are stored as raw-bit views with the logical dtype recorded
# in the manifest.
_BITCAST = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8,
            "float8_e5m2": np.uint8}


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def save(ckpt_dir: str, step: int, tree: Any) -> str:
    """Atomic save of a pytree; returns the final directory."""
    leaves, treedef = _flatten(tree)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    dtypes = []
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        dtypes.append(str(arr.dtype))
        if str(arr.dtype) in _BITCAST:
            arr = arr.view(_BITCAST[str(arr.dtype)])
        np.save(os.path.join(tmp, f"arr_{i}.npy"), arr,
                allow_pickle=False)
    meta = {"step": step, "treedef": str(treedef), "n_leaves": len(leaves),
            "dtypes": dtypes, "time": time.time()}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    with open(os.path.join(ckpt_dir, "LATEST.tmp"), "w") as f:
        f.write(str(step))
    os.replace(os.path.join(ckpt_dir, "LATEST.tmp"),
               os.path.join(ckpt_dir, "LATEST"))
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    path = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return int(f.read().strip())


# steps currently being read by a `restore` call, keyed by
# (abspath(ckpt_dir), step) with a reader count — `AsyncCheckpointer._gc`
# must not delete a step out from under a concurrent restore (the
# restore would crash mid-read on a missing arr_<i>.npy)
_READERS_LOCK = threading.Lock()
_READERS: dict = {}


@contextlib.contextmanager
def _reading(ckpt_dir: str, step: int) -> Iterator[Tuple[str, int]]:
    """Read-guard for one checkpoint step: while held, the step is
    exempt from ``AsyncCheckpointer._gc`` deletion."""
    key = (os.path.abspath(ckpt_dir), step)
    with _READERS_LOCK:
        _READERS[key] = _READERS.get(key, 0) + 1
    try:
        yield key
    finally:
        with _READERS_LOCK:
            n = _READERS.get(key, 1) - 1
            if n <= 0:
                _READERS.pop(key, None)
            else:
                _READERS[key] = n


def restore(ckpt_dir: str, like: Any, step: Optional[int] = None,
            shardings: Any = None) -> Any:
    """Restore into the structure of ``like``; with ``shardings`` the
    leaves are placed sharded (possibly onto a different mesh than the one
    that saved them — elastic re-scaling)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    with _reading(ckpt_dir, step):
        d = os.path.join(ckpt_dir, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            meta = json.load(f)
        leaves, treedef = _flatten(like)
        out = []
        sh_leaves = (jax.tree_util.tree_flatten(shardings)[0]
                     if shardings is not None else [None] * len(leaves))
        for i, (leaf, sh) in enumerate(zip(leaves, sh_leaves)):
            arr = np.load(os.path.join(d, f"arr_{i}.npy"))
            saved_dt = meta["dtypes"][i]
            if saved_dt in _BITCAST:
                arr = arr.view(np.dtype(getattr(ml_dtypes, saved_dt)))
            if hasattr(leaf, "dtype") and arr.dtype != leaf.dtype:
                arr = arr.astype(leaf.dtype)
            if sh is not None:
                out.append(jax.device_put(arr, sh))
            else:
                out.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# mid-job carry snapshots (sliced device segments, DESIGN.md §6)
# --------------------------------------------------------------------------

def save_carry(ckpt_dir: str, label: str, slice_idx: int,
               carry: Any) -> str:
    """Snapshot a SlicedOp carry after ``slice_idx`` completed slices.
    The carry is an ordinary pytree (softmax row stats, recurrent state,
    KV cache + emitted tokens, ...), so the sharded/atomic ``save`` works
    unchanged; a job resumes with ``executor.run_sliced(job, op,
    carry=carry, start=slice_idx)`` instead of re-running the segment."""
    return save(os.path.join(ckpt_dir, f"carry_{label}"), slice_idx, carry)


def load_carry(ckpt_dir: str, label: str, slice_idx: int,
               like: Any) -> Any:
    """The snapshot ``save_carry`` took for ``label`` after ``slice_idx``
    completed slices, restored into the structure of ``like``."""
    return restore(os.path.join(ckpt_dir, f"carry_{label}"), like,
                   step=slice_idx)


def latest_carry(ckpt_dir: str, label: str, like: Any
                 ) -> Optional[tuple]:
    """(slice_idx, carry) of the latest snapshot for ``label``, restored
    into the structure of ``like`` (use ``op.init()``), or ``None`` when
    no snapshot exists."""
    d = os.path.join(ckpt_dir, f"carry_{label}")
    idx = latest_step(d)
    if idx is None:
        return None
    return idx, restore(d, like, step=idx)


# all live AsyncCheckpointers, drained once at interpreter exit: the
# worker is a daemon thread, so without this an in-flight save is killed
# mid-write at shutdown and silently dropped (the tmp-rename keeps the
# *previous* checkpoint intact, but the newest state is lost — exactly
# the checkpoint a crash-recovery path wants)
_LIVE: "weakref.WeakSet[AsyncCheckpointer]" = weakref.WeakSet()


def _drain_at_exit() -> None:
    for ckpt in list(_LIVE):
        try:
            ckpt.wait()
        except Exception:  # noqa: BLE001 — exit path must not raise
            pass


atexit.register(_drain_at_exit)


class AsyncCheckpointer:
    """Fire-and-forget saves on a worker thread (training never stalls on
    I/O); ``wait()`` drains before shutdown, and an atexit hook drains
    every live instance so interpreter exit cannot drop an in-flight
    save."""

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        _LIVE.add(self)

    def save(self, step: int, tree: Any) -> None:
        self.wait()
        host_tree = jax.tree.map(np.asarray, tree)  # snapshot before async

        def _work():
            save(self.ckpt_dir, step, host_tree)
            self._gc()

        self._thread = threading.Thread(target=_work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = sorted(
            int(d.split("_")[1]) for d in os.listdir(self.ckpt_dir)
            if d.startswith("step_") and not d.endswith(".tmp"))
        root = os.path.abspath(self.ckpt_dir)
        for s in steps[:-self.keep]:
            with _READERS_LOCK:
                busy = _READERS.get((root, s), 0) > 0
            if busy:
                # a concurrent restore is reading this step (e.g. a
                # FaultTolerantLoop rollback racing the post-save gc):
                # skip it now, the next gc pass collects it
                continue
            shutil.rmtree(os.path.join(self.ckpt_dir, f"step_{s:08d}"),
                          ignore_errors=True)
