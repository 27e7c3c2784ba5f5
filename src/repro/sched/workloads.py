"""Reconstructible workloads: the registry behind durable submissions.

A daemon cannot journal a closure.  A durable submission therefore names
a *registered workload* — ``{"name": <registry key>, "kwargs": {...}}``
— and the registry maps that spec back to a fresh
:class:`~repro.core.segments.SlicedOp` factory on every release, on
every process: the daemon reconstructs the exact same job body after a
crash and resumes it from the journaled carry (DESIGN.md §9).

``make_body`` is the one definition of the durable job body: one sliced
device segment per release, every completed slice checkpointed through
``checkpointer.save_carry`` with the pointer journaled, iteration
completion journaled — so the store always knows the last durable point
of every live job.  Workload steps must be idempotent at slice
granularity: a crash between the last carry checkpoint and the
``iter_done`` record replays at most one slice + finalize.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Mapping, Optional, Union

import numpy as np

from ..core.segments import SlicedOp

WORKLOADS: Dict[str, Callable[..., SlicedOp]] = {}


def register_workload(name: str,
                      factory: Callable[..., SlicedOp]) -> None:
    """Register ``factory(device=..., **kwargs) -> SlicedOp`` under
    ``name``; ``device`` is the cluster device the job is bound to, so a
    factory that holds device state builds it there.  The factory must
    be importable in the daemon process (module-level), or recovery
    cannot rebuild the job."""
    WORKLOADS[name] = factory


def get_workload(name: str) -> Callable[..., SlicedOp]:
    try:
        return WORKLOADS[name]
    except KeyError:
        raise KeyError(f"unknown workload {name!r} (registered: "
                       f"{sorted(WORKLOADS)})") from None


def normalize_spec(spec: Union[str, Mapping], *,
                   check: bool = True) -> dict:
    """``"demo.spin"`` or ``{"name": ..., "kwargs": {...}}`` → the
    canonical journal form.  ``check=False`` skips the registry
    lookup — a socket client must not validate against its *own*
    registry (the daemon's may register workloads the client process
    never imported); the daemon re-validates on receipt."""
    if isinstance(spec, str):
        spec = {"name": spec}
    out = {"name": spec["name"], "kwargs": dict(spec.get("kwargs") or {})}
    if check:
        get_workload(out["name"])  # fail fast on unknown names
    return out


def make_body(executor, job_name: str, spec: Mapping, *,
              store=None, checkpoint_every: int = 1, offset: int = 0,
              resume: Optional[Mapping] = None) -> Callable:
    """The durable RTJob body for a registered workload.

    Each release ``it`` runs one fresh ``SlicedOp`` from the registry
    under the executor's sliced dispatch (admission re-checked per
    slice).  Iteration indices are global across restarts: a recovered
    job is rebuilt with ``offset = journaled done_iterations`` and
    ``n_iterations = remaining``, so ``offset + it`` matches the journal.

    With a store attached, every ``checkpoint_every``-th slice snapshots
    the carry (``save_carry``, tmp-rename atomic) and journals the
    pointer; ``resume = {"iteration": i, "slice": s}`` makes the release
    whose global index is ``i`` restore the latest snapshot and start at
    its journaled slice (a ``resume`` audit record is appended — the
    kill-and-recover suite asserts on it)."""
    spec = normalize_spec(spec)
    factory = get_workload(spec["name"])
    kwargs = spec["kwargs"]

    def body(job, it):
        from . import checkpointer  # lazy: jax import
        g = offset + it
        op = factory(device=job.device, **kwargs)
        carry, start = None, 0
        if (resume is not None and store is not None
                and g == resume["iteration"]):
            # the journal, not the newest snapshot on disk, says where to
            # resume: a kill between a snapshot and its journal record
            # leaves a snapshot no record acknowledges
            start = resume["slice"]
            carry = checkpointer.load_carry(store.carry_dir(job_name),
                                            job_name, start, op.init())
            store.record_resume(job_name, g, start)
        ckpt = None
        if store is not None:
            def ckpt(i, c):
                checkpointer.save_carry(store.carry_dir(job_name),
                                        job_name, i, c)
                store.record_carry(job_name, g, i)
        with executor.device_segment(job):
            executor.run_sliced(
                job, op, carry=carry, start=start, checkpoint=ckpt,
                checkpoint_every=(checkpoint_every
                                  if store is not None else 0))
        if store is not None:
            store.record_iteration_done(job_name, g)

    return body


# --------------------------------------------------------------------------
# built-in demo workloads (the recovery suite's and CI smoke's subjects)
# --------------------------------------------------------------------------

def _spin(device: Optional[int] = None, slices: int = 8,
          slice_ms: float = 25.0) -> SlicedOp:
    """Pure host-timed sliced segment (``device`` unused): each slice
    sleeps ``slice_ms`` and bumps a counter carry — the minimal
    checkpointable RT job (the counter proves where a resumed run
    actually restarted)."""
    def init():
        return {"done": np.zeros((), np.int64)}

    def step(carry, i):
        time.sleep(slice_ms / 1e3)
        return {"done": carry["done"] + 1}

    def finalize(carry):
        return carry["done"]

    return SlicedOp(slices, init, step, finalize, label="demo.spin")


def _count(device: Optional[int] = None, total: int = 64,
           per_slice: int = 8) -> SlicedOp:
    """Host-arithmetic sliced segment (``device`` unused): accumulates
    ``total`` integers ``per_slice`` at a time (resume-exact: the carry
    holds the running sum and the final value is checkable as
    total*(total+1)/2)."""
    def init():
        return {"sum": np.zeros((), np.int64)}

    def step(carry, i):
        lo = i * per_slice
        hi = min((i + 1) * per_slice, total)
        return {"sum": carry["sum"] + sum(range(lo + 1, hi + 1))}

    def finalize(carry):
        return carry["sum"]

    from ..core.segments import n_slices_for
    return SlicedOp(n_slices_for(total, per_slice), init, step, finalize,
                    label="demo.count")


register_workload("demo.spin", _spin)
register_workload("demo.count", _count)
