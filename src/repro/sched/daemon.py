"""Durable scheduling daemon: the persistent face of ``ClusterExecutor``.

The paper's admission guarantees are only as durable as the process that
holds them; this daemon makes them survive it (DESIGN.md §9).  It owns
the cluster, journals every admit→place→bind transaction through
:class:`~repro.sched.store.JobStore`, accepts submissions over a unix
socket (``repro.sched.client`` / the ``SchedClient`` facade), and on
startup runs the recovery path:

  1. **rebuild** — re-run admission over the journaled taskset in its
     recorded order and assert it reproduces the recorded decisions
     (``AdmissionController.rebuild(conform=True)``); a mismatch raises
     :class:`RecoveryConformanceError` and the daemon refuses to come up
     — the durable analogue of ``tests/conformance.py``'s
     live↔simulated decision identity;
  2. **rebind** — every recovered job is re-bound to its journaled
     device (the immutable binding survives the crash, so the
     migration-free invariant holds *across restarts*);
  3. **resume** — a job that was mid-segment restarts from the
     checkpointed carry at the journaled slice index
     (``checkpointer.load_carry``), not from scratch; remaining
     iterations then run normally.

Run it:

    PYTHONPATH=src python -m repro.sched.daemon \
        --store /var/lib/schedd --socket /run/schedd.sock --n-devices 2

and talk to it with ``python -m repro.sched.client --socket ...`` or
``repro.sched.connect("/run/schedd.sock")``.
"""
from __future__ import annotations

import json
import os
import signal
import socket as socketlib
import threading
import time
from typing import Mapping, Optional

from ..runtime import enable_compile_cache
from .admission import (AdmissionController, AdmissionDecision,
                        JobProfile, RecoveryConformanceError)
from .cluster import ClusterExecutor
from .elastic import ShedPolicy
from .fault import HealthConfig
from .job import RTJob
from .store import CompactionPolicy, JobRecord, JobStore
from .workloads import make_body, normalize_spec

__all__ = ["SchedDaemon", "RecoveryConformanceError"]


class SchedDaemon:
    """Owns the cluster + store; serves the submission API on a unix
    socket.  Construction runs the full recovery path; ``start()``
    spawns the acceptor thread (``serve_forever()`` runs it inline)."""

    def __init__(self, store_dir: str, socket_path: Optional[str] = None,
                 *, n_devices: int = 1, policy="ioctl",
                 wait_mode: str = "suspend", n_cpus: int = 4,
                 epsilon_ms: float = 1.0, placement: str = "pinned",
                 headroom: float = 1.0, try_gpu_priorities: bool = True,
                 checkpoint_every: int = 1, conform: bool = True,
                 resume_jobs: bool = True,
                 health: Optional[HealthConfig] = None,
                 shed_policy: Optional[ShedPolicy] = None,
                 heartbeat_file: Optional[str] = None,
                 auto_compact: Optional[CompactionPolicy] = None):
        self.socket_path = socket_path or os.path.join(store_dir, "sock")
        self.checkpoint_every = checkpoint_every
        # liveness beacon for sched.supervisor: touched every loop turn
        # of serve_forever; a stale mtime means a hung (not just dead)
        # daemon, which a poll-based waitpid watchdog cannot see
        self.heartbeat_file = heartbeat_file
        self.store = JobStore(store_dir, auto_compact=auto_compact)
        state = self.store.load()
        self.recovery = {"recovered": [], "resumed": {},
                         "conformance": None}
        admission = None
        if state.config is not None:
            # the journaled platform model wins: a daemon must come back
            # AS the platform whose guarantees it journaled — a config
            # drift would invalidate every recorded WCRT
            shape = state.cluster or {}
            n_devices = shape.get("n_devices", state.config["n_devices"])
            policy = shape.get("policy", policy)
            placement = shape.get("placement", placement)
            wait_mode = state.config["wait_mode"]
            n_cpus = state.config["n_cpus"]
            epsilon_ms = state.config["epsilon_ms"]
            headroom = state.config["headroom"]
            try_gpu_priorities = state.config["try_gpu_priorities"]
            # decision-conformance on recovery: re-run admission over
            # the journaled taskset, in order, and require identity
            admission = AdmissionController.rebuild(
                state.config, state.admission_entries(), conform=conform)
            self.recovery["conformance"] = ("checked" if conform
                                            else "skipped")
            self.recovery["recovered"] = [r.name
                                          for r in state.jobs.values()]
        self.cluster = ClusterExecutor(
            n_devices=n_devices, policy=policy, wait_mode=wait_mode,
            n_cpus=n_cpus, epsilon_ms=epsilon_ms, placement=placement,
            try_gpu_priorities=try_gpu_priorities, admission=admission,
            store=self.store, health=health, shed_policy=shed_policy)
        if state.epoch or state.failed_devices:
            # a device failed in a previous life stays failed: the
            # journaled re-admissions were proven against the surviving
            # platform, so recovery must come back AS that platform
            self.cluster.restore_fault_state(state.epoch,
                                             state.failed_devices)
            self.recovery["epoch"] = state.epoch
            self.recovery["failed_devices"] = sorted(state.failed_devices)
        # idempotent-submission dedup table, rebuilt from the journal:
        # a client retrying across a daemon restart gets the journaled
        # decision back instead of a double admission
        self._requests = dict(state.requests)
        if state.config is None:
            # the cluster-built controller defaults headroom=1.0; apply
            # the daemon's before anything is admitted or journaled
            self.cluster.admission.headroom = headroom
            self.store.record_config(
                self.cluster.admission.export_config(),
                {"n_devices": n_devices, "policy": policy,
                 "placement": placement})
        self._state = state
        if resume_jobs:
            for rec in state.jobs.values():
                self._resume(rec)
            # a crash mid-fail-over leaves jobs on the displaced ledger
            # (failover journaled, outcome not): settle every one now —
            # re-admitted onto a survivor or explicitly refused on the
            # record — so state.unaccounted() drains to [] and no job
            # is silently lost
            for rec in list(state.displaced.values()):
                self._settle_displaced(rec)
        self._sock: Optional[socketlib.socket] = None
        self._stop = threading.Event()
        self._acceptor: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # recovery: rebind + resume
    # ------------------------------------------------------------------
    def _resume(self, rec: JobRecord) -> None:
        """Rebind one recovered job to its journaled device and restart
        it — mid-segment from the checkpointed carry, otherwise at the
        next unfinished iteration."""
        if rec.workload is None:
            # admitted state is restored (it still charges admission),
            # but a closure-based body cannot be reconstructed
            self.recovery.setdefault("unresumable", []).append(rec.name)
            return
        resume = rec.carry
        remaining = rec.n_iterations - rec.done_iterations
        if remaining <= 0 and resume is None:
            return
        remaining = max(remaining, 1)
        prof = JobProfile.from_dict(rec.profile)
        body = make_body(self.cluster, rec.name, rec.workload,
                         store=self.store,
                         checkpoint_every=self.checkpoint_every,
                         offset=rec.done_iterations, resume=resume)
        job = RTJob(rec.name, body, period_s=prof.period_ms / 1e3,
                    priority=prof.priority,
                    deadline_s=(prof.deadline_ms or prof.period_ms) / 1e3,
                    best_effort=prof.best_effort,
                    n_iterations=remaining, device=rec.device)
        # NOT re-submitted: its admission already charges the rebuilt
        # controller (rebuild re-admitted it) — bind_job honors the
        # journaled immutable binding and bypasses a double admission
        self.cluster.bind_job(job, rec.device)
        job.start(self.cluster)
        self.recovery["resumed"][rec.name] = {
            "device": rec.device,
            "iteration": (resume["iteration"] if resume
                          else rec.done_iterations),
            "slice": resume["slice"] if resume else 0,
            "remaining_iterations": remaining}

    def _settle_displaced(self, rec: JobRecord) -> None:
        """Settle one displaced-ledger entry left by a crash that
        interrupted a fail-over: re-submit the job through the normal
        admit→place→bind path (which journals the outcome, clearing
        the ledger), or journal an explicit refusal when the body
        cannot be reconstructed."""
        prof = JobProfile.from_dict(rec.profile)
        outcome = self.recovery.setdefault("displaced_settled", {})
        if rec.workload is None:
            self.store.record_decision(
                prof, AdmissionDecision.refuse(
                    "validation-refused",
                    error="displaced by device failure; closure-based "
                          "body not reconstructible").bound(None, None),
                device=None, epoch=self.cluster.epoch or None)
            outcome[rec.name] = "refused (unresumable)"
            return
        remaining = max(rec.n_iterations - rec.done_iterations, 1)
        body = make_body(self.cluster, rec.name, rec.workload,
                         store=self.store,
                         checkpoint_every=self.checkpoint_every,
                         offset=rec.done_iterations, resume=rec.carry)
        dec = self.cluster._submit(
            prof, None, body, strategy="least_loaded",
            n_iterations=remaining, start=True,
            journal_meta={"workload": rec.workload})
        outcome[rec.name] = ("rebound to device "
                             f"{dec.get('device')}"
                             if dec["admitted"] else
                             f"refused ({dec.get('error') or dec.reason})")

    # ------------------------------------------------------------------
    # request handling (directly callable — tests drive it in-process)
    # ------------------------------------------------------------------
    def handle(self, req: Mapping) -> dict:
        op = req.get("op")
        if op == "ping":
            return {"ok": True, "pid": os.getpid(),
                    "socket": self.socket_path}
        if op == "submit":
            rid = req.get("request_id")
            if rid is not None and rid in self._requests:
                # idempotent resubmission (client retry across a
                # restart/transport failure): return the journaled
                # decision — the job was NOT admitted twice
                prev = self._requests[rid]
                out = dict(prev.get("decision") or prev)
                out["deduped"] = True
                return out
            prof = JobProfile.from_dict(req["profile"])
            try:
                spec = normalize_spec(req["workload"])
            except KeyError as e:
                return AdmissionDecision.refuse(
                    "validation-refused", error=str(e)).journal_form()
            n_iter = int(req.get("n_iterations", 1))
            body = make_body(self.cluster, prof.name, spec,
                             store=self.store,
                             checkpoint_every=self.checkpoint_every)
            dec = self.cluster._submit(
                prof, None, body, strategy=req.get("strategy"),
                n_iterations=n_iter, start=bool(req.get("start")),
                stop_after_s=req.get("stop_after_s"),
                journal_meta={"workload": spec, "request_id": rid})
            if rid is not None:
                self._requests[rid] = {"job": prof.name,
                                       "admitted": bool(dec["admitted"]),
                                       "decision": dec.journal_form()}
            return dec.journal_form()
        if op == "release":
            return self.cluster.release(req["name"])
        if op == "fail_device":
            return self.cluster.fail_device(
                int(req["device"]), reason=req.get("reason", ""))
        if op == "audit":
            st = self.store.load()
            return {"epoch": st.epoch,
                    "failed_devices": sorted(st.failed_devices),
                    "unaccounted": st.unaccounted(),
                    "shed": sorted(st.shed),
                    "live": sorted(st.jobs)}
        if op == "status":
            return {"pid": os.getpid(), "backend": "daemon",
                    "n_devices": self.cluster.n_devices,
                    "placement": self.cluster.placement,
                    "admitted": [p.name for p in
                                 self.cluster.admission.admitted],
                    "recovery": self.recovery,
                    "stats": self.cluster.stats()}
        if op == "jobs":
            return self._jobs_detail()
        if op == "per_device_mort":
            return self.cluster.per_device_mort()
        if op == "compact":
            st = self.store.compact()
            return {"jobs": sorted(st.jobs)}
        if op == "shutdown":
            # delay the flag so the handler thread can flush the
            # response before the process starts tearing down
            threading.Timer(0.2, self._stop.set).start()
            return {"ok": True}
        raise ValueError(f"unknown op {op!r}")

    def _jobs_detail(self) -> dict:
        """Per-job view joining the journal (admitted WCRT evidence) and
        the live RTJob stats — what the kill-and-recover suite compares
        MORT against."""
        out = {}
        for name, rec in self.store.load().jobs.items():
            job = self.cluster.find_job(name)
            stats = job.stats if job is not None else None
            out[name] = {
                "device": rec.device,
                "best_effort": rec.profile.get("best_effort", False),
                "wcrt_ms": rec.decision.get("wcrt", {}).get(name),
                "via": rec.decision.get("via"),
                "n_iterations": rec.n_iterations,
                "done_iterations": rec.done_iterations,
                "carry": rec.carry,
                "state": job.state if job is not None else None,
                "completions": stats.completions if stats else 0,
                "deadline_misses": (stats.deadline_misses
                                    if stats else 0),
                "mort_s": stats.mort if stats else None,
            }
        return out

    # ------------------------------------------------------------------
    # the socket server
    # ------------------------------------------------------------------
    def start(self) -> "SchedDaemon":
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)   # stale socket of a killed daemon
        self._sock = socketlib.socket(socketlib.AF_UNIX,
                                      socketlib.SOCK_STREAM)
        self._sock.bind(self.socket_path)
        self._sock.listen(16)
        self._sock.settimeout(0.25)       # poll the stop flag
        self._acceptor = threading.Thread(target=self._accept_loop,
                                          name="schedd-accept",
                                          daemon=True)
        self._acceptor.start()
        return self

    def _accept_loop(self) -> None:
        assert self._sock is not None
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socketlib.timeout:
                continue
            except OSError:
                break
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socketlib.socket) -> None:
        with conn:
            try:
                conn.settimeout(30.0)
                buf = b""
                while b"\n" not in buf:
                    chunk = conn.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
                if not buf.strip():
                    return
                req = json.loads(buf.decode())
                resp = {"ok": True, "result": self.handle(req)}
            except Exception as e:  # noqa: BLE001 — protocol boundary
                resp = {"ok": False,
                        "error": f"{type(e).__name__}: {e}"}
            try:
                conn.sendall((json.dumps(resp, default=str)
                              + "\n").encode())
            except OSError:
                pass

    def _touch_heartbeat(self) -> None:
        if self.heartbeat_file is None:
            return
        try:
            tmp = self.heartbeat_file + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                f.write(json.dumps({"pid": os.getpid(),
                                    "t": time.time()}))
            os.replace(tmp, self.heartbeat_file)
        except OSError:
            pass

    def serve_forever(self) -> None:
        if self._acceptor is None:
            self.start()
        self._touch_heartbeat()
        while not self._stop.is_set():
            self._stop.wait(0.25)
            self._touch_heartbeat()
        self.stop()

    def stop(self) -> None:
        self._stop.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        if os.path.exists(self.socket_path):
            try:
                os.unlink(self.socket_path)
            except OSError:
                pass
        self.cluster.shutdown()
        self.store.close()

    def __enter__(self) -> "SchedDaemon":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="repro.sched.daemon",
        description="durable scheduling daemon (journaled job store, "
                    "crash recovery, unix-socket submission API)")
    ap.add_argument("--store", required=True,
                    help="job store directory (journal + snapshots + "
                         "carries)")
    ap.add_argument("--socket", default=None,
                    help="unix socket path (default: <store>/sock)")
    ap.add_argument("--n-devices", type=int, default=1)
    ap.add_argument("--policy", default="ioctl")
    ap.add_argument("--wait-mode", default="suspend",
                    choices=("suspend", "busy"))
    ap.add_argument("--n-cpus", type=int, default=4)
    ap.add_argument("--epsilon-ms", type=float, default=1.0)
    ap.add_argument("--placement", default="pinned")
    ap.add_argument("--headroom", type=float, default=1.0)
    ap.add_argument("--checkpoint-every", type=int, default=1)
    ap.add_argument("--no-conform", action="store_true",
                    help="skip the recovery decision-conformance assert "
                         "(debugging only)")
    ap.add_argument("--compact", action="store_true",
                    help="compact the journal into a snapshot on start")
    ap.add_argument("--health", action="store_true",
                    help="attach per-device health monitoring (slice "
                         "heartbeats, stall→suspect→failed ladder, "
                         "auto fail-over)")
    ap.add_argument("--health-stall-s", type=float, default=5.0,
                    help="stalled-slice seconds before a device turns "
                         "suspect")
    ap.add_argument("--health-fail-s", type=float, default=5.0,
                    help="additional suspect seconds before failed")
    ap.add_argument("--shed-at", type=float, default=None,
                    help="total device utilization above which best-"
                         "effort jobs are shed (enables the overload "
                         "degradation ladder)")
    ap.add_argument("--resume-at", type=float, default=None,
                    help="utilization under which shed jobs resume "
                         "(default: 0.8 * shed-at)")
    ap.add_argument("--tier-budget", action="append", default=[],
                    metavar="TIER=FRAC",
                    help="per-tier best-effort utilization budget "
                         "(repeatable, e.g. --tier-budget 0=0.2; "
                         "needs --shed-at)")
    ap.add_argument("--heartbeat-file", default=None,
                    help="liveness beacon touched every loop turn "
                         "(sched.supervisor watches its mtime)")
    ap.add_argument("--auto-compact-bytes", type=int, default=None,
                    help="auto-compact the journal past this size")
    args = ap.parse_args(argv)
    enable_compile_cache()

    health = (HealthConfig(stall_timeout_s=args.health_stall_s,
                           fail_timeout_s=args.health_fail_s)
              if args.health else None)
    if args.tier_budget and args.shed_at is None:
        ap.error("--tier-budget needs --shed-at (the budgets refine "
                 "the overload ladder)")
    budgets = {int(t): float(b) for t, b in
               (spec.split("=", 1) for spec in args.tier_budget)} or None
    shed = (ShedPolicy(shed_at=args.shed_at,
                       resume_at=(args.resume_at
                                  if args.resume_at is not None
                                  else 0.8 * args.shed_at),
                       tier_budgets=budgets)
            if args.shed_at is not None else None)
    auto_compact = (CompactionPolicy(max_bytes=args.auto_compact_bytes)
                    if args.auto_compact_bytes is not None else None)
    daemon = SchedDaemon(
        args.store, args.socket, n_devices=args.n_devices,
        policy=args.policy, wait_mode=args.wait_mode, n_cpus=args.n_cpus,
        epsilon_ms=args.epsilon_ms, placement=args.placement,
        headroom=args.headroom, checkpoint_every=args.checkpoint_every,
        conform=not args.no_conform, health=health, shed_policy=shed,
        heartbeat_file=args.heartbeat_file, auto_compact=auto_compact)
    if args.compact:
        daemon.store.compact()
    daemon.start()
    print(f"schedd ready pid={os.getpid()} socket={daemon.socket_path} "
          f"recovered={daemon.recovery['recovered']} "
          f"resumed={sorted(daemon.recovery['resumed'])}", flush=True)

    def _term(signum, frame):
        daemon._stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    try:
        daemon.serve_forever()
    finally:
        daemon.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
