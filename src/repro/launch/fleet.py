"""Heterogeneous multi-model serving fleet (DESIGN.md §12).

A *fleet* is N models from the config registry served together on one
multi-device cluster as a mixed-criticality workload: interactive
decode segments run as RT jobs with admission-checked WCRTs, while
background training / batch-eval runs best-effort underneath — shed
first under overload (``sched.elastic``), never able to block an RT
dispatch (the priority-inversion-freedom invariant the conformance
harness pins).  Every member contributes its own *measured*
``WorkloadProfile`` through the ``SegmentedWorkload.profile()``
pipeline, so admission prices the fleet from real per-slice times, and
``ClusterExecutor.stats()`` reports MORT / deadline misses / p50/p99
per model and per criticality tier.

  PYTHONPATH=src python -m repro.launch.fleet --n-devices 2 \
      --duration 6 --models chat,assist,train

``--daemon`` registers every member as a durable workload
(``fleet.<member>``) and runs the scheduling daemon instead, so fleet
submissions survive ``kill -9`` (same pattern as
``repro.launch.serve --daemon``).  On a CPU host, expose devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..configs import get
from ..core.segments import SegmentedWorkload, SlicedOp
from ..models import transformer
from ..optim import adamw
from ..runtime import enable_compile_cache, visible_devices
from .serve import InferenceEngine
from .steps import build_train_step

#: criticality tiers of the default fleet (DESIGN.md §12): interactive
#: chat > latency-tolerant assist/refresh > bulk background
TIER_INTERACTIVE, TIER_STANDARD, TIER_BULK = 2, 1, 0


@dataclass(frozen=True)
class FleetMember:
    """One model of the fleet: which registry architecture, what role
    its device work plays, and where it sits in the criticality order.

    ``role``:
      * ``decode``  — RT interactive serving: a prefill segment + a
        sliced decode segment per release;
      * ``train``   — best-effort training: one optimizer step per
        slice (state committed at finalize);
      * ``eval``    — best-effort batch inference: one forward
        ``lm_loss`` per slice.
    """
    name: str
    arch: str                # configs registry name (reduced() is used)
    role: str                # decode | train | eval
    tier: int
    priority: int
    period_ms: float
    device: int = 0
    best_effort: bool = False
    batch: int = 2
    prompt_len: int = 8
    decode_tokens: int = 4
    slice_tokens: int = 2
    microbatches: int = 2
    seq_len: int = 16
    n_iterations: int = 1000

    def __post_init__(self):
        if self.role not in ("decode", "train", "eval"):
            raise ValueError(f"unknown fleet role {self.role!r}")
        if (self.role != "decode") != self.best_effort:
            raise ValueError(
                f"{self.name}: decode members are RT, train/eval members "
                "are best-effort — the mixed-criticality contract")


def default_fleet(n_devices: int = 2,
                  models: Optional[Sequence[str]] = None
                  ) -> List[FleetMember]:
    """The reference fleet: two interactive decode models over two
    background models, spread across the devices.  ``models`` filters
    by member name (CI runs a 3-model subset)."""
    last = max(n_devices - 1, 0)
    fleet = [
        FleetMember("chat", "smollm-135m", "decode",
                    tier=TIER_INTERACTIVE, priority=50,
                    period_ms=1500.0, device=0),
        FleetMember("assist", "olmo-1b", "decode",
                    tier=TIER_STANDARD, priority=30,
                    period_ms=2000.0, device=last),
        FleetMember("train", "minitron-8b", "train",
                    tier=TIER_STANDARD, priority=5,
                    period_ms=800.0, device=last, best_effort=True),
        FleetMember("batch-eval", "mixtral-8x22b", "eval",
                    tier=TIER_BULK, priority=1,
                    period_ms=600.0, device=0, best_effort=True),
    ]
    if models:
        wanted = set(models)
        unknown = wanted - {m.name for m in fleet}
        if unknown:
            raise ValueError(f"unknown fleet member(s) {sorted(unknown)}; "
                             f"available: {[m.name for m in fleet]}")
        fleet = [m for m in fleet if m.name in wanted]
    return [replace(m, device=min(m.device, last)) for m in fleet]


# --------------------------------------------------------------------------
# member -> SegmentedWorkload (the measured pipeline's entry)
# --------------------------------------------------------------------------

def decode_workload(name: str, eng: InferenceEngine, prompt,
                    decode_tokens: int, slice_tokens: int
                    ) -> SegmentedWorkload:
    """RT serving: a one-slice prefill segment, then ``decode_tokens``
    greedy tokens as a sliced decode segment of ``slice_tokens``."""
    return (SegmentedWorkload(name)
            .device(lambda: eng.prefill_segment(prompt), label="prefill")
            .device(lambda: eng.decode_segment(
                decode_tokens, slice_tokens=slice_tokens), label="decode"))


def train_workload(name: str, cfg, *, batch: int, seq_len: int,
                   microbatches: int, jdev=None,
                   seed: int = 0) -> SegmentedWorkload:
    """Best-effort training: one optimizer step per slice, one
    microbatch each, the (params, opt) state committed at finalize."""
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    mbs = [{"inputs": jnp.zeros((batch, seq_len), jnp.int32),
            "labels": jnp.zeros((batch, seq_len), jnp.int32)}
           for _ in range(microbatches)]
    if jdev is not None:
        params, mbs = jax.device_put((params, mbs), jdev)
    state = {"params": params, "opt": adamw.init_opt_state(params)}
    step_fn = jax.jit(build_train_step(cfg))

    def train_op() -> SlicedOp:
        def step(carry, i):
            p, o, _ = step_fn(carry[0], carry[1], mbs[i])
            return (p, o)

        def finalize(carry):
            state.update(params=carry[0], opt=carry[1])
            return None

        return SlicedOp(len(mbs), lambda: (state["params"], state["opt"]),
                        step, finalize, label="train_step")

    return SegmentedWorkload(name).device(train_op, label="train")


def build_member_workload(member: FleetMember, jdev=None,
                          seed: int = 0) -> SegmentedWorkload:
    """The member's device work as a ``SegmentedWorkload`` — profiled
    for admission and bound as the RT/BE job body.  ``jdev`` (a
    ``jax.Device``) places the params so the programs really run on the
    member's scheduling device."""
    cfg = get(member.arch).reduced()
    if member.role == "decode":
        eng = InferenceEngine(
            cfg, max_len=member.prompt_len + member.decode_tokens,
            seed=seed, device=jdev)
        prompt = jnp.zeros((member.batch, member.prompt_len), jnp.int32)
        return decode_workload(member.name, eng, prompt,
                               member.decode_tokens, member.slice_tokens)
    if member.role == "train":
        return train_workload(member.name, cfg, batch=member.batch,
                              seq_len=member.seq_len,
                              microbatches=member.microbatches, jdev=jdev,
                              seed=seed)

    # eval: forward-only lm_loss, one microbatch per slice
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    shape = (member.batch, member.seq_len)
    mbs = [{"inputs": jnp.zeros(shape, jnp.int32),
            "labels": jnp.zeros(shape, jnp.int32)}
           for _ in range(member.microbatches)]
    if jdev is not None:
        params, mbs = jax.device_put((params, mbs), jdev)
    loss_fn = jax.jit(lambda p, b: transformer.lm_loss(cfg, p, b))

    def eval_op() -> SlicedOp:
        return SlicedOp(len(mbs),
                        lambda: jnp.zeros((), jnp.float32),
                        lambda carry, i: carry + loss_fn(params, mbs[i]),
                        lambda carry: float(carry), label="eval")

    return SegmentedWorkload(member.name).device(eval_op, label="eval")


def member_op_factory(member: FleetMember, seed: int = 1):
    """A durable-workload factory for one member: builds the member's
    stack lazily on first use, on the JAX device of the cluster device
    the job is bound to, then returns a fresh ``SlicedOp`` per release
    (for decode members the prefill runs inline, mirroring
    ``serve.register_serving_workloads``)."""
    built: Dict[int, SegmentedWorkload] = {}

    def factory(device: int = 0) -> SlicedOp:
        wl = built.get(device)
        if wl is None:
            wl = build_member_workload(
                member, jdev=visible_devices(device + 1)[device], seed=seed)
            built[device] = wl
        if member.role == "decode":
            # entries: [prefill, decode] — run prefill to completion
            # inline, hand the executor the resumable decode segment
            wl._entries[0].fn().run()
            return wl._entries[1].fn()
        return wl._entries[0].fn()

    return factory


def register_fleet_workloads(members: Sequence[FleetMember],
                             seed: int = 1) -> None:
    """Register every member as ``fleet.<name>`` in the durable-workload
    registry, so daemon submissions of fleet work survive a restart."""
    from ..sched.workloads import register_workload

    for m in members:
        register_workload(f"fleet.{m.name}", member_op_factory(m, seed))


# --------------------------------------------------------------------------
# the fleet run: profile -> admit -> run -> per-tier report
# --------------------------------------------------------------------------

def launch_fleet(members: Sequence[FleetMember], *, n_devices: int = 2,
                 duration_s: float = 6.0, policy: str = "ioctl",
                 wait_mode: str = "suspend", reps: int = 2,
                 margin: float = 2.0, shed_policy=None,
                 verbose: bool = True) -> dict:
    """Serve the fleet end-to-end: build + profile every member, admit
    the fleet onto an owned cluster (RT members must pass the RTA; a
    refusal aborts before anything starts), run for ``duration_s``, and
    return the observability report — admission evidence plus the
    per-model / per-tier stats surface.

    Raises ``SystemExit`` if any RT member is refused admission."""
    from ..sched import JobProfile, connect

    log = print if verbose else (lambda *a, **k: None)
    jdevs = visible_devices(n_devices)

    workloads: Dict[str, SegmentedWorkload] = {}
    profiles: Dict[str, object] = {}
    for m in members:
        t0 = time.perf_counter()
        workloads[m.name] = build_member_workload(m, jdev=jdevs[m.device])
        # the first profile rep doubles as the jit warm-up
        profiles[m.name] = workloads[m.name].profile(reps=reps)
        log(f"profiled {m.name} ({m.arch}, {m.role}, tier {m.tier}): "
            f"max slice {profiles[m.name].max_slice_ms:.1f}ms "
            f"[{time.perf_counter() - t0:.1f}s]")

    # epsilon = admission-update cost + one in-flight slice (any
    # member's): preemption takes effect at slice boundaries
    max_slice = max(p.max_slice_ms for p in profiles.values())
    eps_ms = 1.0 + max_slice * 1.2

    client = connect(n_devices=n_devices, policy=policy,
                     wait_mode=wait_mode, n_cpus=2, epsilon_ms=eps_ms,
                     shed_policy=shed_policy)
    cluster = client.cluster
    report: dict = {"n_devices": n_devices, "epsilon_ms": eps_ms,
                    "models": {}}
    jobs = []
    try:
        for m in members:
            res = client.submit(
                JobProfile.from_workload(
                    profiles[m.name], period_ms=m.period_ms,
                    priority=m.priority, best_effort=m.best_effort,
                    margin=margin, device=m.device, tier=m.tier),
                workload=workloads[m.name],
                n_iterations=m.n_iterations)
            wcrt = (res.get("wcrt") or {}).get(m.name)
            report["models"][m.name] = {
                "arch": m.arch, "role": m.role, "tier": m.tier,
                "best_effort": m.best_effort,
                "admitted": bool(res.accepted),
                "device": res.get("device"),
                "wcrt_ms": wcrt,
            }
            if not res.accepted and not m.best_effort:
                raise SystemExit(
                    f"RT member {m.name!r} refused admission "
                    f"({res.reason}): {res.error or res.get('wcrt')}")
            log(f"admitted {m.name} -> device {res.get('device')} "
                f"({'BE' if m.best_effort else f'WCRT {wcrt:.1f}ms'})")
            if res.job is not None:
                jobs.append((m, res.job))

        # best-effort background first, then the RT models over it
        for m, job in jobs:
            if m.best_effort:
                job.start(cluster, stop_after_s=duration_s)
        time.sleep(0.05)
        for m, job in jobs:
            if not m.best_effort:
                job.start(cluster, stop_after_s=duration_s)
        client.join(duration_s * 10 + 120)

        report["errors"] = {m.name: repr(job.error) for m, job in jobs
                            if job.error is not None}
        report["per_model"] = cluster.per_model_stats()
        report["per_tier"] = cluster.per_tier_stats()
        report["per_device_mort"] = client.per_device_mort()
        report["admission_latency"] = client.admission_latency()
    finally:
        client.close(shutdown=True)
    cluster.assert_migration_free()
    return report


def check_fleet_report(report: dict) -> None:
    """The fleet acceptance assertions: no member's job failed, and
    every admitted RT model completed releases and observed MORT within
    its admitted WCRT."""
    assert not report["errors"], f"fleet job(s) failed: {report['errors']}"
    for name, m in report["models"].items():
        if m["best_effort"] or not m["admitted"]:
            continue
        stats = report["per_model"][name]
        assert stats["completions"] > 0, f"{name} never completed"
        assert stats["mort_ms"] is not None
        assert stats["mort_ms"] <= m["wcrt_ms"] + 1e-6, \
            f"{name}: MORT {stats['mort_ms']:.1f}ms exceeds admitted " \
            f"WCRT {m['wcrt_ms']:.1f}ms"


def _print_report(report: dict) -> None:
    for name, m in report["models"].items():
        s = report["per_model"].get(name, {})
        kind = "BE" if m["best_effort"] else f"WCRT {m['wcrt_ms']:.1f}ms"
        mort = (f"{s['mort_ms']:.1f}" if s.get("mort_ms") is not None
                else "-")
        p99 = (f"{s['p99_ms']:.1f}" if s.get("p99_ms") is not None
               else "-")
        print(f"  {name:<10} tier {m['tier']} dev {m['device']} "
              f"[{kind}] completions {s.get('completions', 0)} "
              f"misses {s.get('deadline_misses', 0)} "
              f"MORT {mort}ms p99 {p99}ms")
    for tier in sorted(report["per_tier"], reverse=True):
        t = report["per_tier"][tier]
        p99 = (f"{t['p99_ms']:.1f}" if t.get("p99_ms") is not None
               else "-")
        print(f"  tier {tier}: jobs {t['jobs']} completions "
              f"{t['completions']} misses {t['deadline_misses']} "
              f"p99 {p99}ms util {t['utilization']:.3f}")


def run_fleet_daemon(members: Sequence[FleetMember], args) -> None:
    """Daemon mode: the fleet workloads registered durable, then the
    scheduling daemon owning the cluster — submit with
    ``python -m repro.sched.client --socket ... submit --workload
    fleet.chat ...`` and the fleet survives ``kill -9``."""
    import os
    import signal

    from ..sched.daemon import SchedDaemon

    visible_devices(args.n_devices)
    register_fleet_workloads(members)
    daemon = SchedDaemon(args.store, args.socket,
                         n_devices=args.n_devices,
                         shed_policy=_shed_from_args(args))
    daemon.start()
    print(f"fleet daemon ready pid={os.getpid()} "
          f"socket={daemon.socket_path} "
          f"workloads={[f'fleet.{m.name}' for m in members]}", flush=True)
    signal.signal(signal.SIGTERM, lambda *a: daemon._stop.set())
    try:
        daemon.serve_forever()
    finally:
        daemon.stop()


def _shed_from_args(args):
    from ..sched.elastic import ShedPolicy

    if args.shed_at is None:
        if args.tier_budget:
            raise SystemExit("--tier-budget needs --shed-at")
        return None
    budgets = {int(t): float(b) for t, b in
               (spec.split("=", 1) for spec in (args.tier_budget or []))}
    return ShedPolicy(
        shed_at=args.shed_at,
        resume_at=(args.resume_at if args.resume_at is not None
                   else 0.8 * args.shed_at),
        tier_budgets=budgets or None)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="mixed-criticality multi-model serving fleet")
    ap.add_argument("--n-devices", type=int, default=2)
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds to serve before stopping the fleet")
    ap.add_argument("--models", default=None,
                    help="comma-separated member subset (default: all "
                         "four reference models)")
    ap.add_argument("--policy", default="ioctl")
    ap.add_argument("--wait-mode", default="suspend")
    ap.add_argument("--reps", type=int, default=2,
                    help="profile repetitions per member")
    ap.add_argument("--shed-at", type=float, default=None,
                    help="per-device utilization above which best-effort "
                         "members are shed")
    ap.add_argument("--resume-at", type=float, default=None)
    ap.add_argument("--tier-budget", action="append", default=[],
                    metavar="TIER=FRAC",
                    help="per-tier best-effort utilization budget "
                         "(repeatable; requires --shed-at)")
    ap.add_argument("--json", default=None,
                    help="write the fleet report to PATH")
    ap.add_argument("--daemon", action="store_true",
                    help="register fleet workloads and run the durable "
                         "scheduling daemon instead of a one-shot run")
    ap.add_argument("--store", default=None,
                    help="daemon job-store directory (--daemon)")
    ap.add_argument("--socket", default=None,
                    help="daemon unix socket (--daemon)")
    args = ap.parse_args()

    models = args.models.split(",") if args.models else None
    members = default_fleet(args.n_devices, models)
    enable_compile_cache()
    if args.daemon:
        if not args.store:
            ap.error("--daemon requires --store")
        run_fleet_daemon(members, args)
        return

    report = launch_fleet(
        members, n_devices=args.n_devices, duration_s=args.duration,
        policy=args.policy, wait_mode=args.wait_mode, reps=args.reps,
        shed_policy=_shed_from_args(args))
    _print_report(report)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, default=str)
        print(f"wrote {args.json}")
    check_fleet_report(report)
    print(f"fleet OK: {len(members)} models, "
          f"{len(report['per_tier'])} tiers, "
          f"{args.n_devices} devices")


if __name__ == "__main__":
    main()
