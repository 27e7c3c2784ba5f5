"""The paper's scenario as one call: a real-time serving job preempts a
best-effort training job at slice boundaries on a shared device.

Both jobs are ``SegmentedWorkload``s.  Each is profiled
(``SegmentedWorkload.profile()``), priced by the response-time analysis
through ``repro.sched.connect()`` and run on the cluster runtime;
``check_preemptive`` then holds the run to the guarantee.  Used by
``examples/preemptive_serving.py`` (reduced models, CPU or chip) and by
``chip_smoke.py`` (smollm-135m at its published widths, on the chip).
"""
from __future__ import annotations

import time

from ..core.segments import SegmentedWorkload

#: RT releases per run, and how many must complete
N_RT_RELEASES, MIN_RT_COMPLETIONS = 4, 3
#: observations are not WCETs: the profile, and the longest profiled
#: slice that ε is made of, are inflated by this factor
MARGIN = 2.0
#: profile repetitions; the first is the jit warm-up and is dropped
PROFILE_REPS = 4
#: a pause of the whole serving process that the host imposes, a few times
#: a minute outside its longer freezes: 107-112 ms on a TPU v5e host, with
#: every thread stopped (PERF.md §6).  It stretches whatever slice it
#: meets by its own length, however short the slice, so ε adds it once
#: instead of scaling it with the slice
HOST_PAUSE_MS = 120.0


def epsilon_ms(max_slice_ms: float) -> float:
    """ε: 1 ms of runlist update, the margin-inflated longest profiled
    slice of any job, and one host pause."""
    return 1.0 + MARGIN * max_slice_ms + HOST_PAUSE_MS


def _wcet_ms(prof, margin: float) -> float:
    host, dev = prof.segments_ms(margin)
    return sum(host) + sum(m + e for m, e in dev)


def run_preemptive(infer_wl: SegmentedWorkload,
                   train_wl: SegmentedWorkload, *, n_devices: int = 1,
                   infer_device: int = 0, train_device: int = 0,
                   policy: str = "ioctl") -> dict:
    """Profile both workloads, admit them through ``connect()`` with
    suspending waits (RT ``infer`` at priority 50, best-effort ``train``
    below it), run ``N_RT_RELEASES`` RT periods with training underneath,
    and return the evidence.

    ε is :func:`epsilon_ms` of the longest slice of either profile.  The
    RT period is three times the margin-inflated profile plus four ε; the
    BE job is released back to back.  Raises ``SystemExit`` when admission refuses either job."""
    from ..sched import JobProfile, connect

    infer_prof = infer_wl.profile(reps=PROFILE_REPS)
    train_prof = train_wl.profile(reps=PROFILE_REPS)
    # epsilon = admission-update cost + the residual of one in-flight
    # *slice* (any job's), as a host pause may stretch it: preemption
    # takes effect at slice boundaries, so the bound is one slice — not a
    # whole train step (DESIGN.md §6)
    max_slice = max(infer_prof.max_slice_ms, train_prof.max_slice_ms)
    eps_ms = epsilon_ms(max_slice)
    period_ms = 3.0 * (_wcet_ms(infer_prof, MARGIN) + 4 * eps_ms)
    duration_s = (N_RT_RELEASES - 0.5) * period_ms / 1e3

    client = connect(n_devices=n_devices, policy=policy, wait_mode="suspend",
                     n_cpus=1, epsilon_ms=eps_ms)
    cluster = client.cluster
    res = client.submit(
        JobProfile.from_workload(infer_prof, period_ms=period_ms,
                                 priority=50, margin=MARGIN,
                                 device=infer_device),
        workload=infer_wl, n_iterations=N_RT_RELEASES)
    res_train = client.submit(
        JobProfile.from_workload(train_prof,
                                 period_ms=_wcet_ms(train_prof, 1.0),
                                 priority=0, best_effort=True,
                                 margin=MARGIN, device=train_device),
        workload=train_wl, n_iterations=10 ** 6)
    for tag, r in (("infer", res), ("train", res_train)):
        if r.job is None:
            # nothing has started yet (submit was called without start)
            client.close(shutdown=True)
            raise SystemExit(f"admission refused {tag}: "
                             f"{r.get('error') or r['wcrt']}")
    infer, train = res.job, res_train.job
    train.start(cluster, stop_after_s=duration_s)
    time.sleep(0.05)
    infer.start(cluster, stop_after_s=duration_s)
    try:
        infer.join(duration_s + 10 * period_ms / 1e3 + 60)
        train.join(duration_s + 60)
    finally:
        client.close(shutdown=True)
    cluster.assert_migration_free()
    return {
        "admitted": {"infer": res.accepted, "train": res_train.accepted},
        "wcrt_ms": res["wcrt"].get(infer.name, float("inf")),
        "period_ms": period_ms, "epsilon_ms": eps_ms,
        "rt_slice_ms": infer_prof.device[-1].slice_ms,
        "rt_completions": infer.stats.completions,
        "rt_mort_ms": (infer.stats.mort or 0.0) * 1e3,
        "rt_deadline_misses": infer.stats.deadline_misses,
        "be_completions": train.stats.completions,
        "profiled_max_slice_ms": max_slice,
        "max_slice_ms": max(infer.stats.max_slice_time or 0.0,
                            train.stats.max_slice_time or 0.0) * 1e3,
        "slice_ms": {j.name: sorted(1e3 * t for t in j.stats.slice_times)
                     for j in (infer, train)},
        "errors": {j.name: j.error for j in (infer, train)
                   if j.error is not None},
        "per_device_mort": client.per_device_mort(),
    }


def check_preemptive(result: dict) -> None:
    """Raise unless the run kept the guarantee: no job failed, both were
    admitted, no slice outlasted the ε the analysis was given, the RT
    job completed ``MIN_RT_COMPLETIONS`` times within its admitted WCRT
    and missed no deadline, and training progressed."""
    if result["errors"]:
        name, err = next(iter(result["errors"].items()))
        raise RuntimeError(f"job {name!r} failed: {err!r}") from err
    if not all(result["admitted"].values()):
        raise RuntimeError(f"admission: {result['admitted']}")
    if result["max_slice_ms"] > result["epsilon_ms"]:
        raise RuntimeError(f"a slice took {result['max_slice_ms']:.1f} ms, "
                           f"longer than the preemption bound ε "
                           f"{result['epsilon_ms']:.1f} ms admission "
                           f"assumed")
    if result["rt_completions"] < MIN_RT_COMPLETIONS:
        raise RuntimeError(f"RT job completed {result['rt_completions']} "
                           f"time(s), fewer than {MIN_RT_COMPLETIONS}")
    if result["rt_mort_ms"] > result["wcrt_ms"]:
        raise RuntimeError(f"WCRT bound violated: MORT "
                           f"{result['rt_mort_ms']:.1f} ms > WCRT "
                           f"{result['wcrt_ms']:.1f} ms")
    if result["rt_deadline_misses"]:
        raise RuntimeError(f"{result['rt_deadline_misses']} RT deadline "
                           f"miss(es)")
    if result["be_completions"] < 1:
        raise RuntimeError("best-effort training made no progress")
