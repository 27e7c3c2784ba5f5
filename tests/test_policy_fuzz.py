"""Property-based fuzzing of every registered policy's runtime face.

Random arrival / priority / segment sequences are driven through the
same hook surface ``DeviceExecutor`` uses, asserting after every step:

  (a) the reserved job (policies with an Algorithm 1 reservation) is
      always a highest-device-priority active real-time job;
  (b) ``Alg2State`` (policies with Algorithm 2 lists) never admits two
      RT programs on one device concurrently — and never co-schedules a
      best-effort member with an RT member;
  (c) for the paper's approaches (a reservation or Alg2 lists), a
      best-effort job is never admitted while a real-time job is denied
      — BE work cannot block RT work.  The lock-based sync baselines
      are exempt by design: a best-effort lock holder blocking an RT
      waiter is exactly the priority inversion the paper's approaches
      remove (Sec. II).

``hypothesis`` stays optional via tests/_optional.py (property tests
skip without it); a seeded exhaustive-ish fallback below runs the same
driver regardless, so the invariants are exercised on every platform.
"""
import random

import pytest

from _optional import given, settings, st  # hypothesis or skip-shims
from repro.core import available_policies, make_policy
from repro.sched import RTJob

ACTIONS = ("start", "begin", "end", "complete", "poll")
MAX_JOBS = 5


def _jobs(prios, dprios, be_flags):
    return [RTJob(f"j{i}", lambda j, it: None, period_s=1.0,
                  priority=prios[i], device_priority=dprios[i],
                  best_effort=be_flags[i])
            for i in range(len(prios))]


def _check_invariants(pol, active, in_seg):
    paper_approach = (hasattr(pol, "reserved") or hasattr(pol, "alg2"))
    # (a) Algorithm 1: reserved is a top-device-priority active RT job
    res = getattr(pol, "reserved", None)
    if res is not None:
        assert res.is_rt, f"reserved a best-effort job: {res.name}"
        assert res in active, f"reserved a dead job: {res.name}"
        top = max(j.device_priority for j in active if j.is_rt)
        assert res.device_priority == top, (
            f"reserved {res.name} (dprio {res.device_priority}) over a "
            f"higher-priority active RT job (top {top})")
    # (b) Algorithm 2: at most one RT program; no BE next to an RT
    alg2 = getattr(pol, "alg2", None)
    if alg2 is not None:
        rt_running = [j for j in alg2.running if j.is_rt]
        assert len(rt_running) <= 1, (
            f"two RT programs admitted concurrently: "
            f"{[j.name for j in rt_running]}")
        if rt_running:
            be_running = [j for j in alg2.running if not j.is_rt]
            assert not be_running, (
                f"best-effort {[j.name for j in be_running]} co-admitted "
                f"with RT {rt_running[0].name}")
        assert not (set(map(id, alg2.running)) &
                    set(map(id, alg2.pending))), "running ∩ pending ≠ ∅"
    # (c) BE never blocks RT (paper approaches only; see module docstring)
    if paper_approach:
        domain = in_seg if pol.needs_segment_hooks else active
        denied_rt = [j for j in domain
                     if j.is_rt and not pol.runtime_admitted(j)]
        admitted_be = [j for j in domain
                       if not j.is_rt and pol.runtime_admitted(j)]
        assert not (denied_rt and admitted_be), (
            f"BE {[j.name for j in admitted_be]} admitted while RT "
            f"{[j.name for j in denied_rt]} is denied")


def drive(policy_name, prios, dprios, be_flags, script):
    """Interpret ``script`` (a list of (job_idx, action)) leniently —
    illegal transitions are skipped — exactly the way the executor
    drives the runtime face, checking invariants after every step."""
    pol = make_policy(policy_name)
    pol.runtime_attach(None)
    jobs = _jobs(prios, dprios, be_flags)
    active, in_seg, completed = [], [], set()

    def poll():
        if pol.wants_poll_thread:
            pol.runtime_poll([j for j in active if j.is_rt])

    steps = 0
    for idx, act in script:
        job = jobs[idx % len(jobs)]
        if act == "start":
            if job in active or job.uid in completed:
                continue
            active.append(job)
            pol.runtime_on_start(job)
            poll()
        elif act == "begin":
            if job not in active or job in in_seg:
                continue
            if pol.needs_segment_hooks:
                pol.runtime_segment_begin(job)
            in_seg.append(job)
        elif act == "end":
            if job not in in_seg:
                continue
            if pol.needs_segment_hooks:
                pol.runtime_segment_end(job)
            in_seg.remove(job)
        elif act == "complete":
            if job not in active:
                continue
            if job in in_seg:   # well-formed jobs close their segments
                if pol.needs_segment_hooks:
                    pol.runtime_segment_end(job)
                in_seg.remove(job)
            active.remove(job)
            completed.add(job.uid)
            pol.runtime_on_complete(job)
            poll()
        else:  # "poll"
            poll()
        _check_invariants(pol, active, in_seg)
        steps += 1
    return steps


# ---------------------------------------------------------------------------
# hypothesis properties (skip without the test extra)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", available_policies())
@settings(max_examples=60, deadline=None)
@given(prios=st.permutations(list(range(1, MAX_JOBS + 1))),
       dprios=st.permutations(list(range(1, MAX_JOBS + 1))),
       be_flags=st.lists(st.booleans(), min_size=MAX_JOBS,
                         max_size=MAX_JOBS),
       script=st.lists(st.tuples(st.integers(0, MAX_JOBS - 1),
                                 st.sampled_from(ACTIONS)),
                       max_size=80))
def test_policy_invariants_fuzzed(policy, prios, dprios, be_flags, script):
    drive(policy, prios, dprios, be_flags, script)


@settings(max_examples=100, deadline=None)
@given(rt_flags=st.lists(st.booleans(), min_size=2, max_size=6),
       script=st.lists(st.tuples(st.integers(0, 5),
                                 st.sampled_from(["add", "remove"])),
                       max_size=60))
def test_alg2_state_never_two_rt(rt_flags, script):
    """Algorithm 2 in isolation: whatever the add/remove interleaving,
    task_running holds at most one RT member and never mixes RT with
    best-effort members."""
    from repro.core import Alg2State

    class Stub:
        def __init__(self, i, rt):
            self.name = f"s{i}"
            self.is_rt = rt
            self.priority = self.device_priority = i + 1
            self.gpu_pending = False

    stubs = [Stub(i, rt) for i, rt in enumerate(rt_flags)]
    st_ = Alg2State()
    inside = set()
    for idx, op in script:
        s = stubs[idx % len(stubs)]
        if op == "add" and id(s) not in inside:
            st_.add(s)
            inside.add(id(s))
        elif op == "remove" and id(s) in inside:
            st_.remove(s)
            inside.discard(id(s))
        rt_running = [j for j in st_.running if j.is_rt]
        assert len(rt_running) <= 1
        if rt_running:
            assert all(j.is_rt for j in st_.running)
        assert not (set(map(id, st_.running)) & set(map(id, st_.pending)))


# ---------------------------------------------------------------------------
# seeded fallback: same driver, no hypothesis required
# ---------------------------------------------------------------------------

def test_alg2_end_from_pending_does_not_corrupt_runlist():
    """Regression (found by this fuzzer): end() from a job that never
    reached task_running (its segment body errored/cancelled while
    pending — the executor's device_segment.__exit__ still calls end())
    used to run the handover and admit a pending RT job *next to* the
    current holder: two RT programs concurrently.  The departing
    pending job must simply be dropped."""
    from repro.core import Alg2State

    class Stub:
        def __init__(self, name, prio, rt=True):
            self.name = name
            self.is_rt = rt
            self.priority = self.device_priority = prio
            self.gpu_pending = False

    holder, waiter, be = Stub("hold", 20), Stub("wait", 10), \
        Stub("be", 0, rt=False)
    st_ = Alg2State()
    st_.add(holder)
    st_.add(be)       # pending behind the RT holder
    st_.add(waiter)   # pending, lower priority than holder
    assert [j.name for j in st_.running] == ["hold"]
    # the BE job gives up from pending: no handover, no membership change
    assert st_.remove(be) is False
    assert [j.name for j in st_.running] == ["hold"]
    assert not be.gpu_pending
    # the waiter gives up from pending: holder keeps the runlist alone
    assert st_.remove(waiter) is False
    assert [j.name for j in st_.running] == ["hold"]
    # and the real holder's end() still hands over normally
    st_.add(waiter)
    assert st_.remove(holder) is True
    assert [j.name for j in st_.running] == ["wait"]


def test_alg2_rt_arrival_displaces_best_effort_of_any_number():
    """Regression (found by this fuzzer): an RT arrival used to be ranked
    against the highest-numbered member of task_running even when that
    member was best-effort; behind it the RT job waited in task_pending,
    and the next end() handed it the runlist beside the best-effort
    member.  An RT arrival outranks every best-effort member."""
    from repro.core import Alg2State

    class Stub:
        def __init__(self, name, prio, rt):
            self.name = name
            self.is_rt = rt
            self.priority = self.device_priority = prio
            self.gpu_pending = False

    be_low, be_high, rt = (Stub("be1", 1, False), Stub("be6", 6, False),
                           Stub("rt5", 5, True))
    st_ = Alg2State()
    st_.add(be_low)
    st_.add(be_high)
    assert st_.add(rt) is True
    assert [j.name for j in st_.running] == ["rt5"]
    assert {j.name for j in st_.pending} == {"be1", "be6"}
    st_.remove(rt)
    assert {j.name for j in st_.running} == {"be1", "be6"}


def test_best_effort_device_priority_is_ignored():
    """Regression (found by this fuzzer): a best-effort RTJob built with
    an explicit high device_priority used to outrank RT arrivals in
    Alg2State.top_running, pushing the RT job to task_pending behind
    best-effort work.  BE jobs have no real-time priority — the
    constructor must pin their device priority to the BE level."""
    from repro.sched.job import BEST_EFFORT

    be = RTJob("be", lambda j, it: None, period_s=1.0, priority=0,
               device_priority=99, best_effort=True)
    assert be.device_priority == BEST_EFFORT
    # and the end-to-end Alg2 consequence: the RT arrival preempts
    drive("ioctl", [10, 0], [10, 99], [False, True],
          [(1, "start"), (1, "begin"), (0, "start"), (0, "begin")])


@pytest.mark.parametrize("policy", available_policies())
@pytest.mark.parametrize("seed", range(8))
def test_policy_invariants_seeded(policy, seed):
    # PYTHONHASHSEED-stable seed (hash() is randomized per process)
    rng = random.Random(10_000 * seed + sum(map(ord, policy)))
    n = rng.randint(1, MAX_JOBS)
    prios = rng.sample(range(1, 50), n)
    dprios = rng.sample(range(1, 50), n)
    be_flags = [rng.random() < 0.4 for _ in range(n)]
    script = [(rng.randrange(n), rng.choice(ACTIONS))
              for _ in range(120)]
    assert drive(policy, prios, dprios, be_flags, script) > 0
