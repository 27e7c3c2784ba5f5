"""The scheduler's spans (sched/spans.py, DESIGN.md §13): nesting and one
identifier per release, the bounded buffer, spans outside a release and
under a running profiler, the executor's spans against the counters that
share their clock readings, blocking by the slice in flight, and
responses timed from the due release."""
import glob
import os
import threading
import time

import pytest

from repro.sched import DeviceExecutor, RTJob, spans
from repro.sched.job import JobStats
from repro.sched.workloads import get_workload


def _by_name(records, name):
    return [s for s in records if s.name == name]


def test_nesting_records_parents_and_one_release():
    buf = spans.new_buffer()
    with spans.release(buf, job="j", k=0, due=0):
        with spans.span("a", x=1):
            with spans.span("b"):
                pass
        with spans.span("c"):
            pass
    assert [s.name for s in buf] == ["b", "a", "c", "sched.release"]
    b, a, c, root = buf
    assert root.parent_id == 0 and root.release == root.span_id
    assert a.parent_id == root.span_id and c.parent_id == root.span_id
    assert b.parent_id == a.span_id
    assert {s.release for s in buf} == {root.span_id}
    assert len({s.span_id for s in buf}) == 4
    assert root.start_ns <= a.start_ns <= b.start_ns <= b.end_ns \
        <= a.end_ns <= c.start_ns <= c.end_ns <= root.end_ns
    assert a.args == {"x": 1}
    assert root.args == {"job": "j", "k": 0, "due": 0}


def test_each_release_has_its_own_identifier_on_each_thread():
    """Two releases in a row on one thread, and two threads each in a
    release at once: a span joins the release open on its own thread."""
    bufs = {n: spans.new_buffer() for n in ("x", "y")}
    gate = threading.Barrier(2)

    def work(name):
        for k in range(2):
            with spans.release(bufs[name], job=name, k=k, due=0):
                gate.wait(timeout=10)
                with spans.span("inner"):
                    gate.wait(timeout=10)

    threads = [threading.Thread(target=work, args=(n,)) for n in bufs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    ids = []
    for buf in bufs.values():
        roots = _by_name(buf, "sched.release")
        assert len(roots) == 2
        for root in roots:
            inner = [s for s in buf if s.parent_id == root.span_id]
            assert [s.name for s in inner] == ["inner"]
            assert inner[0].release == root.span_id
        ids += [r.span_id for r in roots]
    assert len(set(ids)) == 4


def test_identifiers_stay_unique_under_many_threads():
    """More threads than cores, switching often, each through many
    releases: every span gets its own identifier, and every span joins
    the release open on its own thread."""
    import sys
    n_threads, n_releases = 4 * (os.cpu_count() or 1), 50
    bufs = [spans.new_buffer() for _ in range(n_threads)]

    def work(buf):
        for k in range(n_releases):
            with spans.release(buf, job="j", k=k, due=0):
                with spans.span("outer"):
                    with spans.span("inner"):
                        pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(b,)) for b in bufs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    every = [s for buf in bufs for s in buf]
    assert len(every) == n_threads * n_releases * 3
    assert len({s.span_id for s in every}) == len(every)
    for buf in bufs:
        roots = {s.span_id for s in buf if s.name == "sched.release"}
        assert len(roots) == n_releases
        by_id = {s.span_id: s for s in buf}
        for s in buf:
            assert s.release in roots
            if s.name != "sched.release":
                assert by_id[s.parent_id].release == s.release


def test_buffer_keeps_the_newest_spans_only():
    st = JobStats()
    assert st.spans.maxlen == spans.BUFFER
    with spans.release(st.spans, job="j", k=0, due=0):
        for i in range(spans.BUFFER + 10):
            with spans.span("s", i=i):
                pass
    assert len(st.spans) == spans.BUFFER
    assert st.spans[-1].name == "sched.release"
    assert st.spans[0].args["i"] == 11


def test_span_outside_a_release_records_nothing_and_still_times():
    with spans.span("lonely", a=1) as s:
        time.sleep(0.002)
    assert s.seconds >= 0.002
    # an executor driven by hand, as profiling before admission does
    ex = DeviceExecutor(policy="ioctl", wait_mode="suspend")
    job = RTJob("by-hand", lambda job, it: None, period_s=1.0, priority=5)
    with ex.device_segment(job):
        ex.run_sliced(job, get_workload("demo.count")(total=16))
    ex.shutdown()
    assert len(job.stats.spans) == 0
    assert len(ex.update_times) == 2
    assert len(job.stats.slice_times) == 3


def test_spans_land_in_a_running_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    buf = spans.new_buffer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.release(buf, job="traced", k=3, due=7):
            with spans.span("sched.slice", i=1, op="decode"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sched."):
                    seen[e.name] = dict(e.stats)
    assert seen["sched.release"]["job"] == "traced"
    assert int(seen["sched.release"]["k"]) == 3
    assert seen["sched.slice"]["op"] == "decode"
    assert [s.name for s in buf] == ["sched.slice", "sched.release"]


def _spin_body(ex, slices, slice_ms):
    def body(job, it):
        with ex.device_segment(job):
            ex.run_sliced(job, get_workload("demo.spin")(
                slices=slices, slice_ms=slice_ms))
    return body


def test_rt_release_waits_out_the_slice_in_flight():
    """An RT release that arrives during a BE slice records a
    ``sched.device_wait`` about as long as what was left of that slice,
    and its own slices follow; the runlist updates of both jobs are the
    very samples of ``update_times``."""
    slice_s = 0.08
    ex = DeviceExecutor(policy="ioctl", wait_mode="suspend")
    be = RTJob("be", _spin_body(ex, 5, slice_s * 1e3), period_s=10.0,
               priority=0, best_effort=True)
    rt = RTJob("rt", _spin_body(ex, 2, 5.0), period_s=10.0, priority=50)
    be.start(ex)
    time.sleep(slice_s * 1.5)       # mid-slice
    rt.start(ex)
    rt.join(20)
    be.join(20)
    ex.shutdown()
    assert rt.stats.completions == 1 and be.stats.completions == 1
    first_wait = min(_by_name(rt.stats.spans, "sched.device_wait"),
                     key=lambda s: s.start_ns)
    in_flight = [s for s in _by_name(be.stats.spans, "sched.slice")
                 if s.start_ns <= first_wait.start_ns < s.end_ns]
    assert len(in_flight) == 1
    left = (in_flight[0].end_ns - first_wait.start_ns) / 1e9
    waited = (first_wait.end_ns - first_wait.start_ns) / 1e9
    assert left > 0.01
    assert waited == pytest.approx(left, abs=0.02)
    # the BE job was refused by the policy while the RT job ran
    assert _by_name(be.stats.spans, "sched.policy_wait")
    rt_slices = _by_name(rt.stats.spans, "sched.slice")
    assert [s.args["i"] for s in rt_slices] == [0, 1, 2]
    assert {s.args["op"] for s in rt_slices} == {"demo.spin"}
    assert [s.end_ns - s.start_ns for s in rt_slices] == \
        [round(t * 1e9) for t in rt.stats.slice_times]
    updates = [s for j in (be, rt)
               for s in _by_name(j.stats.spans, "sched.runlist_update")]
    assert len(updates) == 4
    assert sorted((s.end_ns - s.start_ns) / 1e9 for s in updates) == \
        sorted(ex.update_times)
    assert sorted(s.args["which"] for s in updates) == \
        ["add", "add", "end", "end"]


@pytest.mark.parametrize("wait_mode", ["busy", "suspend"])
def test_policy_wait_spans_the_refused_interval(wait_mode):
    """A lower-priority job refused at a slice boundary records one
    ``sched.policy_wait`` per refusal, covering the higher-priority
    job's device segment; an admitted check records none."""
    ex = DeviceExecutor(policy="ioctl", wait_mode=wait_mode)
    lo = RTJob("lo", _spin_body(ex, 6, 30.0), period_s=10.0, priority=10)
    hi = RTJob("hi", _spin_body(ex, 3, 30.0), period_s=10.0, priority=20)
    lo.start(ex)
    time.sleep(0.045)
    hi.start(ex)
    hi.join(20)
    lo.join(20)
    ex.shutdown()
    assert not _by_name(hi.stats.spans, "sched.policy_wait")
    waits = _by_name(lo.stats.spans, "sched.policy_wait")
    assert len(waits) == 1
    hi_root = _by_name(hi.stats.spans, "sched.release")[0]
    # lo waits from its first refusal until hi's segment ends
    assert waits[0].end_ns >= hi_root.end_ns - 5_000_000
    assert (waits[0].end_ns - waits[0].start_ns) / 1e9 > 0.05


def test_segment_build_precedes_the_segment_slices():
    """Building each device segment's op is one ``sched.segment_build``
    under the release, inside the segment's runlist updates and before
    its first slice; host segments build nothing."""
    from repro.core.segments import SegmentedWorkload
    ex = DeviceExecutor(policy="ioctl", wait_mode="suspend")
    wl = (SegmentedWorkload("w")
          .host(lambda: None)
          .device(lambda: get_workload("demo.spin")(slices=2, slice_ms=2.0),
                  label="first")
          .device(lambda: get_workload("demo.spin")(slices=1, slice_ms=2.0),
                  label="second"))
    job = RTJob("seg", wl.bind(ex), period_s=10.0, priority=10)
    job.start(ex)
    job.join(20)
    ex.shutdown()
    assert job.stats.completions == 1
    root = _by_name(job.stats.spans, "sched.release")[0]
    builds = _by_name(job.stats.spans, "sched.segment_build")
    assert [b.args["op"] for b in builds] == ["first", "second"]
    assert all(b.parent_id == root.span_id for b in builds)
    adds = [s for s in _by_name(job.stats.spans, "sched.runlist_update")
            if s.args["which"] == "add"]
    slices = _by_name(job.stats.spans, "sched.slice")
    for b, add, first in zip(builds, adds, (slices[0], slices[3])):
        assert add.end_ns <= b.start_ns <= b.end_ns <= first.start_ns


def test_decode_enqueue_is_a_child_of_its_slice():
    import jax

    from repro.configs import get
    from repro.launch.serve import InferenceEngine
    eng = InferenceEngine(get("smollm-135m").reduced(), max_len=32)
    eng.prefill_batch(jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0,
                                         eng.cfg.vocab_size))
    ex = DeviceExecutor(policy="ioctl", wait_mode="suspend")

    def body(job, it):
        with ex.device_segment(job):
            ex.run_sliced(job, eng.decode_segment(5, slice_tokens=2))

    job = RTJob("decode", body, period_s=10.0, priority=10)
    job.start(ex)
    job.join(120)
    ex.shutdown()
    assert job.stats.completions == 1
    slices = {s.span_id: s for s in _by_name(job.stats.spans, "sched.slice")}
    assert sorted(s.args["i"] for s in slices.values()) == [0, 1, 2, 3]
    enq = _by_name(job.stats.spans, "serve.decode_enqueue")
    assert [e.args["tokens"] for e in enq] == [2, 2, 1]
    for e in enq:
        parent = slices[e.parent_id]
        assert parent.args["op"] == "decode"
        assert parent.start_ns <= e.start_ns <= e.end_ns <= parent.end_ns


def test_response_counts_the_lateness_a_release_carries():
    """The first release overruns its period; the second starts late and
    its response runs from when it was due, so it carries the overrun.
    The schedule moves on from the late start: the third is due a period
    after it, and responds in its own time."""
    period = 0.1

    def body(job, it):
        time.sleep(0.25 if it == 0 else 0.01)

    ex = DeviceExecutor(policy="ioctl", wait_mode="suspend")
    job = RTJob("late", body, period_s=period, priority=10, n_iterations=3)
    job.start(ex)
    job.join(10)
    ex.shutdown()
    r = job.stats.response_times
    assert len(r) == 3
    assert r[1] >= 0.25 - period + 0.01 - 0.002
    assert r[2] < 0.1
    assert job.stats.deadline_misses == 2
    roots = _by_name(job.stats.spans, "sched.release")
    dues = [s.args["due"] for s in roots]
    assert dues[1] - dues[0] == pytest.approx(period * 1e9, abs=1e6)
    # the second started late, about when the first completed
    assert roots[1].start_ns - dues[1] >= 0.14e9
    assert roots[1].start_ns >= roots[0].end_ns
    # the third is due a period after the late start of the second
    assert dues[2] - roots[1].start_ns == pytest.approx(period * 1e9,
                                                        abs=2e6)


def test_on_time_releases_are_due_a_period_apart():
    ex = DeviceExecutor(policy="ioctl", wait_mode="suspend")
    job = RTJob("steady", lambda job, it: time.sleep(0.005), period_s=0.05,
                priority=10, n_iterations=4)
    job.start(ex)
    job.join(10)
    ex.shutdown()
    dues = [s.args["due"] for s in _by_name(job.stats.spans,
                                            "sched.release")]
    assert [b - a for a, b in zip(dues, dues[1:])] == \
        [pytest.approx(0.05e9, abs=1e6)] * 3
    assert all(r < 0.05 for r in job.stats.response_times)
