"""The readers of the program's spans (bench/program_spans.py and the
metrics built on it), on hand-built runs with no chip: each reads what
it should from known spans, and gives None where the program recorded
none.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests"""
from __future__ import annotations

import itertools
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

from bench import catalog, traffic  # noqa: E402
from bench import program_spans as ps  # noqa: E402
from repro.sched.spans import Span  # noqa: E402

MS = 1_000_000
#: program (monotonic) ns = trace ns + BASE
BASE = 100_000 * MS


def read(name, run):
    return catalog.metric_reader(name)(run)


class Spans:
    """Builds one job's spans: ``release`` opens a root, ``add`` a span
    below it (or below ``parent``); times in trace ms."""

    ids = itertools.count(1)

    def __init__(self, job):
        self.job, self.out, self.root = job, [], None

    def _next(self):
        return next(Spans.ids)

    def release(self, k, due_ms, start_ms, end_ms):
        sid = self._next()
        self.root = Span("sched.release", BASE + int(start_ms * MS),
                         BASE + int(end_ms * MS), sid, 0, sid,
                         {"job": self.job, "k": k,
                          "due": BASE + int(due_ms * MS)})
        self.out.append(self.root)
        return self.root

    def add(self, name, start_ms, end_ms, parent=None, **args):
        s = Span(name, BASE + int(start_ms * MS), BASE + int(end_ms * MS),
                 self._next(), (parent or self.root).span_id,
                 self.root.span_id, args)
        self.out.append(s)
        return s


def _mono_s(ms):
    return (BASE + ms * MS) / 1e9


def _run(jobs, releases=(), slices=(), trace_events=None):
    return NS(job_stats={j.job: NS(spans=j.out) for j in jobs},
              releases=list(releases), slices=list(slices),
              trace_events=trace_events)


def _parent_run():
    """What a program that records no spans leaves: JobStats without
    them, and a trace."""
    st = NS(slice_times=[0.1], mort=1.0)
    return NS(job_stats={"hi": st, "be_train": st},
              releases=[{"task": "hi", "k": 0, "due": 1.0, "start": 1.1}],
              slices=[{"kind": "train", "task": "be_train", "start": 1.0,
                       "end": 1.1}],
              trace_events=_trace())


# -- RT releases --------------------------------------------------------

def _rt_run():
    hi = Spans("hi")
    hi.release(0, 0, 1, 60)
    hi.add("sched.runlist_update", 1.1, 1.12, which="add")
    hi.add("sched.segment_build", 1.12, 1.2, op="prefill")
    hi.add("sched.device_wait", 1.2, 4.2)
    hi.add("sched.slice", 4.2, 20, i=0, op="prefill")
    hi.add("sched.device_wait", 20.1, 21.1)
    dec = hi.add("sched.slice", 21.1, 31.1, i=0, op="decode")
    hi.add("serve.decode_enqueue", 21.2, 25.2, parent=dec, tokens=4)
    hi.release(1, 100, 100, 160)
    hi.add("sched.device_wait", 100.5, 110.5)
    dec = hi.add("sched.slice", 110.5, 120.5, i=0, op="decode")
    hi.add("serve.decode_enqueue", 110.6, 114.6, parent=dec, tokens=4)
    hi.release(2, 200, 200, 300)            # due after the window
    hi.add("sched.device_wait", 200, 300)
    be = Spans("be_train")
    be.release(0, 0, 0, 50)
    for i, us in enumerate((10, 20, 30, 40, 50, 60, 70, 80, 90, 100)):
        be.add("sched.runlist_update", i, i + us / 1e3,
               which=("add", "end")[i % 2])
    releases = [{"task": "hi", "k": 0, "due": _mono_s(0),
                 "start": _mono_s(4.5)},
                {"task": "hi", "k": 1, "due": _mono_s(100),
                 "start": _mono_s(110.6)}]
    return _run([hi, be], releases)


def test_rt_block_is_the_median_release_device_wait():
    # release 0: 3 ms + 1 ms; release 1: 10 ms; release 2 is not due in
    # the window
    assert read("rt_block_p50_ms", _rt_run()) == pytest.approx(7.0)


def test_runlist_update_p95():
    want = traffic.percentile([0.02 * 1e3, 10, 20, 30, 40, 50, 60, 70, 80,
                               90, 100], 95)
    assert read("runlist_update_p95_us", _rt_run()) == pytest.approx(
        want, rel=1e-6)


def test_decode_enqueue_share_of_its_slices():
    # 4 ms of enqueue in each of two 10 ms decode slices; the prefill
    # slice has none and is not counted
    assert read("decode_enqueue_share", _rt_run()) == pytest.approx(40.0)


def test_start_delay_is_split_into_spans():
    rows = ps.start_delay_split(_rt_run())
    assert [(r["task"], r["k"]) for r in rows] == [("hi", 0), ("hi", 1)]
    r = rows[0]
    assert r["delay_ms"] == pytest.approx(4.5)
    assert r["lag_ms"] == pytest.approx(1.0)
    assert r["sched.runlist_update"] == pytest.approx(0.02)
    assert r["sched.segment_build"] == pytest.approx(0.08)
    assert r["sched.device_wait"] == pytest.approx(3.0)
    assert r["sched.policy_wait"] == 0
    assert r["residual_ms"] == pytest.approx(4.5 - 1.0 - 0.02 - 0.08 - 3.0)
    assert rows[1]["sched.device_wait"] == pytest.approx(10.0)


# -- boundaries on a trace ---------------------------------------------

def _trace():
    """Device work 11-28, 35-49 and 53-69 ms of a 0-100 ms window, and
    the benchmark's span inside each slice that has device work."""
    dev = [["op", 11 * MS, 17 * MS], ["op", 35 * MS, 10 * MS],
           ["op", 40 * MS, 9 * MS], ["op", 53 * MS, 16 * MS]]
    host = [["bench.window", 0, 100 * MS, {}],
            ["bench.be_train_slice", 10 * MS, 19 * MS,
             {"task": "be_train"}],
            ["bench.rt_prefill", 33 * MS, 16 * MS, {"task": "hi"}],
            ["bench.be_train_slice", 52 * MS, 17 * MS,
             {"task": "be_train"}]]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def _boundary_run():
    """Slices A (train, 10-30), B (a finalize, 31-31.1), C (prefill,
    33-50), D (train, 52-70): boundaries A|B|C, idle 28-35 ms, and C|D,
    idle 49-53 ms."""
    be, hi = Spans("be_train"), Spans("hi")
    be.release(0, 0, 5, 31.5)
    be.add("sched.slice", 10, 30, i=0, op="train")
    be.add("sched.slice", 31, 31.1, i=1, op="train")
    hi.release(0, 30, 30.5, 51)
    hi.add("sched.segment_build", 30.6, 31.4, op="prefill")
    hi.add("sched.device_wait", 31.5, 33)
    hi.add("sched.slice", 33, 50, i=0, op="prefill")
    be.release(1, 31.5, 50.1, 80)
    be.add("sched.policy_wait", 50.2, 51.8)
    be.add("sched.slice", 52, 70, i=0, op="train")
    # the benchmark's own record of each span of the trace
    slices = [{"kind": k, "task": t, "start": _mono_s(s), "end": _mono_s(e)}
              for k, t, s, e in (("train", "be_train", 10, 29),
                                 ("prefill", "hi", 33, 49),
                                 ("train", "be_train", 52, 69))]
    return _run([be, hi], slices=slices, trace_events=_trace())


def test_trace_offset_from_the_benchmark_spans():
    assert ps.trace_offset_ns(_boundary_run()) == pytest.approx(-BASE,
                                                               abs=1)


def test_boundary_idle_joins_gaps_around_a_slice_without_device_work():
    run = _boundary_run()
    bounds = ps.boundaries(run)
    assert [b["idle_ns"] / MS for b in bounds] == [pytest.approx(7.0),
                                                   pytest.approx(4.0)]
    assert len(bounds[0]["pairs"]) == 2
    assert read("boundary_idle_p50_ms", run) == pytest.approx(5.5)


def test_boundary_idle_split_by_what_the_host_was_in():
    run = _boundary_run()
    got = {k: v / MS for k, v in ps.boundary_split(
        run, ps.boundaries(run)).items()}
    # 28-30 the train slice's sync tail, 30.6-31.4 hi builds its
    # segment, the rest of 30-31.5 nothing (B is a finalize), 31.5-33 hi
    # waits for the device, 33-35 hi dispatches; 49-50 prefill's tail,
    # 50.2-51.8 the BE job's policy wait, 52-53 dispatch
    assert got == {"sync tail": pytest.approx(3.0),
                   "dispatch": pytest.approx(3.0),
                   "sched.runlist_update": 0,
                   "sched.segment_build": pytest.approx(0.8),
                   "sched.policy_wait": pytest.approx(1.6),
                   "sched.device_wait": pytest.approx(1.5),
                   "none": pytest.approx(0.7 + 0.2 + 0.2)}
    assert sum(got.values()) == pytest.approx(11.0)


def test_boundaries_correct_the_device_clock_skew():
    """Three 100 ms train slices whose device work the trace stamps 2 ms
    early, before the host dispatched it: uncorrected, no device gap
    would overlap the host's 1 ms between two slices."""
    be = Spans("be_train")
    be.release(0, 0, 0, 310)
    host, dev, slices = [["bench.window", -5 * MS, 315 * MS, {}]], [], []
    for i, (s0, d0, d1) in enumerate(((0, 0.5, 99.5), (101, 101.6, 200.4),
                                      (202, 202.9, 301.5))):
        be.add("sched.slice", s0, s0 + 100, i=i, op="train")
        host.append(["bench.be_train_slice", s0 * MS, 100 * MS,
                     {"task": "be_train"}])
        slices.append({"kind": "train", "task": "be_train",
                       "start": _mono_s(s0), "end": _mono_s(s0 + 100)})
        # the work as the trace has it, 2 ms early, with ns-long holes
        for a in np.arange(d0 - 2, d1 - 2, 0.5):
            dev.append(["op", int(a * MS), int(0.5 * MS) - 2])
    run = _run([be], slices=slices,
               trace_events={"devices": {"/device:TPU:0": dev},
                             "host": host})
    pl = ps.placement(run)
    assert pl["skew"] - pl["margin"] <= -2 * MS <= pl["skew"] + pl["margin"]
    assert [b["idle_ns"] / MS for b in ps.boundaries(run)] == [
        pytest.approx(2.1, abs=0.01), pytest.approx(2.3, abs=0.01)]
    assert read("boundary_idle_p50_ms", run) == pytest.approx(2.2, abs=0.01)


# -- a program without spans -------------------------------------------

@pytest.mark.parametrize("name", ["rt_block_p50_ms", "runlist_update_p95_us",
                                  "boundary_idle_p50_ms",
                                  "decode_enqueue_share"])
def test_readers_give_nothing_without_spans(name):
    assert read(name, _parent_run()) is None


def test_boundaries_need_a_trace_to_place_spans_on():
    run = _boundary_run()
    run.trace_events = None
    assert read("boundary_idle_p50_ms", run) is None
    run = _boundary_run()
    run.slices = []
    assert ps.trace_offset_ns(run) is None
