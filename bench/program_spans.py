"""The program's own spans (``repro.sched.spans``) as the per-layer
readers see them: each job's ``JobStats.spans``, grouped by RT release,
and placed on the clock of a profiler trace.

A span is recorded on ``time.monotonic_ns()``; the trace's events are
relative to the trace's start.  The benchmark's ``bench.*`` spans are
known on both clocks: each is in the trace, and ``Run.slices`` holds the
monotonic time at which the benchmark opened it.  The median distance
between the two places every program span on the trace.  The device's
operations are stamped on a clock the profiler aligns with the host's
only to within milliseconds, so spans are set against them after the
skew that ``device_skew`` bounds.

A program that records no spans gives None here, and so does every
reader built on this module."""
from __future__ import annotations

import bisect
import statistics

import numpy as np

from bench import trace as tr

#: the benchmark's span around each kind of slice in ``Run.slices``
BENCH_SPAN = {"bench.rt_prefill": "prefill",
              "bench.rt_decode_slice": "decode",
              "bench.be_train_slice": "train"}


def dur(s) -> int:
    return s.end_ns - s.start_ns


def job_spans(run) -> dict | None:
    """Job name -> the spans it recorded, or None where no job did."""
    out = {n: list(getattr(st, "spans", ()))
           for n, st in run.job_stats.items()}
    return out if any(out.values()) else None


def releases(run) -> list | None:
    """One entry per RT release due in the window whose root span was
    recorded: the harness's record (task, k, due, start, ...) with the
    root span under ``root`` and every span below it under ``spans``."""
    js = job_spans(run)
    if js is None:
        return None
    roots, below = {}, {}
    for spans in js.values():
        for s in spans:
            if s.name == "sched.release":
                roots[(s.args["job"], s.args["k"])] = s
            else:
                below.setdefault(s.release, []).append(s)
    out = []
    for r in run.releases:
        root = roots.get((r["task"], r["k"]))
        if root is not None:
            out.append(dict(r, root=root,
                            spans=below.get(root.span_id, [])))
    return out or None


def trace_offset_ns(run) -> float | None:
    """What to add to a ``time.monotonic_ns()`` reading to place it on
    the trace: the median distance between each ``bench.*`` slice span of
    the trace and its entry in ``Run.slices``.  Both lists end with the
    window's last slice, so they are paired from the end; a pair whose
    kinds differ is left out, and with fewer than half of them paired
    this gives None."""
    if not run.trace_events or not run.slices:
        return None
    host = sorted((e for e in run.trace_events["host"]
                   if e[0] in BENCH_SPAN), key=lambda e: e[1])
    mine = sorted(run.slices, key=lambda x: x["start"])
    n = min(len(host), len(mine))
    pairs = [(h[1], m["start"]) for h, m in zip(host[len(host) - n:],
                                                mine[len(mine) - n:])
             if BENCH_SPAN[h[0]] == m["kind"]
             and h[3].get("task", m["task"]) == m["task"]]
    if not n or len(pairs) * 2 < n:
        return None
    return statistics.median(h - m * 1e9 for h, m in pairs)


#: a slice at least this long is one device program; its work lies
#: inside the slice's span, so the slice bounds the trace's skew
LONG_NS = 50e6
#: idle gaps shorter than this do not split the device's busy blocks:
#: a program's operations leave ns-long holes between them, while the
#: device idles for a millisecond or more between two slices
HOLE_NS = 50e3


def device_skew(slices: list, gaps: list, lo: float, hi: float) -> tuple:
    """(skew, margin) in ns: what to add to a host time on the trace to
    place it among the device's operations, and how far the true skew
    may lie from it either way (None where the bounds disagree).  The
    profiler aligns the device's clock with the host's only to within
    milliseconds: a v5e trace showed device work starting up to 0.8 ms
    before the host opened the slice that dispatched it.  A long slice's
    device work (the busy block that overlaps most of it) starts after
    the host opened the slice and ends before the host closed it, so
    ``block end - slice end <= skew <= block start - slice start``; the
    skew is the middle of the tightest of those bounds.  A block that
    holds only part of the slice's work gives looser bounds, never wrong
    ones.  With no long slice the skew is 0."""
    blocks, at = [], lo
    for a, b in gaps:
        if b - a < HOLE_NS:
            continue
        if a > at:
            blocks.append((at, a))
        at = b
    if hi > at:
        blocks.append((at, hi))
    starts = [a for a, _ in blocks]
    low, high = [], []
    for s0, s1, _ in slices:
        if s1 - s0 < LONG_NS:
            continue
        j = bisect.bisect_right(starts, s1)
        near = blocks[max(j - 3, 0):j]
        if not near:
            continue
        b0, b1 = max(near, key=lambda b: min(b[1], s1) - max(b[0], s0))
        if min(b1, s1) - max(b0, s0) >= (s1 - s0) / 2:
            low.append(b1 - s1)
            high.append(b0 - s0)
    if not low:
        return 0.0, None
    if max(low) <= min(high):
        return (max(low) + min(high)) / 2, (min(high) - max(low)) / 2
    return statistics.median((a + b) / 2 for a, b in zip(low, high)), None


def placement(run) -> dict | None:
    """The traced window on the device's clock: its bounds ``lo``,
    ``hi``, the device's idle ``gaps``, ``off`` (what to add to a
    ``time.monotonic_ns()`` reading to place it among the device's
    operations), the ``skew`` and ``margin`` of ``device_skew``, and
    the window's ``sched.slice`` spans (start, end, job), so placed."""
    off = trace_offset_ns(run)
    js = job_spans(run)
    if off is None or js is None:
        return None
    spans = [(e[1], e[1] + e[2]) for e in run.trace_events["host"]
             if e[0] == tr.WINDOW_SPAN]
    evs = [(s, s + d) for evs in run.trace_events["devices"].values()
           for _, s, d in evs]
    if not spans or not evs:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    starts = np.array([a for a, _ in evs], dtype=np.float64)
    ends = np.array([b for _, b in evs], dtype=np.float64)
    _, gaps = tr._gaps(starts, ends, lo, hi)
    slices = sorted((s.start_ns + off, s.end_ns + off, job)
                    for job, ss in js.items() for s in ss
                    if s.name == "sched.slice")
    slices = [s for s in slices if s[0] >= lo and s[1] <= hi]
    skew, margin = device_skew(slices, gaps, lo, hi)
    return {"lo": lo, "hi": hi, "gaps": gaps, "off": off + skew,
            "skew": skew, "margin": margin,
            "slices": [(a + skew, b + skew, j) for a, b, j in slices]}


def boundaries(run) -> list | None:
    """The slice boundaries of the traced window: between each two
    consecutive ``sched.slice`` spans (of any job) the device idles from
    the end of the first one's work to the start of the next one's.  The
    device's idle gaps that overlap the host's interval between the two
    spans, placed on the device's clock (``placement``), make up the
    boundary's idle time; a gap that overlaps two such intervals (around
    a slice with no device work, as a finalize is) joins them into one
    boundary.  Each boundary: ``idle_ns``, its ``gaps`` [(start, end)]
    and ``pairs`` [(slice before, slice after)], each slice (start, end,
    job) on the device's clock."""
    pl = placement(run)
    if pl is None or len(pl["slices"]) < 2:
        return None
    slices, gaps = pl["slices"], pl["gaps"]
    gap_starts = [a for a, _ in gaps]
    out: list = []
    last_gap = -1
    for a, b in zip(slices, slices[1:]):
        # gaps overlapping the host interval (a's end, b's start)
        j0 = max(bisect.bisect_right(gap_starts, a[1]) - 1, 0)
        idx = [j for j in range(j0, bisect.bisect_left(gap_starts, b[0]))
               if gaps[j][1] > a[1] and gaps[j][0] < b[0]]
        if out and idx and idx[0] <= last_gap:
            cur = out[-1]
            new = [j for j in idx if j > last_gap]
        else:
            cur = {"idle_ns": 0.0, "gaps": [], "pairs": []}
            out.append(cur)
            new = idx
        cur["pairs"].append((a, b))
        for j in new:
            cur["gaps"].append(gaps[j])
            cur["idle_ns"] += gaps[j][1] - gaps[j][0]
        if idx:
            last_gap = max(last_gap, idx[-1])
    return out


#: the program's spans between a release's slices
BETWEEN = ("sched.runlist_update", "sched.segment_build",
           "sched.policy_wait", "sched.device_wait")
#: what the host was doing in a boundary's idle time, in the order a
#: moment is named: the slice before still waiting for its device work
#: to be seen done, the slice after already dispatching, then a span of
#: ``BETWEEN`` of either job
SPLIT = ("sync tail", "dispatch") + BETWEEN + ("none",)


def boundary_split(run, bounds: list) -> dict:
    """The summed idle ns of ``bounds`` split by ``SPLIT``."""
    off = placement(run)["off"]
    by_job = {job: sorted((s.start_ns + off, s.end_ns + off, s.name)
                          for s in ss if s.name in SPLIT)
              for job, ss in (job_spans(run) or {}).items()}
    out = dict.fromkeys(SPLIT, 0.0)
    for bd in bounds:
        first, last = bd["pairs"][0][0], bd["pairs"][-1][1]
        order = [[(first[0], first[1])], [(last[0], last[1])]]
        for name in BETWEEN:
            order.append([(s, e) for job in (last[2], first[2])
                          for s, e, n in by_job.get(job, ()) if n == name])
        for a, b in bd["gaps"]:
            left = [(a, b)]
            for cat, covers in zip(SPLIT, order):
                left, took = _cut(left, covers)
                out[cat] += took
            out["none"] += sum(e - s for s, e in left)
    return out


def _cut(pieces: list, covers: list) -> tuple:
    """``pieces`` less ``covers``, and the ns that ``covers`` took."""
    took, rest = 0.0, []
    for s, e in pieces:
        cuts = sorted((max(s, c0), min(e, c1)) for c0, c1 in covers
                      if c1 > s and c0 < e)
        at = s
        for c0, c1 in cuts:
            if c0 > at:
                rest.append((at, c0))
            took += max(0.0, c1 - max(at, c0))
            at = max(at, c1)
        if at < e:
            rest.append((at, e))
    return rest, took


def start_delay_split(run) -> list | None:
    """Per RT release due in the window that started: the harness's
    due → first device slice delay (ms) beside the program's spans that
    account for it: the lag from due to ``sched.release``, and the spans
    of ``BETWEEN`` before the release's first ``sched.slice``."""
    rel = releases(run)
    if rel is None:
        return None
    out = []
    for r in rel:
        slices = [s for s in r["spans"] if s.name == "sched.slice"]
        if "start" not in r or not slices:
            continue
        first = min(s.start_ns for s in slices)
        row = {"task": r["task"], "k": r["k"],
               "delay_ms": (r["start"] - r["due"]) * 1e3,
               "lag_ms": r["root"].start_ns / 1e6 - r["due"] * 1e3}
        for name in BETWEEN:
            row[name] = sum(dur(s) for s in r["spans"]
                            if s.name == name and s.end_ns <= first) / 1e6
        row["residual_ms"] = row["delay_ms"] - row["lag_ms"] - sum(
            row[n] for n in BETWEEN)
        out.append(row)
    return out
