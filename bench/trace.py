"""Reduces a profiler trace to the device's busy time, its idle gaps and
the time of named kernels.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists; ``reduce`` works on those lists alone, so it runs on a
small recorded trace (``testdata/``) with no chip.  Busy time is the
union of the intervals in which an operation ran on a device, inside the
host span ``bench.window``; an idle gap is named by the benchmark's host
span (``bench.*``) that covers most of it, or "none"."""
from __future__ import annotations

import glob
import os
import re

import numpy as np

#: the device line whose events are single operations
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


def load(log_dir: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns, {arg: value}], ...]} from the newest
    trace under ``log_dir``.  Host events are the benchmark's own spans
    only, with the arguments they were opened with.  Each
    distinct operation name is one string object, however many events
    carry it."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"devices": {}, "host": []}
    names: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        n = e.name
                        evs.append([names.setdefault(n, n), e.start_ns,
                                    e.duration_ns])
            if evs:
                out["devices"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns,
                                    dict(e.stats)]
                                   for e in line.events
                                   if e.name.startswith("bench."))
    return out


def short_name(op: str) -> str:
    """An operation's instruction name, and the custom call it makes:
    ``%fusion.12`` or ``%closed_call.8 tpu_custom_call``."""
    head = op.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', op)
    return f"{head} {m.group(1)}" if m else head


def _window(trace: dict) -> tuple:
    spans = [(e[1], e[1] + e[2]) for e in trace["host"]
             if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _gaps(starts, ends, lo, hi):
    """Busy ns and the idle gaps [(start, end)] of the union of
    [starts, ends) clipped to [lo, hi)."""
    keep = (ends > lo) & (starts < hi)
    s = np.clip(starts[keep], lo, hi)
    e = np.clip(ends[keep], lo, hi)
    if s.size == 0:
        return 0.0, [(lo, hi)]
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    gaps = []
    if s[0] > lo:
        gaps.append((lo, s[0]))
    idx = np.nonzero(s[1:] > reach[:-1])[0]
    gaps.extend(zip(reach[idx].tolist(), s[idx + 1].tolist()))
    if reach[-1] < hi:
        gaps.append((float(reach[-1]), hi))
    idle = sum(b - a for a, b in gaps)
    return (hi - lo) - idle, gaps


def _cover(gap, spans) -> str:
    """The host span that covers most of ``gap``, or "none" where no
    span covers half of it."""
    a, b = gap
    best, name = (b - a) / 2, "none"
    for n, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (averaged over devices), window_s, idle_share, the ``top``
    device operations by summed time, the ``top`` longest idle gaps by
    covering host span, and every operation's summed seconds and calls
    inside the window (``op_time``, ``op_calls``)."""
    lo, hi = _window(trace)
    spans = sorted((e[0], e[1], e[1] + e[2]) for e in trace["host"]
                   if e[0] != WINDOW_SPAN)
    busy_all, gaps_all, op_time, op_calls = [], [], {}, {}
    for evs in trace["devices"].values():
        names = [e[0] for e in evs]
        starts = np.array([e[1] for e in evs], dtype=np.float64)
        ends = starts + np.array([e[2] for e in evs], dtype=np.float64)
        busy, gaps = _gaps(starts, ends, lo, hi)
        busy_all.append(busy)
        gaps_all.extend(gaps)
        # each operation's time inside the window
        inside = np.minimum(ends, hi) - np.maximum(starts, lo)
        for n, dur in zip(names, inside.tolist()):
            if dur > 0:
                op_time[n] = op_time.get(n, 0.0) + dur / 1e9
                op_calls[n] = op_calls.get(n, 0) + 1
    window_s = (hi - lo) / 1e9
    busy_s = (sum(busy_all) / len(busy_all) / 1e9) if busy_all else 0.0
    longest = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_s, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": [[short_name(n), t] for n, t in
                       sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[_cover(g, spans), (g[1] - g[0]) / 1e9]
                      for g in longest],
        "op_time": op_time, "op_calls": op_calls,
    }
