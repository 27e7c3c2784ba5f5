"""Slice boundary (sched/executor.py): per RT release due in the window,
the summed ``sched.device_wait`` of its slices, the blocking by the
slice in flight when it asked for the device; the median in ms."""
import statistics

from bench import program_spans as ps


def read(run):
    rel = ps.releases(run)
    if rel is None:
        return None
    return statistics.median(
        sum(ps.dur(s) for s in r["spans"] if s.name == "sched.device_wait")
        for r in rel) / 1e6
