"""Slice boundary (core/segments.py): the BE job's longest slice in ms,
from the program's own JobStats.slice_times, the blocking that
preemption at slice boundaries leaves an RT release."""


def read(run):
    t = run.job_stats.get("be_train")
    return max(t.slice_times) * 1e3 if t and t.slice_times else None
