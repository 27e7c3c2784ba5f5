"""Executor (sched/executor.py): every ``sched.runlist_update`` the jobs
recorded (the measured part of ε, as ``DeviceExecutor.update_times``
reads it), the 95th percentile in µs."""
from bench import program_spans as ps
from bench import traffic


def read(run):
    js = ps.job_spans(run)
    us = [ps.dur(s) / 1e3 for ss in (js or {}).values() for s in ss
          if s.name == "sched.runlist_update"]
    return traffic.percentile(us, 95) if us else None
