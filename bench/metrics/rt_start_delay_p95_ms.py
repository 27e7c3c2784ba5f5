"""Executor layer (sched/executor.py, sched/cluster.py): from each RT
release's due time to the start of its first device slice, the 95th
percentile in ms, over the releases due in the window that started."""
from bench import traffic


def read(run):
    d = [(r["start"] - r["due"]) * 1e3 for r in run.releases
         if "start" in r]
    return traffic.percentile(d, 95) if d else None
