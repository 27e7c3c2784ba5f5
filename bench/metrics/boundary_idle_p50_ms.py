"""Executor (sched/executor.py): the device's idle time at each slice
boundary of the traced window, from the end of one ``sched.slice``'s
device work to the start of the next one's (bench/program_spans.py,
``boundaries``); the median over the boundaries, in ms."""
import statistics

from bench import program_spans as ps


def read(run):
    b = ps.boundaries(run)
    return statistics.median(x["idle_ns"] for x in b) / 1e6 if b else None
