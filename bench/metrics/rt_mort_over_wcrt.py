"""Admission (sched/admission.py): the largest MORT of an RT task over
the WCRT admission analysed for it, the paper's guarantee, on the host
clock over the whole window."""


def read(run):
    return run.timing.get("mort_over_wcrt")
