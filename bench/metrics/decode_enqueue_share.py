"""Serving engine (launch/serve.py): over the RT releases due in the
window, the host's enqueue of each decode slice's programs
(``serve.decode_enqueue``) over the ``sched.slice`` it ran in, which
ends when the device is done, in %.  Near 100 the host sets the pace."""
from bench import program_spans as ps


def read(run):
    rel = ps.releases(run)
    enq = took = 0
    for r in rel or ():
        slices = {s.span_id: s for s in r["spans"] if s.name == "sched.slice"}
        for s in r["spans"]:
            if s.name == "serve.decode_enqueue" and s.parent_id in slices:
                enq += ps.dur(s)
                took += ps.dur(slices[s.parent_id])
    return 100.0 * enq / took if took else None
