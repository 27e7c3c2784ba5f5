"""Kernels (kernels/decode_attention.py): the flash-decode kernel's least
time, from its shapes (each row's K and V up to its length), over the
summed device time of its calls, in %.  Read from the trace: every
decode slice whose span lies in it, with the flash-decode calls that ran
inside the span, one per attention layer and token (the count the
configuration's family gives); a slice whose call count differs from
that is left out, and with none left it gives nothing."""
import bisect
import re

from bench import flops

#: the kernel in the trace: a Pallas call (``tpu_custom_call``) whose
#: first operand is the scalar-prefetched int32 length vector, which
#: among the served programs only flash decode has
OP = re.compile(r'custom-call\(s32\[\d+\].*tpu_custom_call')


def read(run):
    if not run.peak or not run.trace_events:
        return None
    match: dict = {}
    calls = sorted((s, s + d) for evs in run.trace_events["devices"].values()
                   for n, s, d in evs
                   if match.setdefault(n, bool(OP.search(n))))
    starts = [c[0] for c in calls]
    tasks = {t["name"]: t for t in run.mix["rt"]}
    layers = flops.decode_attention_layers(run.sizes)
    least = took = 0.0
    for name, s, d, args in run.trace_events["host"]:
        if name != "bench.rt_decode_slice":
            continue
        inside = calls[bisect.bisect_left(starts, s):
                       bisect.bisect_left(starts, s + d)]
        if len(inside) != args["tokens"] * layers:
            continue
        b = tasks[args["task"]]["batch"]
        for j in range(args["tokens"]):
            ops, byts = flops.flash_decode_call(run.sizes, b,
                                                args["pos0"] + j + 1)
            least += layers * flops.least_time_s(ops, byts, run.peak)
        took += sum(e - a for a, e in inside) / 1e9
    return 100.0 * least / took if took else None
