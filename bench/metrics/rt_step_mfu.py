"""Whole RT request: the least time the chip could take for every RT
slice run (for each prefill and each decode step, the larger of its
operations over peak bf16 and its bytes over HBM bandwidth) over the
time those slices took, in %."""
from bench import flops


def read(run):
    if not run.peak:
        return None
    tasks = {t["name"]: t for t in run.mix["rt"]}
    least = took = 0.0
    for x in run.slices:
        if x["kind"] == "prefill":
            t = tasks[x["task"]]
            least += flops.least_time_s(
                flops.prefill_flops(run.sizes, t["batch"], t["prompt"]),
                flops.prefill_bytes(run.sizes, t["batch"], t["prompt"]),
                run.peak)
        elif x["kind"] == "decode":
            b = tasks[x["task"]]["batch"]
            for j in range(x["tokens"]):
                ctx = x["pos0"] + j + 1
                least += flops.least_time_s(
                    flops.decode_step_flops(run.sizes, b, ctx),
                    flops.decode_step_bytes(run.sizes, b, ctx), run.peak)
        else:
            continue
        took += x["end"] - x["start"]
    return 100.0 * least / took if took else None
