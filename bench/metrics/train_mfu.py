"""Train step (launch/steps.py): the model operations of the BE steps
run (6·N·tokens plus the attention term, of the BE job's configuration)
over their slices' time, as a share of the chip's peak bf16 rate, in %."""
from bench import flops


def read(run):
    if not run.peak:
        return None
    be = run.mix["be"]
    s = [x for x in run.slices if x["kind"] == "train"]
    took = sum(x["end"] - x["start"] for x in s)
    if not took:
        return None
    ops = len(s) * flops.train_step_flops(run.be_sizes, be["batch"], be["seq"])
    return 100.0 * ops / took / run.peak["bf16_flops_per_s"]
