"""Serving engine (launch/serve.py): RT decode slices' time over the
tokens they emitted, in ms per token (a step serves a whole batch)."""


def read(run):
    s = [x for x in run.slices if x["kind"] == "decode"]
    n = sum(x["tokens"] for x in s)
    return sum(x["end"] - x["start"] for x in s) * 1e3 / n if n else None
