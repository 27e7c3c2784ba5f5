#!/usr/bin/env python3
"""One run of a cell, read through the program's spans.

  python3 bench/span_report.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1> [--out <file>]

Runs the cell as ``bench/run.py`` does and applies every per-layer
reader of the cell to the run, traced or not (a reader that needs the
trace gives nothing untraced).  A traced run adds:

  * ``clock``: the program's ``sched.slice`` spans, placed on the trace
    by ``bench/program_spans.py``, against the profiler's annotations of
    the same spans (median distance of starts and ends, in µs), and the
    trace's start on the realtime clock against the process's realtime
    less monotonic offset;
  * ``boundaries``: the device's idle time at slice boundaries, its
    share of the traced window's idle time, its split by what the host
    was in (``program_spans.SPLIT``) and by the kinds of job on either
    side, and the device clock's skew against the host's
    (``program_spans.device_skew``);
  * ``start_delay``: per RT release due in the window, the harness's
    due → first slice delay beside the spans that account for it.

The last line of standard output is the report; ``--out`` also writes
it to a file.  Needs the chip, as ``bench/run.py`` does."""
import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def clock_check(run, trace_dir: str) -> dict | None:
    """The profiler's ``sched.slice`` annotations against the recorded
    spans placed on the trace."""
    from jax.profiler import ProfileData

    from bench import program_spans as ps
    off = ps.trace_offset_ns(run)
    js = ps.job_spans(run)
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if off is None or js is None or not paths:
        return None
    prof, base = [], None
    for plane in ProfileData.from_file(max(paths,
                                           key=os.path.getmtime)).planes:
        if plane.name == "Task Environment":
            base = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                prof.extend((e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name == "sched.slice")
    mine = sorted((s.start_ns + off, s.end_ns + off) for ss in js.values()
                  for s in ss if s.name == "sched.slice")
    starts = [a for a, _ in mine]
    d_start, d_end = [], []
    for a, b in prof:
        j = bisect.bisect_left(starts, a)
        near = min((k for k in (j - 1, j) if 0 <= k < len(mine)),
                   key=lambda k: abs(mine[k][0] - a))
        d_start.append((mine[near][0] - a) / 1e3)
        d_end.append((mine[near][1] - b) / 1e3)
    out = {"offset_ns": off, "annotations": len(prof),
           "recorded": len(mine)}
    if prof:
        out.update(start_us_p50=statistics.median(d_start),
                   end_us_p50=statistics.median(d_end),
                   abs_us_p50=statistics.median(
                       [abs(x) for x in d_start + d_end]))
    if base is not None:
        # a trace time plus the trace's realtime start is realtime; a
        # monotonic reading plus ``off`` is trace time
        out["realtime_us"] = (base + off - (time.time_ns()
                                            - time.monotonic_ns())) / 1e3
    return out


def by_pair(bounds: list) -> dict:
    """The boundaries by the kinds of job on either side ("be" for the
    best-effort job, "rt" for the others): how many, how many with no
    idle time, the median and summed idle ms, and the median time from
    the end of the slice before to the start of the slice after on the
    host."""
    groups: dict = {}
    for b in bounds:
        a, z = b["pairs"][0][0], b["pairs"][-1][1]
        key = ">".join("be" if j[2] == "be_train" else "rt" for j in (a, z))
        groups.setdefault(key, []).append((b["idle_ns"] / 1e6,
                                           (z[0] - a[1]) / 1e6))
    return {k: {"n": len(v), "zero": sum(1 for x, _ in v if x == 0),
                "p50_ms": statistics.median(x for x, _ in v),
                "sum_ms": sum(x for x, _ in v),
                "host_gap_p50_ms": statistics.median(g for _, g in v)}
            for k, v in sorted(groups.items())}


def report(cell: str, seed: int, seconds: float, trace: bool, *,
           checkout: Path = CHECKOUT, need_tpu: bool = True,
           started: float = STARTED) -> dict:
    from bench import catalog, harness
    from bench import program_spans as ps
    kept, info = [], []

    class Kept(harness.Run):
        """The run's ``Run``, kept for the readers applied here."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept.append(self)

    real = harness.Run
    harness.Run = Kept
    try:
        out = harness.run(cell, seed, seconds, trace, checkout=checkout,
                          need_tpu=need_tpu, started=started,
                          log=lambda line: info.append(json.loads(line)))
    finally:
        harness.Run = real
    run = kept[-1]
    rep = {k: out[k] for k in ("correct", "attempted", "failed", "device")}
    rep["metrics"] = out["metrics"]
    rep["e2e"] = info[-1]["info"]["e2e"]
    rep["info"] = {k: info[-1]["info"][k] for k in (
        "epsilon_ms", "refused", "profiled_ms", "profiled_be_ms",
        "releases_met", "timing", "in_window", "longest_slices")}
    rep["readers"] = {
        m["name"]: catalog.metric_reader(m["name"],
                                         checkout / "bench")(run)
        for m in catalog.metrics_of(cell, "per_layer", checkout)}
    if not trace:
        return rep
    rep["clock"] = clock_check(run, str(checkout / ".bench_cache" /
                                        "trace"))
    bounds = ps.boundaries(run)
    pl = ps.placement(run)
    if bounds and run.trace:
        idle_s = run.trace["window_s"] - run.trace["busy_s"]
        total = sum(b["idle_ns"] for b in bounds) / 1e9
        rep["boundaries"] = {
            "n": len(bounds), "idle_s": total, "window_idle_s": idle_s,
            "share_of_idle": total / idle_s if idle_s else None,
            "split_s": {k: v / 1e9 for k, v in
                        ps.boundary_split(run, bounds).items()},
            "by_pair": by_pair(bounds),
            "skew_us": pl["skew"] / 1e3,
            "skew_margin_us": (pl["margin"] / 1e3
                               if pl["margin"] is not None else None),
            "device_planes": {k: len(v) for k, v in
                              run.trace_events["devices"].items()}}
    rows = ps.start_delay_split(run)
    if rows:
        cover = [1 - r["residual_ms"] / r["delay_ms"] for r in rows
                 if r["delay_ms"] > 0]
        delay = sum(r["delay_ms"] for r in rows)
        rep["start_delay"] = {
            "rows": rows, "covered_min": min(cover) if cover else None,
            "covered_all": (1 - sum(r["residual_ms"] for r in rows) / delay
                            if delay > 0 else None)}
    return rep


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]
    from bench import harness
    try:
        rep = report(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except harness.Refused as e:
        print(e.code, file=sys.stderr)
        return 3
    line = json.dumps(rep)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
