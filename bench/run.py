#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object (correct,
attempted, failed, metrics, device, and with --trace 1 breakdown); the
numbers that decide ``correct`` follow it under ``checks`` and are the
last lines of standard error.  It exits non-zero, printing no result,
on a backend other than TPU, with REPRO_PALLAS set, on a device kind
with no peaks in bench/peaks.json, or with fewer chips than the cell
asks for.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --sweep

finds the knee of the cell's mix once (see bench/harness.py:sweep).
``--controls`` also puts each control's readings (the fp8 reference, a
step that drops half its batch) in the program's place and judges them
against the same limits; the result gains ``controls``, each with its
own ``correct``, which has to be false.  The benchmark's own runs never
take it.
"""
import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args()
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"bench: no program under {CHECKOUT / 'src'}; nothing was "
              f"run", file=sys.stderr)
        return 2
    sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]
    from bench import harness
    try:
        if args.sweep:
            out = harness.sweep(args.workload, args.seed, args.seconds,
                                checkout=CHECKOUT)
            print(json.dumps({"sweep": out}))
            return 0
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), checkout=CHECKOUT,
                          controls=args.controls, started=STARTED)
    except harness.Refused as e:
        print(e.code, file=sys.stderr)
        return 3
    sys.stdout.flush()
    for tag, c in out.get("controls", {}).items():
        print(f"control {tag} correct {c['correct']} over {c['over']}",
              file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
