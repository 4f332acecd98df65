#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window, with a
``breakdown`` of the device's top operations and longest idle gaps. The last
line of standard output is the result as one JSON object; the numbers the
correctness check compared, each beside its limit, are the last lines of
standard error. A run that finds no TPU, or fewer chips than the cell asks
for, exits 1 and prints no result.

``--control`` serves in the precision below the traffic's, the control that
the correctness limits were set against; ``--rehearse`` runs the
configuration's tiny size on any backend and prints no result. Neither is
part of a measured run.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    try:
        from bench import harness

        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), started=STARTED,
                          rehearse=args.rehearse, control=args.control)
    except Exception as e:  # report, and print no result line
        traceback.print_exc()
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if args.rehearse:
        print(f"bench: rehearsal, correct={out['correct']}; no result is "
              f"reported off the chip", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
