"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell is
looked up in ``BENCHMARK.json``.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
and a breakdown of the profiler trace.  Each number the correctness
check compared is printed, with its limit, as the last lines of
standard error and under ``checks``, the last key of the result.
Without a TPU, or without the program beside the benchmark, the command
prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "traffic"),
                str(BENCH.parent / "src")]

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (BENCH.parent / "src" / "repro").is_dir():
        print("[bench] the program (src/repro) is not beside the benchmark",
              file=sys.stderr)
        return EXIT_NO_PROGRAM

    import device
    import harness
    import spec

    cell = spec.cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_PROCESS)
    except device.NoAccelerator as e:
        print(f"[bench] {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for name, c in result["checks"].items():
        print(f"[bench] check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
