"""Runs a cell as ``run.py`` does, with a planted fault or changed traffic.

    python3 bench/probe.py --workload <cell> --seed N --seconds S
                           [--fault pack16] [--set traffic.batch=8]

For the control (``--fault pack16``) and the planted faults (each a file
in ``bench/faults/``), which have to come out not correct, and for rehearsals
off the chip at a tiny size (``--no-chip``, ``--set``).  The benchmark's
own runs never use it.
Prints the result line as ``run.py`` does, and on standard error the
request counts and latencies of the window.
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


def overrides(pairs) -> dict:
    out: dict = {}
    for pair in pairs:
        key, value = pair.split("=", 1)
        part, name = key.split(".", 1)
        out.setdefault(part, {})[name] = json.loads(value)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--set", action="append", default=[],
                    metavar="config.KEY=JSON|traffic.KEY=JSON")
    ap.add_argument("--dump", metavar="PATH",
                    help="write the run's request records there as JSON")
    ap.add_argument("--no-chip", action="store_true",
                    help="skip the look for a chip (a CPU rehearsal: the "
                         "check runs, no metric is printed)")
    args = ap.parse_args(argv)

    import device
    import harness
    import spec

    keep: dict = {}
    try:
        result = harness.run(spec.cell(args.workload), args.seed,
                             args.seconds, bool(args.trace), T_PROCESS,
                             require_chip=not args.no_chip,
                             faults=args.fault,
                             overrides=overrides(args.set), keep=keep)
    except device.NoAccelerator as e:
        print(f"[probe] {e}", file=sys.stderr)
        return 3
    if args.dump:
        Path(args.dump).parent.mkdir(parents=True, exist_ok=True)
        Path(args.dump).write_text(json.dumps(keep))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
