"""One load-generator process: makes its share of a mix and drives it.

    python bench/loadgen.py --traffic <mix> --config <config> --seed N
                            --client C --seconds S

Started by the harness, never imports JAX or the program.  It speaks
JSON lines: on standard output ``{"ready": true}`` once its requests are
made; on standard input it waits for ``{"go": {...}}`` (port and times),
drives the mix, and answers ``{"result": {...}}`` with one record per
request.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "traffic")]

import spec  # noqa: E402


def _say(doc: dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--client", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    params = json.loads(args.traffic)
    config = json.loads(args.config)
    kind = spec.kind_module(params["kind"])
    state = kind.prepare(params, config, args.seed, args.client, args.seconds)
    _say({"ready": True})
    line = sys.stdin.readline()
    if not line:
        return 1                    # the harness went away
    go = json.loads(line)["go"]
    gc.freeze()          # no collector pause may make a request late
    gc.disable()
    _say({"result": asyncio.run(kind.drive(state, go))})
    return 0


if __name__ == "__main__":
    sys.exit(main())
