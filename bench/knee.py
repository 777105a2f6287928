"""Finds the highest rate an open-loop cell sustains, by a sweep on the chip.

    python3 bench/knee.py --workload hybrid-lzr.ycsb-b --seed N --seconds 20
                          --rates 20,40,60,80,100 [--op get_tokens]
                          [--out chiprun_out/knee.json] [-- PROBE ARGS]

Runs the cell once per rate, lowest first, each run in a process of its
own (``probe.py --set traffic.rate_per_s=R``; this process never imports
JAX, so each run holds the chip alone), and reads the run's requests.
A rate is sustained when

* no request of the window was refused at admission,
* the p95 latency of the window's ``op`` requests is within 3x that at
  the lowest rate swept, and
* the p95 of the window's second half is within 1.25x that of its first
  half: no backlog grows.

The knee is the highest rate at which it and every lower rate are
sustained.  A cell's traffic file fixes its rate at four fifths of the
knee; the benchmark's own runs never search for one.  Arguments after
``--`` go to every run (``-- --no-chip --set traffic.records=48`` for a
rehearsal off the chip).
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH)]

import harness  # noqa: E402
import stats  # noqa: E402

P95_OVER_LOWEST = 3.0
SECOND_OVER_FIRST_HALF = 1.25


def reading(dump: dict, op: str) -> dict:
    """One run's numbers: its window's rejects and ``op`` p95, whole and
    by half."""
    t0, t1 = dump["t0"], dump["t1"]
    ctx = SimpleNamespace(t1=t1, grace_s=harness.GRACE_S)
    window = [r for r in dump["records"] if t0 <= r["due"] < t1]
    recs = [r for r in window if r["op"] == op]
    half = (t0 + t1) / 2

    def p95(part):
        return stats.nearest_rank([stats.latency_ms(ctx, r) for r in part],
                                  0.95)

    return {"n": len(recs),
            "rejects": sum(r["status"] == "admission_reject" for r in window),
            "not_ok": sum(r["status"] != "ok" for r in window),
            "p50_ms": stats.nearest_rank(
                [stats.latency_ms(ctx, r) for r in recs], 0.5),
            "p95_ms": p95(recs),
            "p95_first_half_ms": p95([r for r in recs if r["due"] < half]),
            "p95_second_half_ms": p95([r for r in recs if r["due"] >= half])}


def sustained(r: dict, lowest_p95: float) -> bool:
    return (r["rejects"] == 0 and r["not_ok"] == 0
            and r["p95_ms"] <= P95_OVER_LOWEST * lowest_p95
            and r["p95_second_half_ms"]
            <= SECOND_OVER_FIRST_HALF * r["p95_first_half_ms"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--op", default="get_tokens")
    ap.add_argument("--out", type=Path)
    ap.add_argument("probe_args", nargs="*")
    args = ap.parse_args(argv)
    rates = sorted(float(r) for r in args.rates.split(","))
    dump_path = harness.WORK / "knee-dump.json"
    rows = []
    for rate in rates:
        out = subprocess.run(
            [sys.executable, str(BENCH / "probe.py"), "--workload",
             args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--set", f"traffic.rate_per_s={rate}",
             "--dump", str(dump_path), *args.probe_args],
            cwd=str(BENCH.parent), stdout=subprocess.PIPE, text=True)
        if out.returncode != 0:         # a run that fails sustains nothing
            row = {"rate": rate, "exit": out.returncode}
        else:
            result = json.loads(out.stdout.strip().splitlines()[-1])
            row = dict(reading(json.loads(dump_path.read_text()), args.op),
                       rate=rate, correct=result["correct"],
                       setup_s=result["metrics"].get("setup_s", {})
                       .get("value"))
        rows.append(row)
        print("[knee] " + json.dumps(row), flush=True)
    knee = None
    lowest = rows[0].get("p95_ms")
    for row in rows:
        row["sustained"] = ("exit" not in row and row["correct"]
                            and lowest is not None
                            and sustained(row, lowest))
    for row in rows:
        if not row["sustained"]:
            break
        knee = row["rate"]
    doc = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "op": args.op, "rows": rows,
           "knee": knee, "cell_rate": None if knee is None else 0.8 * knee}
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(doc, indent=1))
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
