"""Checks ``trace_reduce.py`` on the chip trace committed in ``testdata/``.

    python bench/selfcheck.py        # exits non-zero on the first failure

``record_trace.py`` made the trace on a TPU v5e: inside the window span,
three token-pack batch calls of known id counts, each after a 0.2 s host
sleep, and a last sleep before the window closed.  The reduction has to
find the window, a device busy time that is a union of intervals and no
longer than the window, one kernel event per launch, a roofline share no
higher than 100 %, and the sleeps among the longest idle gaps.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402
import trace_reduce  # noqa: E402

DATA = BENCH / "testdata"


def check(ok: bool, what: str) -> None:
    print(("ok    " if ok else "FAIL  ") + what)
    if not ok:
        sys.exit(1)


def main() -> int:
    u = trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 6.5), (3, 4), (9, 9)])
    check(u == [(0, 4), (5, 7), (9, 9)], f"union of intervals: {u}")

    rec = json.loads((DATA / "pack_trace.json").read_text())
    calls, sleep = rec["calls"], rec["sleep_s"]
    work = spec.load_module(BENCH / "work" / "token_pack.py")
    red = trace_reduce.reduce(DATA / "pack_trace.xplane.pb",
                              {"token_pack": work.OP_NAME})
    print(json.dumps(red, indent=1))
    sleeps = sleep * (len(calls) + 1)
    host = sum(c["host_s"] for c in calls)
    check(sleeps <= red["window_s"] <= sleeps + host + 0.15,
          f"window {red['window_s']} s: the sleeps ({sleeps} s, each "
          f"overshooting by up to tens of ms) and calls ({host} s)")
    check(0 < red["busy_s"] <= min(red["window_s"], host),
          f"device busy {red['busy_s']} s within the calls' host time")
    check(red["kernel_n"]["token_pack"] == len(calls),
          f"{red['kernel_n']['token_pack']} kernel events, one per launch")
    t = red["kernel_s"]["token_pack"]
    check(0 < t <= red["busy_s"], f"kernel time {t} s within busy time")
    peaks = spec.load_json(BENCH / "peaks.json")["devices"][
        rec["device"]["kind"]]
    _, nbytes = work.work([[[sum(c["ids"]), c["width"]]] for c in calls])
    share = nbytes / peaks["hbm_bytes_per_s"] / t * 100
    check(0 < share <= 100, f"roofline share {share} %")
    ops = [s for _, s in red["device_ops"]]
    check(ops == sorted(ops, reverse=True)
          and sum(ops) <= red["busy_s"] * (1 + 1e-9),
          "device ops sorted, summing to no more than busy time")
    gaps = [s for _, s in red["idle_gaps"]]
    check(len(gaps) >= len(calls) + 1
          and min(gaps[: len(calls) + 1]) >= 0.9 * sleep,
          f"the {len(calls) + 1} sleeps are the longest idle gaps: {gaps}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
