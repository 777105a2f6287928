#!/usr/bin/env python
"""Gateway smoke: spawn a real gateway subprocess, drive it with
concurrent clients, and assert the service tier actually measured
itself — nonzero request-latency percentiles in the obs snapshot, a
graceful SIGTERM drain (exit 0), and an atomically published
``--stats-json`` that parses.

Run by scripts/check.sh (and ``make gateway-smoke``); needs only the
stdlib + the repo.  This parent never asks JAX for a device; the one
child is a writer, which names its platform (``auto``: the chip when one
is attached) — see ``repro.launch.gateway`` on one process per chip.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")
sys.path.insert(0, SRC)

from repro.service.gateway import GatewayClient  # noqa: E402

N_CLIENTS = 3
N_BATCHES = 4


def main() -> int:
    tmp = Path(tempfile.mkdtemp(prefix="gateway-smoke-"))
    port_file = tmp / "port.json"
    stats_json = tmp / "stats.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.gateway",
         "--store-dir", str(tmp / "store"), "--role", "writer",
         "--platform", "auto", "--build-corpus", "12",
         "--port", "0", "--port-file", str(port_file),
         "--stats-json", str(stats_json), "--flush-batch", "8"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        t0 = time.monotonic()
        while not port_file.exists():
            if proc.poll() is not None:
                print(proc.stdout.read())
                print("gateway smoke: FAIL (gateway died at startup)")
                return 1
            if time.monotonic() - t0 > 30:
                print("gateway smoke: FAIL (gateway not ready in 30s)")
                return 1
            time.sleep(0.05)
        info = json.loads(port_file.read_text())
        errors: list = []

        def client(ci: int) -> None:
            try:
                with GatewayClient(info["host"], info["port"]) as c:
                    for bi in range(N_BATCHES):
                        texts = [f"smoke c{ci} b{bi} r{r}: drain the "
                                 "queue, verify the quorum. " * 6
                                 for r in range(3)]
                        keys = c.put_async(texts, wait=True)["keys"]
                        got = c.get_many(keys)
                        if got != texts:
                            raise AssertionError(
                                f"lossless violation on client {ci}")
                        c.get_tokens(keys[0])
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        if errors:
            print(f"gateway smoke: FAIL (client errors: {errors})")
            return 1

        with GatewayClient(info["host"], info["port"]) as c:
            snap = c.stats(snapshot=True)["obs"]
        lat = {k: v for k, v in snap["histograms"].items()
               if k.startswith("gateway.request.s")}
        live = {k: v for k, v in lat.items() if v["count"] > 0}
        if not live or not all(v["p50"] > 0 and v["p99"] > 0
                               for v in live.values()):
            print(f"gateway smoke: FAIL (no nonzero request-latency "
                  f"percentiles: {lat})")
            return 1

        proc.send_signal(signal.SIGTERM)
        code = proc.wait(timeout=30)
        if code != 0:
            print(proc.stdout.read())
            print(f"gateway smoke: FAIL (drain exit code {code})")
            return 1
        final = json.loads(stats_json.read_text())  # atomic publish parses
        ops = ", ".join(
            f"{k.split('op=')[1].rstrip('}')} p50 {v['p50']*1e3:.2f}ms "
            f"p99 {v['p99']*1e3:.2f}ms" for k, v in sorted(live.items()))
        print(f"gateway smoke: {N_CLIENTS} clients x {N_BATCHES} batches, "
              f"{ops}; drain exit 0, stats-json "
              f"({len(final['histograms'])} histograms) parses")
        return 0
    finally:
        if proc.poll() is None:
            proc.kill()


if __name__ == "__main__":
    sys.exit(main())
