"""Records the small chip trace that ``selfcheck.py`` checks the reduction on.

    python3 bench/record_trace.py        # on a TPU; writes bench/testdata/

Inside a ``bench.window`` host span, the program's batch token-pack path
(``pack_fixed_batch_device``) packs a few batches of known id counts,
each compiled beforehand, with host sleeps between them so the window
has idle gaps of known length.  The trace and what was launched
(``pack_trace.json``: id counts, widths, host-clock times) go to
``bench/testdata/``, the checkout's path blanked out of the trace.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

OUT = BENCH / "testdata"
BATCHES = [[300_000], [200_000, 150_000], [100_000]]   # ids per stream
SLEEP_S = 0.2


def main() -> int:
    import jax
    import numpy as np

    import device
    from repro.kernels.token_pack import pack_fixed_batch_device

    dev = device.require_tpu(1)
    rng = np.random.default_rng(0)
    batches = [[rng.integers(0, 8192, n).astype(np.uint32) for n in b]
               for b in BATCHES]
    for b in batches:                      # compile every shape first
        pack_fixed_batch_device(b)
    tmp = BENCH / ".work" / "record_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    calls = []
    with jax.profiler.TraceAnnotation("bench.window"):
        for b in batches:
            time.sleep(SLEEP_S)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("kernel.token_pack"):
                pack_fixed_batch_device(b)
            calls.append({"ids": [int(a.size) for a in b], "width": 2,
                          "host_s": time.perf_counter() - t})
        time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    found = sorted(tmp.rglob("*.xplane.pb"))
    OUT.mkdir(exist_ok=True)
    # the trace's source locations name this checkout; blank its path
    # with a string of the same length, so the protobuf stays valid
    here = (str(BENCH.parent) + "/").encode()
    blank = b"/" + b"x" * (len(here) - 2) + b"/"
    (OUT / "pack_trace.xplane.pb").write_bytes(
        found[-1].read_bytes().replace(here, blank))
    (OUT / "pack_trace.json").write_text(json.dumps(
        {"device": dev, "calls": calls, "sleep_s": SLEEP_S}, indent=1))
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"device": dev, "calls": calls,
                      "bytes": (OUT / "pack_trace.xplane.pb").stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
