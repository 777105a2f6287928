"""Benchmark driver (deliverable d): one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows; exit code 0 iff every
lossless check passed.  Rows whose derived field starts with ``SKIP``
(e.g. the service benchmarks on a read-only store root) count as
passed."""

import importlib
import sys
import time

# modules whose absence downgrades a benchmark to a SKIP row instead of
# failing the sweep (requirements-dev.txt; not baked into every container)
_OPTIONAL_DEPS = ("zstandard", "hypothesis")

MODULES = [
    ("table5_compression_ratio", "compression_ratio"),
    ("table6_space_savings", "space_savings"),
    ("table7_throughput", "throughput"),
    ("sec5.5_memory", "memory"),
    ("table2_3_robustness", "robustness"),
    ("sec5.7_scaling", "scaling"),
    ("sec3.6_entropy", "entropy_efficiency"),
    ("sec5.3_disk", "disk_sizes"),
    ("beyond_paper_baselines", "baselines"),
    ("store_batch_throughput", "batch_throughput"),
    ("service_throughput", "service_throughput"),
    ("gateway_throughput", "gateway_throughput"),
    ("dist_grad_compress", "grad_compress"),
    ("codec_throughput", "codec_throughput"),
    ("obs_overhead", "obs_overhead"),
]


def main() -> None:
    print("name,us_per_call,derived")
    failed = False
    for name, modname in MODULES:
        t0 = time.perf_counter()
        try:
            # import inside the loop so a benchmark that imports an
            # optional dependency at module level SKIPs instead of
            # killing the whole sweep before it starts
            rows = importlib.import_module(f"benchmarks.{modname}").run()
        except ImportError as e:
            if e.name in _OPTIONAL_DEPS:
                rows = [f"{name},0,SKIP:missing_dependency:{e.name}"]
            else:  # a real import regression stays fatal
                failed = True
                rows = [f"{name},0,ERROR:{type(e).__name__}:{e}"]
        except Exception as e:  # pragma: no cover
            failed = True
            rows = [f"{name},0,ERROR:{type(e).__name__}:{e}"]
        dt = time.perf_counter() - t0
        for row in rows:
            print(row)
            if "FAIL" in row or "ERROR" in row:
                failed = True
        print(f"{name}_wall,{1e6*dt:.0f},done")
    _dump_obs_snapshot()
    sys.exit(1 if failed else 0)


def _dump_obs_snapshot() -> None:
    """Attach the sweep's obs snapshot (every benchmark above ran with
    live instrumentation) so a perf regression comes with its per-stage
    codec timings and byte counters on the same commit."""
    import json
    from pathlib import Path

    from repro import obs

    snap = obs.snapshot()
    out = Path(__file__).resolve().parent / "BENCH_obs_snapshot.json"
    out.write_text(json.dumps(snap, indent=1, sort_keys=True) + "\n")
    print(f"obs_snapshot,0,{len(snap['counters'])}c_{len(snap['gauges'])}g_"
          f"{len(snap['histograms'])}h_{out.name}")


if __name__ == "__main__":
    main()
