# Test tiers (markers registered in pytest.ini; see ARCHITECTURE.md):
#   make analyze     static invariant checker (repro.analysis): lock order,
#                    durability, frozen wire formats, kernel hygiene, env
#                    registry, pool re-entrancy.  Waive a false positive with
#                    `# repro-analysis: disable=REPRO00N <reason>` inline;
#                    re-pin a frozen-format hash (only together with its
#                    golden test) via `python -m repro.analysis --repin-frozen`.
#   make quick       analyze + not-slow tests + golden frame-layout pins
#                    (scripts/check.sh)
#   make crash       crash-injection suite alone (fault points in fsync/replace)
#   make test        full tier-1 (slow + concurrency included)
#   make bench       the full benchmark sweep (writes BENCH_*.json)
#   make bench-codec the codec hot-path sweep alone (BENCH_codec_throughput.json)
#   make bench-kernels the device-kernel parity gate + sweeps, on a chip
#                    only (BENCH_kernel_codec.json; raises on a CPU host)
#   make obs-smoke   REPRO_OBS=0 codec overhead guard (scripts/obs_smoke.py)
#   make gateway-smoke spawn a gateway subprocess, drive concurrent socket
#                    clients, assert latency percentiles + SIGTERM drain
#   make chaos       seeded chaos harness x5 seeds: live writer/standby/replica
#                    fleet under fault injection + SIGKILL takeover; asserts
#                    zero acked-write loss, quarantine + degraded reads, and
#                    fault/retry counters in the obs snapshot (scripts/chaos.py)
PY := PYTHONPATH=src python

.PHONY: analyze quick crash test bench bench-codec bench-kernels obs-smoke \
	gateway-smoke chaos

analyze:
	$(PY) -m repro.analysis src --baseline analysis-baseline.json

quick:
	bash scripts/check.sh

crash:
	$(PY) -m pytest -q -m crash

test:
	$(PY) -m pytest -x -q

bench:
	PYTHONPATH=src:. python benchmarks/run.py

bench-codec:
	PYTHONPATH=src:. python benchmarks/codec_throughput.py

bench-kernels:
	PYTHONPATH=src:. python benchmarks/kernel_throughput.py

obs-smoke:
	$(PY) scripts/obs_smoke.py

gateway-smoke:
	$(PY) scripts/gateway_smoke.py

chaos:
	for s in 0 1 2 3 4; do $(PY) scripts/chaos.py --seed $$s || exit 1; done
