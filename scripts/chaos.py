#!/usr/bin/env python
"""Chaos harness: a seeded fault schedule against a live gateway fleet.

Spawns a writer + a lease-waiting standby + a read replica (real
subprocesses via ``repro.launch.gateway``), drives them with concurrent
retrying clients while faults are armed at every layer, and asserts the
fault-tolerance contract end to end:

* **zero acked-write loss** — every put acknowledged ``durable: true``
  survives an injected fsync error, injected socket faults, and a
  SIGKILL of the writer mid-workload, and reads back byte-identical
  from the standby that takes over the lease — and from the replica
  after a refresh;
* **degraded reads** — an injected on-disk corruption is quarantined by
  the standby's background scrubber; the corrupt key refuses with
  ``shard_quarantined`` (terminal, non-retryable) while every healthy
  key — including healthy keys in the quarantined shard — keeps
  serving.  Corruption never escalates into a store-wide failure;
* **observability** — the fault/retry/quarantine counters
  (``faults.fired``, ``gateway.client.retries``, ``scrub.quarantines``)
  are visible in the obs snapshots on both sides of the wire.

Fault placement per process (all four site families are exercised):

    writer   REPRO_FAULTS  fsync error (nth) + fsync latency (p) +
                           store.replace latency — any in-memory
                           weirdness dies with the SIGKILL; durability
                           is what the standby verifies
    standby  REPRO_FAULTS  fsync latency only (it must survive to
                           verify), deterministic nth + seeded p
    replica  REPRO_FAULTS  codec decompress/tokens errors (nth) —
                           absorbed by app-level retry
    clients  arm_spec      gateway.send/recv errors (nth + seeded p) —
                           absorbed by GatewayClient's retry loop

Every random choice — nth schedules, probabilities, which record gets
corrupted — derives from ``--seed``, and the same seed flows into
``REPRO_FAULTS_SEED`` (server ``p:`` schedules, client retry jitter),
so a failing run replays exactly.

    PYTHONPATH=src python scripts/chaos.py --seed 3          # full run
    PYTHONPATH=src python scripts/chaos.py --smoke --seed 0  # ~30s gate
    make chaos                                               # seeds 0-4

Needs only the stdlib + the repo.  This parent process never asks JAX
for a device, and each child names its platform (``_ROLE_PLATFORM``):
on a TPU host only the writer opens the chip, and the standby opens it
only after taking the lease from the killed writer.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SRC = str(Path(__file__).resolve().parent.parent / "src")
sys.path.insert(0, SRC)

from repro import obs  # noqa: E402
from repro.core import failpoints  # noqa: E402
from repro.core.api import PromptCompressor  # noqa: E402
from repro.core.store import ShardedPromptStore  # noqa: E402
from repro.service.gateway import (GatewayClient, GatewayError)  # noqa: E402
from repro.tokenizer.vocab import default_tokenizer  # noqa: E402

#: protocol verdicts the harness treats as bugs, not injected noise
_TERMINAL_CODES = frozenset({
    "shard_quarantined", "not_found", "bad_request", "unknown_op",
    "read_only", "frame_too_large", "bad_frame", "unknown_ticket",
    "not_a_replica"})


class Config:
    def __init__(self, seed: int, smoke: bool, clients: int) -> None:
        self.seed = seed
        self.smoke = smoke
        self.clients = clients or (2 if smoke else 4)
        self.batches_a = 3 if smoke else 5
        self.batches_b = 2 if smoke else 4
        self.texts = 3 if smoke else 4
        self.op_deadline_s = 60.0


def _text(seed: int, phase: str, ci: int, bi: int, r: int) -> str:
    return (f"chaos s{seed} {phase} c{ci} b{bi} r{r}: flush the journal, "
            f"fence the epoch, re-elect the shard leader. " * 3)


def _fault_specs(seed: int) -> Dict[str, str]:
    rng = random.Random(0xC4A05 ^ seed)
    return {
        # one deterministic fsync error (past startup's ~4 fsyncs, well
        # inside phase A's >= 12) + seeded latency jitter everywhere
        "writer": (
            f"durability.fsync_file=nth:{rng.randint(6, 10)},error;"
            f"durability.fsync_file|durability.fsync_dir=p:0.03,"
            f"latency:0.002;"
            f"store.replace=nth:{rng.randint(1, 3)},latency:0.02"),
        # the standby must survive to verify: latency only
        "standby": (
            f"durability.fsync_file=nth:2,latency:0.005;"
            f"durability.fsync_file|durability.fsync_dir=p:0.03,"
            f"latency:0.002"),
        "replica": (
            f"codec.decompress=nth:{rng.randint(2, 6)},error;"
            f"codec.tokens=nth:1,error"),
        "clients": (
            f"gateway.recv=nth:{rng.randint(2, 5)},error;"
            f"gateway.send|gateway.recv=p:0.04,error"),
    }


# ---------------------------------------------------------------------------
# fleet processes
# ---------------------------------------------------------------------------


class Proc:
    def __init__(self, name: str, cmd: List[str], env: dict,
                 log: Path) -> None:
        self.name = name
        self.log = log
        self._logf = open(log, "w")
        self.popen = subprocess.Popen(cmd, env=env, stdout=self._logf,
                                      stderr=subprocess.STDOUT, text=True)

    def tail(self, n: int = 25) -> str:
        self._logf.flush()
        lines = self.log.read_text(errors="replace").splitlines()
        return "\n".join(f"  [{self.name}] {ln}" for ln in lines[-n:])

    def close(self) -> None:
        if self.popen.poll() is None:
            self.popen.kill()
            self.popen.wait(10)
        self._logf.close()


#: one process per chip: the writer (and the standby once it holds the
#: lease) keep JAX's choice; the replica stays on the host
_ROLE_PLATFORM = {"writer": "auto", "standby": "auto", "replica": "cpu"}


def _spawn(name: str, role: str, store: Path, port_file: Path, spec: str,
           seed: int, tmp: Path, *, scrub_s: float = 0.0,
           stats_json: Optional[Path] = None) -> Proc:
    cmd = [sys.executable, "-m", "repro.launch.gateway",
           "--store-dir", str(store), "--role", role,
           "--platform", _ROLE_PLATFORM[role],
           "--port", "0", "--port-file", str(port_file),
           "--shards", "3", "--flush-batch", "8"]
    if scrub_s:
        cmd += ["--scrub-interval", str(scrub_s)]
    if stats_json is not None:
        cmd += ["--stats-json", str(stats_json)]
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["REPRO_FAULTS"] = spec
    env["REPRO_FAULTS_SEED"] = str(seed)
    return Proc(name, cmd, env, tmp / f"{name}.log")


def _wait_port(port_file: Path, proc: Proc, timeout_s: float = 30.0) -> dict:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if port_file.exists():
            try:
                return json.loads(port_file.read_text())
            except ValueError:  # mid-publish
                pass
        if proc.popen.poll() is not None:
            raise RuntimeError(
                f"{proc.name} died at startup "
                f"(exit {proc.popen.returncode})\n{proc.tail()}")
        time.sleep(0.05)
    raise RuntimeError(f"{proc.name} not serving within {timeout_s}s")


# ---------------------------------------------------------------------------
# failover client
# ---------------------------------------------------------------------------


class FleetClient:
    """A GatewayClient that fails over across an ordered list of port
    files: when the dialed gateway dies (connection loss the client's
    own retry budget cannot heal) it re-dials whichever endpoint serves
    first — the SIGKILL takeover path.  Injected server-side faults
    (``FailpointError`` responses) get a bounded application-level
    retry; genuine protocol verdicts propagate."""

    def __init__(self, port_files: List[Path], seed: int,
                 deadline_s: float = 60.0) -> None:
        self._port_files = list(port_files)
        self._seed = seed
        self._deadline_s = deadline_s
        self._client: Optional[GatewayClient] = None
        self.injected_errors = 0
        self.redials = 0

    def _dial(self) -> GatewayClient:
        t0 = time.monotonic()
        while time.monotonic() - t0 < self._deadline_s:
            for pf in self._port_files:
                try:
                    info = json.loads(pf.read_text())
                except (OSError, ValueError):
                    continue  # not published yet (standby pre-takeover)
                try:
                    client = GatewayClient(info["host"], info["port"],
                                           timeout=10.0,
                                           retry_seed=self._seed)
                except OSError:
                    continue  # that gateway is dead; try the next
                self.redials += 1
                return client
            time.sleep(0.1)
        raise TimeoutError(
            f"no gateway endpoint dialable within {self._deadline_s}s "
            f"(tried {[str(p) for p in self._port_files]})")

    def op(self, name: str, *args, **kw):
        last: Optional[BaseException] = None
        t0 = time.monotonic()
        attempt = 0
        while time.monotonic() - t0 < self._deadline_s:
            if self._client is None:
                self._client = self._dial()
            try:
                return getattr(self._client, name)(*args, **kw)
            except GatewayError as e:
                if e.code in _TERMINAL_CODES:
                    raise
                # an injected server-side fault surfaced as an error
                # response (e.g. FailpointError at a writer fsync): the
                # op was not acked, so a re-issue is safe and idempotent
                self.injected_errors += 1
                last = e
            except (ConnectionError, OSError) as e:
                last = e
                self.close()
            attempt += 1
            time.sleep(min(0.5, 0.05 * attempt))
        raise TimeoutError(f"op {name!r} did not succeed within "
                           f"{self._deadline_s}s") from last

    def close(self) -> None:
        if self._client is not None:
            try:
                self._client.close()
            except OSError:
                pass
            self._client = None


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------


def _worker(cfg: Config, phase: str, ci: int, n_batches: int,
            port_files: List[Path], acked: Dict[str, str],
            lock: threading.Lock, first_ack: threading.Event,
            errors: List[BaseException], injected: List[int]) -> None:
    fleet = FleetClient(port_files, cfg.seed + ci,
                        deadline_s=cfg.op_deadline_s)
    try:
        for bi in range(n_batches):
            texts = [_text(cfg.seed, phase, ci, bi, r)
                     for r in range(cfg.texts)]
            keys = fleet.op("put", texts)
            with lock:
                acked.update(zip(keys, texts))
            first_ack.set()
            got = fleet.op("get_many", keys)
            if got != texts:
                raise AssertionError(
                    f"lossless violation: {phase} c{ci} b{bi} read back "
                    f"different bytes than it acked")
    except BaseException as e:  # noqa: BLE001 - reported by the driver
        errors.append(e)
    finally:
        with lock:
            injected[0] += fleet.injected_errors
        fleet.close()


def _run_phase(cfg: Config, phase: str, n_batches: int,
               port_files: List[Path], acked: Dict[str, str],
               injected: List[int]) -> threading.Event:
    lock = threading.Lock()
    first_ack = threading.Event()
    errors: List[BaseException] = []
    threads = [threading.Thread(
        target=_worker, name=f"{phase}-c{ci}",
        args=(cfg, phase, ci, n_batches, port_files, acked, lock,
              first_ack, errors, injected))
        for ci in range(cfg.clients)]
    for t in threads:
        t.start()
    if phase == "pB":
        return first_ack, threads, errors  # caller kills the writer
    for t in threads:
        t.join(120)
    if errors:
        raise RuntimeError(f"phase {phase} worker errors: {errors!r}")
    return first_ack, [], errors


def _verify_acked(fleet: FleetClient, acked: Dict[str, str],
                  chunk: int = 64) -> None:
    keys = sorted(acked)
    for i in range(0, len(keys), chunk):
        ks = keys[i:i + chunk]
        texts = fleet.op("get_many", ks)
        for k, t in zip(ks, texts):
            if t != acked[k]:
                raise AssertionError(
                    f"acked-write loss: key {k[:12]}... read back "
                    f"{len(t)} chars != the {len(acked[k])} acked")


# ---------------------------------------------------------------------------
# corruption
# ---------------------------------------------------------------------------


def _corrupt_record(store_dir: Path, key: str) -> Tuple[int, List[str]]:
    """Flip bytes mid-record in `key`'s on-disk frame (readonly open: the
    standby holds the lease).  Returns (shard id, every key routed to
    that shard) so degraded-read assertions can target shard-mates."""
    store = ShardedPromptStore(
        store_dir, PromptCompressor(default_tokenizer(), method="zstd"),
        readonly=True)
    try:
        lay = store._layout
        sid = store._shard_of(key, lay.n_shards)
        rec = store._index[key]
        data, _ = store._shard_paths(sid, lay.gens[sid], lay.n_shards)
        with open(data, "r+b") as f:
            f.seek(rec["offset"] + rec["length"] // 2)
            n = max(4, rec["length"] // 4)
            f.write(bytes(b ^ 0xFF for b in f.read(n)) or b"\xff")
        mates = [k for k in store._index
                 if store._shard_of(k, lay.n_shards) == sid]
        return sid, mates
    finally:
        store.close()


def _wait_quarantine(fleet: FleetClient, timeout_s: float = 45.0) -> dict:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        st = fleet.op("stats")
        if st["service"]["store"]["quarantined_shards"]:
            return st
        time.sleep(0.3)
    raise TimeoutError(
        f"scrubber never quarantined the corrupted shard in {timeout_s}s")


def _counter_sum(snap: dict, name: str, contains: str = "") -> float:
    return sum(v for k, v in snap.get("counters", {}).items()
               if (k == name or k.startswith(name + "{"))
               and contains in k)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run(cfg: Config) -> int:
    rng = random.Random(cfg.seed)
    specs = _fault_specs(cfg.seed)
    tmp = Path(tempfile.mkdtemp(prefix=f"chaos-s{cfg.seed}-"))
    store_dir = tmp / "store"
    pf = {r: tmp / f"{r}.port.json" for r in ("writer", "standby",
                                              "replica")}
    stats_json = tmp / "standby-stats.json"
    procs: List[Proc] = []
    try:
        writer = _spawn("writer", "writer", store_dir, pf["writer"],
                        specs["writer"], cfg.seed, tmp)
        procs.append(writer)
        _wait_port(pf["writer"], writer)
        # only after the writer owns the lease: a standby racing an
        # un-created store would win the flock and become the writer
        standby = _spawn("standby", "standby", store_dir, pf["standby"],
                         specs["standby"], cfg.seed, tmp, scrub_s=0.5,
                         stats_json=stats_json)
        procs.append(standby)
        replica = _spawn("replica", "replica", store_dir, pf["replica"],
                         specs["replica"], cfg.seed, tmp)
        procs.append(replica)
        _wait_port(pf["replica"], replica)

        failpoints.arm_spec(specs["clients"], seed=cfg.seed)
        acked: Dict[str, str] = {}
        injected = [0]
        endpoints = [pf["writer"], pf["standby"]]

        # phase A: workload against the (fault-armed) writer
        _run_phase(cfg, "pA", cfg.batches_a, endpoints, acked, injected)

        # the writer's nth fsync error is guaranteed to have fired inside
        # phase A's puts; its own snapshot proves it (a client may or may
        # not see the error response — its read can be severed by a
        # client-side injected socket fault, and the retried put succeeds)
        fleet = FleetClient([pf["writer"], pf["standby"]], cfg.seed,
                            deadline_s=cfg.op_deadline_s)
        wsnap = fleet.op("stats", snapshot=True)["obs"]
        if _counter_sum(wsnap, "faults.fired", contains="action=error") < 1:
            raise AssertionError(
                "the writer's injected fsync error never fired during "
                "phase A — the fault schedule did not run")

        # phase B: SIGKILL the writer mid-workload; the standby's lease
        # wait breaks the instant the flock drops and clients fail over
        first_ack, threads, errors = _run_phase(
            cfg, "pB", cfg.batches_b, endpoints, acked, injected)
        if not first_ack.wait(60):
            raise TimeoutError("phase B never acked a first write")
        time.sleep(0.1)
        writer.popen.send_signal(signal.SIGKILL)
        for t in threads:
            t.join(120)
        if errors:
            raise RuntimeError(f"phase B worker errors: {errors!r}")
        writer.popen.wait(10)

        # phase C: the fleet must keep ACCEPTING writes after the
        # takeover, not just serving old ones — and it guarantees the
        # standby's own deterministic fsync faults fire (phase B can
        # complete against the writer if the SIGKILL lands late)
        _run_phase(cfg, "pC", 1, endpoints, acked, injected)

        # zero acked-write loss through the takeover (the fleet client
        # redials: the writer endpoint refuses, the standby serves)
        _verify_acked(fleet, acked)

        # the replica converges after a refresh — byte-identical too,
        # through its injected codec faults
        rfleet = FleetClient([pf["replica"]], cfg.seed,
                             deadline_s=cfg.op_deadline_s)
        rfleet.op("refresh")
        _verify_acked(rfleet, acked)
        sample = rng.choice(sorted(acked))
        if len(rfleet.op("get_tokens", sample)) == 0:
            raise AssertionError("replica served an empty token array")
        wgen = fleet.op("stats")["gateway"]["store_generation"]
        rgen = rfleet.op("stats")["gateway"]["store_generation"]
        if not (wgen >= 1 and rgen == wgen):
            raise AssertionError(
                f"replica staleness after refresh: gen {rgen} != {wgen}")
        rfleet.close()

        # corruption -> scrub -> quarantine -> degraded reads
        bad_key = rng.choice(sorted(acked))
        sid, mates = _corrupt_record(store_dir, bad_key)
        st = _wait_quarantine(fleet)
        if st["service"]["store"]["quarantined_shards"] != [sid]:
            raise AssertionError(
                f"expected exactly shard {sid} quarantined, got "
                f"{st['service']['store']['quarantined_shards']}")
        try:
            fleet.op("get", bad_key)
            raise AssertionError(
                "corrupt key served instead of refusing with "
                "shard_quarantined")
        except GatewayError as e:
            if e.code != "shard_quarantined" or e.retryable:
                raise AssertionError(
                    f"corrupt key refused with {e.code!r} "
                    f"retryable={e.retryable}; wanted terminal "
                    f"shard_quarantined") from e
        healthy = {k: v for k, v in acked.items() if k != bad_key}
        healthy_mates = [k for k in healthy if k in mates]
        if not healthy_mates:
            raise AssertionError(
                f"no healthy shard-mates for {bad_key[:12]}... — cannot "
                f"prove per-key (not per-shard) degradation")
        _verify_acked(fleet, healthy)  # shard-mates included

        # counters on both sides of the wire
        snap = fleet.op("stats", snapshot=True)["obs"]
        local = obs.snapshot()
        checks = {
            "standby scrub.quarantines": _counter_sum(
                snap, "scrub.quarantines"),
            "standby scrub.corrupt_records": _counter_sum(
                snap, "scrub.corrupt_records"),
            "standby faults.fired": _counter_sum(snap, "faults.fired"),
            "client gateway.client.retries": _counter_sum(
                local, "gateway.client.retries"),
            "client faults.fired": _counter_sum(local, "faults.fired"),
            "client reconnects": _counter_sum(
                local, "gateway.client.reconnects"),
        }
        missing = {k: v for k, v in checks.items() if v < 1}
        if missing:
            raise AssertionError(
                f"fault/retry/quarantine counters not visible: {missing}")

        # graceful drain of the survivors; SIGKILL is the writer's only
        # legitimate exit
        standby.popen.send_signal(signal.SIGTERM)
        replica.popen.send_signal(signal.SIGTERM)
        if standby.popen.wait(30) != 0:
            raise RuntimeError(
                f"standby drain exit {standby.popen.returncode}\n"
                f"{standby.tail()}")
        if replica.popen.wait(30) != 0:
            raise RuntimeError(
                f"replica drain exit {replica.popen.returncode}\n"
                f"{replica.tail()}")
        if writer.popen.returncode != -signal.SIGKILL:
            raise RuntimeError(
                f"writer exit {writer.popen.returncode}, expected "
                f"-SIGKILL")
        json.loads(stats_json.read_text())  # atomic publish parses
        fleet.close()

        print(f"chaos seed {cfg.seed}: OK — {len(acked)} acked writes "
              f"lossless across a SIGKILL takeover; shard {sid} "
              f"quarantined ({len(mates) - len(healthy_mates)} casualty, "
              f"{len(healthy_mates)} shard-mates kept serving); "
              f"server errors absorbed={injected[0]}, client retries="
              f"{int(checks['client gateway.client.retries'])}, "
              f"reconnects={int(checks['client reconnects'])}")
        return 0
    except (AssertionError, RuntimeError, TimeoutError, OSError) as e:
        print(f"chaos seed {cfg.seed}: FAIL — {e}", file=sys.stderr)
        for p in procs:
            print(p.tail(), file=sys.stderr)
        return 1
    finally:
        failpoints.disarm_all()
        for p in procs:
            p.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="drives every schedule and random choice")
    ap.add_argument("--smoke", action="store_true",
                    help="bounded ~30s run (CI gate): one SIGKILL "
                         "takeover + one injected fsync fault + one "
                         "injected shard corruption")
    ap.add_argument("--clients", type=int, default=0,
                    help="concurrent workload clients (default 2 smoke, "
                         "4 full)")
    args = ap.parse_args(argv)
    return run(Config(args.seed, args.smoke, args.clients))


if __name__ == "__main__":
    sys.exit(main())
