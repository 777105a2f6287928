"""One run of one cell: set-up, a measured window, the check, one result.

The run holds the chip in this one process.  It trains the vocabulary,
starts the load generators (child processes that never import JAX),
serves a fresh store through the program's ``PromptService`` and an
in-process writer gateway, as ``deploy.py`` builds them from the
configuration, runs the traffic kind's ``setup`` hook if it has one
(preloading or warming through the gateway, inside set-up), warms up on
the cell's own traffic, and measures ``seconds`` of it.  After the
window it drains the service, reads the device's memory peak, frees the
program's state and compares the answers with the plain reference the
configuration names.  Every kind is held to these checks:

* every request answered (none lost), and none answered with an error
  other than an admission reject (rejects count as failed requests);
* every acknowledged put returned the content keys of its texts;
* every acknowledged put was durable when its ack reached the client:
  its index line and data bytes covered by fsyncs that had returned
  (``durable.py`` watches the process's fsyncs);
* every acknowledged put is in the store reopened from only the bytes
  its fsyncs made durable, and reads back as its text (its content key
  is the SHA-256 of the text); a sample drawn from the seed, the longest
  put among it, reads back as the reference's token ids.

A kind's ``check`` hook adds its own exact counts beside them (the
answers to its reads, say), each with the limit 0.

End-to-end metrics (``--trace 0``) and per-layer metrics (``--trace 1``)
are each read by ``bench/metrics/<name>.py`` from what the run gathered.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import device
import durable
import spec
import stats
import wire

BENCH = spec.BENCH
WORK = BENCH / ".work"
COMPILE_CACHE = BENCH / ".jax_cache"
GRACE_S = 60.0            # how long past the close an answer may come
START_LEAD_S = 0.5        # from "go" to the first request


class LoadGen:
    """A load-generator child and a thread that reads its JSON lines."""

    def __init__(self, cell: spec.Cell, seed: int, client: int,
                 seconds: float) -> None:
        cmd = [sys.executable, str(BENCH / "loadgen.py"),
               "--traffic", json.dumps(cell.traffic),
               "--config", json.dumps(cell.config),
               "--seed", str(seed), "--client", str(client),
               "--seconds", str(seconds)]
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env,
                                     cwd=str(spec.ROOT))
        self.lines: "queue.Queue[Optional[dict]]" = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(json.loads(line))
        self.lines.put(None)

    def expect(self, key: str, timeout: float):
        try:
            doc = self.lines.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(f"load generator sent no {key!r} within "
                               f"{timeout} s") from None
        if doc is None or key not in doc:
            raise RuntimeError(f"load generator ended before {key!r} "
                               f"(exit {self.proc.poll()})")
        return doc[key]

    def go(self, doc: dict) -> None:
        self.proc.stdin.write((json.dumps({"go": doc}) + "\n").encode())
        self.proc.stdin.flush()

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._reader.join(5.0)


def _disk_bytes(root: Path) -> int:
    total = 0
    for dirpath, _, files in os.walk(root):
        for name in files:
            try:
                total += os.stat(os.path.join(dirpath, name)).st_size
            except FileNotFoundError:
                pass
    return total


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _enable_compile_cache() -> str:
    import jax

    COMPILE_CACHE.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    return str(COMPILE_CACHE)


def _program_env(assets: Path) -> None:
    """Default routing and settings: no REPRO_* knob from outside.  The
    vocabulary the program trains from its fixed seed is kept in
    ``assets`` across runs, as the program keeps it across starts."""
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[k]
    os.environ["REPRO_ASSET_DIR"] = str(assets)


def _merge(base: dict, extra: Optional[dict]) -> dict:
    out = dict(base)
    out.update(extra or {})
    return out


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, *, require_chip: bool = True,
        faults: List[str] = (), overrides: Optional[dict] = None,
        keep: Optional[dict] = None) -> dict:
    """One run; returns the result document (the last line's object).
    ``keep``, if given, receives the run's request records."""
    overrides = overrides or {}
    cell.config = _merge(cell.config, overrides.get("config"))
    cell.traffic = _merge(cell.traffic, overrides.get("traffic"))
    cfg, mix = cell.config, cell.traffic
    peaks = spec.load_json(BENCH / "peaks.json")["devices"]
    _enable_compile_cache()             # before the first compile
    if require_chip:
        dev = device.require_tpu(cell.chips)
        if dev["kind"] not in peaks:
            raise device.NoAccelerator(
                f"device kind {dev['kind']!r} is not in bench/peaks.json")
    else:
        dev = device.describe(cell.chips)
    marks = {"device": time.monotonic()}
    work = WORK / cell.name
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    _program_env(WORK / "assets")   # the vocabulary, trained once
    clock = device.CompileClock()

    gens = [LoadGen(cell, seed, c, seconds) for c in range(mix["clients"])]
    try:
        return _run(cell, seed, seconds, trace, t_process, dev, peaks, work,
                    clock, gens, faults, require_chip, keep, marks)
    finally:
        for g in gens:
            g.stop()


def _run(cell, seed, seconds, trace, t_process, dev, peaks, work, clock,
         gens, faults, require_chip, keep, marks) -> dict:
    import deploy
    import faults as fault_lib
    from repro import obs
    from repro.tokenizer.vocab import default_tokenizer

    cfg, mix = cell.config, cell.traffic
    kind = spec.kind_module(mix["kind"])
    tok = default_tokenizer()                     # trained from its seed
    marks["vocabulary"] = time.monotonic()

    def compressor():
        return deploy.compressor(cfg, tok)

    root = work / "store"
    fsyncs = durable.Fsyncs().install()
    try:
        store = deploy.store(cfg, root, compressor())
    except BaseException:
        fsyncs.remove()
        raise
    undo = [fault_lib.apply(name) for name in faults]

    spans = _TraceSpans(BENCH / "trace_spans.json") if trace else None
    service = deploy.service(cfg, store)
    service.start()
    gw = deploy.gateway(cfg, service)
    try:
        for g in gens:
            g.expect("ready", 600)
        marks["generators"] = time.monotonic()
        disk0 = _disk_bytes(root)
        prepared = {"records": [], "go": {}}
        if hasattr(kind, "setup"):
            prepared = kind.setup(mix, cfg, seed, seconds, gw.port)
            marks["kind_setup"] = time.monotonic()
        t_start = time.monotonic() + START_LEAD_S
        t0 = t_start + mix["warmup_s"]
        t1 = t0 + seconds
        for g in gens:
            g.go(dict(prepared["go"], port=gw.port, t_start=t_start,
                      t0=t0, t1=t1, grace_s=GRACE_S))
        tracer = _Tracer(work / "trace") if trace else None
        if tracer:
            tracer.start()
        _sleep_until(t0)
        snap0 = obs.snapshot()
        window = tracer.window() if tracer else None
        _sleep_until(t1)
        snap1 = obs.snapshot()
        if window:
            window.__exit__(None, None, None)
        compiles = clock.window(t0, t1)
        if tracer:
            tracer.stop()
        results = [g.expect("result", seconds + mix["warmup_s"] + GRACE_S
                            + 120) for g in gens]
        service.drain()
        disk1 = _disk_bytes(root)
        mem_peak = device.memory_peak_bytes(cell.chips)
    finally:
        gw.shutdown()
        service.stop()
        store.close()
        fsyncs.remove()
        if spans:
            spans.remove()
        for u in reversed(undo):
            u()
    del service, store, gw

    records = prepared["records"] + [r for res in results
                                     for r in res["records"]]
    if keep is not None:
        keep.update(records=records, t0=t0, t1=t1)
    ctx = SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, t0=t0, t1=t1,
        grace_s=GRACE_S, setup_s=t0 - t_process, records=records,
        window=[r for r in records if t0 <= r["due"] < t1],
        disk0=disk0, disk1=disk1, obs=obs.diff(snap0, snap1),
        compiles=compiles, device=dev, peaks=peaks.get(dev["kind"]),
        trace=None, launches=spans.launches(t0, t1) if spans else {})
    if tracer:
        import trace_reduce

        try:
            ctx.trace = trace_reduce.reduce(tracer.dir, _kernel_patterns())
        except ValueError:
            if require_chip:        # a chip's trace has to reduce
                raise
        shutil.rmtree(tracer.dir, ignore_errors=True)

    t_check = time.monotonic()
    checks = _check(ctx, kind, root, compressor, fsyncs)
    ctx.check_s = time.monotonic() - t_check
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_module(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if not require_chip:
        # a rehearsal off the chip: its times are the CPU's, not the
        # device's, so the metrics are read but not printed
        print(f"[bench] rehearsal off the chip; metrics read but not "
              f"printed: {sorted(metrics)}", file=sys.stderr, flush=True)
        metrics = {}
    out_dev = dict(dev, memory_peak_bytes=mem_peak)
    if ctx.trace:
        out_dev.update(busy_s=ctx.trace["busy_s"],
                       window_s=ctx.trace["window_s"])
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(ctx.window),
        "failed": sum(r["status"] != "ok" for r in ctx.window),
        "metrics": metrics, "device": out_dev,
    }
    if ctx.trace:
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    notes = _notes(ctx, marks, t_process, results)
    print("[bench] " + json.dumps(notes), file=sys.stderr, flush=True)
    result["checks"] = checks
    return result


def _notes(ctx, marks: dict, t_process: float, results: list) -> dict:
    """What a reader of one run needs beside its metrics: set-up by
    phase, compiles in the window, and each op's tail by half window
    (a tail that grows from the first half to the second is a backlog)."""
    notes = {
        "setup_s": ctx.setup_s,
        "setup_marks_s": {k: v - t_process for k, v in marks.items()},
        "check_s": ctx.check_s,
        "backend_compiles_in_window": ctx.compiles["backend_compiles"],
        "backend_compiles_in_window_by_fn":
            ctx.compiles["backend_compiles_by_fn"],
        "compile_s_in_window": ctx.compiles["compile_s"],
        "bodies_reused": sum(res.get("reused", 0) for res in results),
    }
    half = (ctx.t0 + ctx.t1) / 2
    for op in sorted({r["op"] for r in ctx.window}):
        recs = [r for r in ctx.window if r["op"] == op]
        lat = [stats.latency_ms(ctx, r) for r in recs]
        notes[f"{op}_n"] = len(lat)
        for q in (50, 90, 95, 99):
            notes[f"{op}_p{q}_ms"] = stats.nearest_rank(lat, q / 100)
        for label, part in (("first", [r for r in recs if r["due"] < half]),
                            ("second", [r for r in recs if r["due"] >= half])):
            notes[f"{op}_p95_ms_{label}_half"] = stats.nearest_rank(
                [stats.latency_ms(ctx, r) for r in part], 0.95)
    late = [(r["sent"] - r["due"]) * 1e3 for r in ctx.window]
    notes["generator_late_ms_p99"] = stats.nearest_rank(late, 0.99)
    notes["generator_late_ms_max"] = max(late, default=None)
    notes["answered_ok"] = sum(r["status"] == "ok" for r in ctx.window)
    notes["answered_ok_by_close_1s"] = sum(
        r["status"] == "ok" and r["done"] <= ctx.t1 + 1.0 for r in ctx.window)
    notes["rejects"] = sum(r["status"] == "admission_reject"
                           for r in ctx.window)
    return notes


def _kernel_patterns() -> Dict[str, str]:
    """Each kernel's device-op name pattern, from ``bench/work/*.py``."""
    return {p.stem: spec.load_module(p).OP_NAME
            for p in sorted((BENCH / "work").glob("*.py"))}


def _check(ctx, kind, root, compressor, fsyncs) -> dict:
    """Every number compared, each beside its limit (all exact: 0): the
    checks of acknowledged puts that every kind is held to, then those
    of the kind's ``check`` hook."""
    import numpy as np

    import deploy

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    recs = ctx.records
    acked = [r for r in recs if r["op"] == "put" and r["status"] == "ok"]
    checks = {
        "lost": sum(r["status"] == "lost" for r in recs),
        "errors": sum(r["status"] not in ("ok", "lost", "admission_reject")
                      for r in recs),
        "wrong_keys": sum(not r["keys_ok"] for r in acked),
        "not_durable_at_ack": fsyncs.not_durable_at_ack(
            root, [(r["done"], r["keys"]) for r in acked]),
    }
    fsyncs.crash(root)
    reopened = deploy.store(cfg, root, compressor(), readonly=True)
    stored = set(reopened.keys())
    keys = [k for r in acked for k in r["keys"]]
    checks["acked_missing"] = sum(k not in stored for k in keys)
    # the text of every acked put, read back by processes of their own
    # while this one runs the reference: its key, checked by the client
    # to be the SHA-256 of the text it sent, is the SHA-256 of what reads
    readers = _Readback(cfg, root, [k for k in keys if k in stored],
                        root.parent)
    try:
        enc = spec.reference_module(cfg).encoder(cfg)

        idents = [(tuple(i), k) for r in acked
                  for i, k in zip(r["ids"], r["keys"])]
        rng = np.random.default_rng([ctx.seed, 99])
        n = min(mix["readback_sample"], len(idents))
        pick = (set(rng.choice(len(idents), n, replace=False).tolist())
                if n else set())
        if idents:
            pick.add(max(range(len(idents)), key=lambda j: kind.put_chars(
                mix, cfg, ctx.seed, list(idents[j][0]))))
        bad = 0
        for j in sorted(pick):
            ident, key = idents[j]
            text = kind.put_text(mix, cfg, ctx.seed, list(ident))
            try:
                ok = wire.ids_digest(reopened.get_tokens(key).tolist()) \
                    == wire.ids_digest(enc.encode(text))
            except Exception:           # a read that fails is a wrong answer
                ok = False
            bad += not ok
        checks["readback_ids_bad"] = bad
    finally:
        reopened.close()
        checks["readback_text_bad"] = readers.bad()
    print(f"[bench] compared: {len(keys)} acked puts, each checked durable "
          f"at its ack and read back as text from the store reopened after "
          f"a cut to its fsynced bytes; {len(pick)} of them read back as "
          f"token ids", file=sys.stderr, flush=True)
    if hasattr(kind, "check"):
        checks.update(kind.check(ctx, enc))
    return {name: {"value": int(v), "limit": 0} for name, v in checks.items()}


class _Readback:
    """``readback.py`` over a share of the keys each, on the CPU."""

    def __init__(self, cfg: dict, root: Path, keys: List[str],
                 work: Path) -> None:
        path = work / "acked_keys.txt"
        path.write_text("\n".join(keys))
        self.n_keys = len(keys)
        parts = max(1, min(8, (os.cpu_count() or 2) // 2))
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
        env["JAX_PLATFORMS"] = "cpu"
        self.procs = [subprocess.Popen(
            [sys.executable, str(BENCH / "readback.py"),
             "--config", json.dumps(cfg), "--root", str(root),
             "--keys", str(path), "--part", str(i), "--parts", str(parts)],
            stdout=subprocess.PIPE, env=env, cwd=str(spec.ROOT))
            for i in range(parts)]

    def bad(self) -> int:
        """Keys whose text read back wrong; a reader that gives no count
        counts its whole share."""
        read = bad = 0
        for p in self.procs:
            try:
                out, _ = p.communicate(timeout=GRACE_S * 5)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            try:
                doc = json.loads(out.decode().strip().splitlines()[-1])
                read, bad = read + doc["read"], bad + doc["bad"]
            except (ValueError, IndexError, KeyError):
                pass
        return bad + (self.n_keys - read)


class _Tracer:
    """The profiler over the window, with a host span marking the window."""

    def __init__(self, path: Path) -> None:
        self.dir = path

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # Python call events would swamp it
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def window(self):
        import jax

        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        return span

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


class _TraceSpans:
    """In a traced run, host spans around the program's calls into each
    layer, as ``trace_spans.json`` names them; a span that names a
    ``work`` module also records, per call, what ``bench/work/<work>.py``
    needs to count the kernel's operations and bytes."""

    def __init__(self, path: Path) -> None:
        import importlib

        self._undo = []
        self._launches: List[tuple] = []
        for entry in spec.load_json(path)["spans"]:
            owner = importlib.import_module(entry["module"])
            parts = entry["attr"].split(".")
            for p in parts[:-1]:
                owner = getattr(owner, p)
            orig = getattr(owner, parts[-1])
            work = (spec.load_module(BENCH / "work" / f"{entry['work']}.py")
                    if "work" in entry else None)
            setattr(owner, parts[-1],
                    self._wrap(orig, entry["span"], entry.get("work"), work))
            self._undo.append((owner, parts[-1], orig))

    def _wrap(self, fn, span, work_name, work):
        import jax

        launches = self._launches

        def wrapped(*args, **kwargs):
            if work is not None:
                launches.append((time.monotonic(), work_name,
                                 work.launch(*args, **kwargs)))
            with jax.profiler.TraceAnnotation(span):
                return fn(*args, **kwargs)

        wrapped.__wrapped__ = fn
        return wrapped

    def launches(self, t0: float, t1: float) -> Dict[str, list]:
        out: Dict[str, list] = {}
        for t, name, launch in self._launches:
            if t0 <= t < t1:
                out.setdefault(name, []).append(launch)
        return out

    def remove(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
