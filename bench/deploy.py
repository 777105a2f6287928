"""Builds a configuration's deployment of the program, in one place.

The harness's run, its read-only reopen after the window and the
read-back processes all build the compressor, the store, the service
and the gateway here, from the configuration file
(``bench/configs/<config>.json``):

* ``method``, ``backend``, ``level``: the compressor
  (``PromptCompressor(tokenizer, method=, backend=, level=)``);
* ``shards``: the store (``ShardedPromptStore(root, compressor,
  n_shards=)``; a reopen is ``readonly=True`` and takes the layout on
  disk);
* ``cache_bytes``, ``flush_batch``, ``flush_interval_s``: the service
  (``PromptService(store, cache_bytes=, flush_batch=,
  flush_interval_s=)``);
* ``gateway_max_inflight``, ``gateway_conn_window``: the in-process
  writer gateway (``start_in_thread(service, max_inflight=,
  conn_window=)``).

An optional ``"program"`` object passes further keyword arguments to
those constructors by the program's own parameter names, one block per
constructor: ``{"compressor": {...}, "store": {...}, "service": {...},
"gateway": {...}}``.  The ``store`` block applies to the writer and to
every read-only reopen alike.  So a deployment that needs a store or
service option says so in its configuration file, with no edit here.
"""

from __future__ import annotations

from pathlib import Path
from typing import Union

PARTS = ("compressor", "store", "service", "gateway")


def _extra(cfg: dict, part: str) -> dict:
    program = cfg.get("program", {})
    unknown = sorted(set(program) - set(PARTS))
    if unknown:
        raise ValueError(f"configuration {cfg.get('name')!r}: 'program' "
                         f"has blocks {unknown}; known are {list(PARTS)}")
    return dict(program.get(part, {}))


def compressor(cfg: dict, tokenizer):
    from repro.core.api import PromptCompressor

    return PromptCompressor(tokenizer, method=cfg["method"],
                            backend=cfg["backend"], level=cfg["level"],
                            **_extra(cfg, "compressor"))


def store(cfg: dict, root: Union[str, Path], comp, *, readonly: bool = False):
    from repro.core.store import ShardedPromptStore

    if readonly:
        return ShardedPromptStore(root, comp, readonly=True,
                                  **_extra(cfg, "store"))
    return ShardedPromptStore(root, comp, n_shards=cfg["shards"],
                              **_extra(cfg, "store"))


def service(cfg: dict, st):
    from repro.service import PromptService

    return PromptService(st, cache_bytes=cfg["cache_bytes"],
                         flush_batch=cfg["flush_batch"],
                         flush_interval_s=cfg["flush_interval_s"],
                         **_extra(cfg, "service"))


def gateway(cfg: dict, svc):
    from repro.service.gateway import start_in_thread

    return start_in_thread(svc, max_inflight=cfg["gateway_max_inflight"],
                           conn_window=cfg["gateway_conn_window"],
                           **_extra(cfg, "gateway"))
