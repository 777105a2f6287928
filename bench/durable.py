"""What the store made durable, seen from outside the program: its fsyncs.

While a ``Fsyncs`` is installed, every ``os.fsync`` or ``os.fdatasync``
of a regular file records the file's path, its length before the call
(what the call makes durable) and the monotonic time at which the call
returned; ``os.replace`` and ``os.rename`` carry a path's records to its
new name.  From the records:

* ``not_durable_at_ack`` counts the acknowledged keys whose index line or
  data bytes no fsync had made durable by the time the client held the
  ack (the client's clock is the same monotonic clock);
* ``crash`` cuts every file under the store's root back to the bytes an
  fsync made durable, and removes the files never fsynced: the store as
  a power loss after the run would leave it, page cache lost.

Directory entries are not modelled: a file keeps its name once its
contents were fsynced.  The shard layout read here is the store's
on-disk format (``<name>.bin`` frames, ``<name>.idx.jsonl`` one JSON
record per frame with ``key``, ``offset`` and ``length``).
"""

from __future__ import annotations

import bisect
import json
import os
import stat
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple


class Fsyncs:
    def __init__(self) -> None:
        self._events: List[Tuple[float, str, int]] = []   # (t, path, length)
        self._renames: List[Tuple[float, str, str]] = []
        self._orig: Dict[str, object] = {}

    def install(self) -> "Fsyncs":
        for name in ("fsync", "fdatasync"):
            orig = getattr(os, name)
            self._orig[name] = orig
            setattr(os, name, self._sync(orig))
        for name in ("replace", "rename"):
            orig = getattr(os, name)
            self._orig[name] = orig
            setattr(os, name, self._move(orig))
        return self

    def remove(self) -> None:
        for name, orig in self._orig.items():
            setattr(os, name, orig)
        self._orig = {}

    def _sync(self, orig):
        events = self._events

        def sync(fd):
            fd = fd if isinstance(fd, int) else fd.fileno()
            st = os.fstat(fd)
            path = (os.readlink(f"/proc/self/fd/{fd}")
                    if stat.S_ISREG(st.st_mode) else None)
            orig(fd)
            if path is not None:
                events.append((time.monotonic(), path, st.st_size))
        return sync

    def _move(self, orig):
        renames = self._renames

        def move(src, dst, *args, **kwargs):
            orig(src, dst, *args, **kwargs)
            renames.append((time.monotonic(), os.path.realpath(src),
                            os.path.realpath(dst)))
        return move

    def _lengths(self) -> Dict[str, Tuple[List[float], List[int]]]:
        """Per path, the times of its fsyncs and the length durable after
        each (a running maximum; a rename hands the source's to the
        destination)."""
        merged = sorted([(t, 0, p, n) for t, p, n in self._events]
                        + [(t, 1, s, d) for t, s, d in self._renames])
        out: Dict[str, Tuple[List[float], List[int]]] = {}
        for t, is_move, a, b in merged:
            if is_move:
                if a in out:
                    times, lens = out.pop(a)
                    out[b] = ([t], [lens[-1]])
                else:
                    out.pop(b, None)
                continue
            times, lens = out.setdefault(a, ([], []))
            times.append(t)
            lens.append(max(b, lens[-1] if lens else 0))
        return out

    def durable_at(self, path: str, t: float,
                   lengths: Dict[str, Tuple[List[float], List[int]]]) -> int:
        times, lens = lengths.get(os.path.realpath(path), ([], []))
        i = bisect.bisect_right(times, t)
        return lens[i - 1] if i else 0

    def not_durable_at_ack(self, root: Path,
                           acks: Sequence[Tuple[float, Sequence[str]]]) -> int:
        """Acked keys whose record no fsync had made durable by its ack."""
        lengths = self._lengths()
        need: Dict[str, Tuple[str, int, str, int]] = {}
        for idx in sorted(root.glob("*.idx.jsonl")):
            data = str(idx)[: -len(".idx.jsonl")] + ".bin"
            end = 0
            for line in idx.read_bytes().splitlines(keepends=True):
                end += len(line)
                try:
                    rec = json.loads(line)
                except ValueError:
                    break
                need.setdefault(rec["key"], (str(idx), end, data,
                                             rec["offset"] + rec["length"]))
        bad = 0
        for t, keys in acks:
            for key in keys:
                if key not in need:
                    bad += 1
                    continue
                idx, idx_end, data, data_end = need[key]
                bad += (self.durable_at(idx, t, lengths) < idx_end
                        or self.durable_at(data, t, lengths) < data_end)
        return bad

    def crash(self, root: Path) -> None:
        """Cut the files under ``root`` to what their fsyncs made durable."""
        lengths = self._lengths()
        for dirpath, _, files in os.walk(root):
            for name in files:
                path = os.path.realpath(os.path.join(dirpath, name))
                if path not in lengths:
                    os.unlink(path)
                    continue
                keep = lengths[path][1][-1]
                if os.stat(path).st_size > keep:
                    os.truncate(path, keep)
