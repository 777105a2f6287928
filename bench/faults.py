"""Breaks planted under the timed path, to show that the check catches them.

Each is applied in the run's own process, after the store is opened and
before any put, and returns a function that takes it out again.

``pack16``   the control: the hybrid method's lossless guarantee broken
             by the step that would tempt a later change, every token id
             packed in 16 bits (ids of 65536 and above, the special
             markers, lose their high bits).
``no_write`` a group commit that acknowledges and writes nothing: the
             store's state unchanged by a step.
``half``     a group commit that writes the first half of its records
             and acknowledges all of them.
``token_put`` one token id of every record altered where the codec
             produces it, before packing.
``no_fsync`` the store's fsyncs left out: every write flushed to the
             operating system, none made durable.
``late_fsync`` each fsync made 0.2 s after the store asked for it, on a
             thread of its own: the ack goes out before its bytes are
             durable.
"""

from __future__ import annotations

from typing import Callable


def _patch(owner, attr: str, make) -> Callable[[], None]:
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    return lambda: setattr(owner, attr, orig)


def pack16() -> Callable[[], None]:
    import numpy as np

    from repro.core.codec import TokenPackCodec

    def make(orig):
        def encode_ids_batch(self, ids_list):
            return orig(self, [np.asarray(a, np.uint32) & np.uint32(0xFFFF)
                               for a in ids_list])
        return encode_ids_batch
    return _patch(TokenPackCodec, "encode_ids_batch", make)


def no_write() -> Callable[[], None]:
    from repro.core.store import ShardedPromptStore

    def make(orig):
        def commit_batch(self, shard_id, entries):
            return []
        return commit_batch
    return _patch(ShardedPromptStore, "commit_batch", make)


def half() -> Callable[[], None]:
    from repro.core.store import ShardedPromptStore

    def make(orig):
        def commit_batch(self, shard_id, entries):
            entries = list(entries)
            return orig(self, shard_id, entries[: (len(entries) + 1) // 2])
        return commit_batch
    return _patch(ShardedPromptStore, "commit_batch", make)


def token_put() -> Callable[[], None]:
    import numpy as np

    from repro.core.codec import TokenPackCodec

    def make(orig):
        def encode_ids_batch(self, ids_list):
            out = [np.array(a, np.uint32) for a in ids_list]
            for a in out:
                if a.size:
                    a[a.size // 2] ^= 1
            return orig(self, out)
        return encode_ids_batch
    return _patch(TokenPackCodec, "encode_ids_batch", make)


def no_fsync() -> Callable[[], None]:
    import repro.core.store as store

    def make(orig):
        def fsync_file(f):
            f.flush()
        return fsync_file
    return _patch(store, "fsync_file", make)


def late_fsync() -> Callable[[], None]:
    import os
    import threading

    import repro.core.store as store

    def later(fd: int) -> None:
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    def make(orig):
        def fsync_file(f):
            f.flush()
            timer = threading.Timer(0.2, later, (os.dup(f.fileno()),))
            timer.daemon = True
            timer.start()
        return fsync_file
    return _patch(store, "fsync_file", make)


FAULTS = {"pack16": pack16, "no_write": no_write, "half": half,
          "token_put": token_put, "no_fsync": no_fsync,
          "late_fsync": late_fsync}


def apply(name: str) -> Callable[[], None]:
    return FAULTS[name]()
