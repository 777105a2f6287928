"""Each fsync made 0.2 s after the store asked for it, on a thread of its
own: the ack goes out before its bytes are durable."""

import os
import threading

import faults


def _later(fd: int) -> None:
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def make(orig):
    def fsync_file(f):
        f.flush()
        timer = threading.Timer(0.2, _later, (os.dup(f.fileno()),))
        timer.daemon = True
        timer.start()
    return fsync_file


def apply():
    return faults.patch("repro.core.store", "fsync_file", make)
