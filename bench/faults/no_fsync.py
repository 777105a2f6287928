"""The store's fsyncs left out: every write flushed to the operating
system, none made durable."""

import faults


def make(orig):
    def fsync_file(f):
        f.flush()
    return fsync_file


def apply():
    return faults.patch("repro.core.store", "fsync_file", make)
