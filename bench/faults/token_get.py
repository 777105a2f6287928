"""One token id of every record altered on the read path, where the codec
unpacks it."""

import numpy as np

import faults


def make(orig):
    def decode_ids_batch(self, payloads, to_device=False):
        out = [np.array(a, np.uint32)
               for a in orig(self, payloads, to_device=to_device)]
        for a in out:
            if a.size:
                a[a.size // 2] ^= 1
        return out
    return decode_ids_batch


def apply():
    return faults.patch("repro.core.codec", "TokenPackCodec.decode_ids_batch",
                        make)
