"""One token id of every record altered where the codec produces it,
before packing."""

import numpy as np

import faults


def make(orig):
    def encode_ids_batch(self, ids_list):
        out = [np.array(a, np.uint32) for a in ids_list]
        for a in out:
            if a.size:
                a[a.size // 2] ^= 1
        return orig(self, out)
    return encode_ids_batch


def apply():
    return faults.patch("repro.core.codec", "TokenPackCodec.encode_ids_batch",
                        make)
