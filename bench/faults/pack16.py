"""The control: the hybrid method's lossless guarantee broken by the step
that would tempt a later change, every token id packed in 16 bits (ids of
65536 and above, the special markers, lose their high bits)."""

import numpy as np

import faults


def make(orig):
    def encode_ids_batch(self, ids_list):
        return orig(self, [np.asarray(a, np.uint32) & np.uint32(0xFFFF)
                           for a in ids_list])
    return encode_ids_batch


def apply():
    return faults.patch("repro.core.codec", "TokenPackCodec.encode_ids_batch",
                        make)
