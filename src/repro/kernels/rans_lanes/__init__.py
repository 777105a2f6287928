from repro.kernels.rans_lanes.ops import (rans_decode_interleaved_device,
                                          rans_encode_interleaved_device,
                                          rans_encode_records_device)
from repro.kernels.rans_lanes.ref import decode_lanes_ref, encode_lanes_ref

__all__ = [
    "rans_encode_interleaved_device",
    "rans_encode_records_device",
    "rans_decode_interleaved_device",
    "encode_lanes_ref",
    "decode_lanes_ref",
]
