"""Work of the token-pack kernel (``repro.kernels.token_pack``).

One launch packs the n ids of one width group: it reads n int32 ids and
writes n * width bytes.  The work is counted from the real n of each
launch, not the padded one, so it is the same whatever implements it.
The kernel does shifts and masks only: no floating-point operations.
"""

from __future__ import annotations

import numpy as np

#: the kernel's device op in a profiler trace: the Mosaic custom call of
#: the program's jitted ``_pack_padded`` (not its pad and layout copies)
OP_NAME = r'^%_pack_padded[.\d]* = .*custom_call_target="tpu_custom_call"'


def launch(ids_list, *args, **kwargs) -> list:
    """[[n, width], ...] for the width groups of one batch call, as the
    program groups them (width 2 iff every id of the stream fits)."""
    n = {2: 0, 4: 0}
    for ids in ids_list:
        ids = np.asarray(ids)
        if ids.size:
            n[2 if int(ids.max()) <= 0xFFFF else 4] += int(ids.size)
    return [[k, w] for w, k in n.items() if k]


def work(launches: list) -> tuple:
    """(operations, bytes) of the launches of a window."""
    nbytes = sum(n * (4 + w) for call in launches for n, w in call)
    return 0.0, float(nbytes)
