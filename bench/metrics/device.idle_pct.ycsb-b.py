"""Share of the traced window in which no operation ran on the device:
``device.idle_pct.ingest``'s arithmetic, for a cell that moves
``get_tokens_p50_ms``."""

import spec

read = spec.metric_module("device.idle_pct.ingest").read
