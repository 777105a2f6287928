"""Seconds of XLA backend compiles in the window: ``device.compile_s``'s
sum, for a cell that moves ``get_tokens_p50_ms``."""

import spec

read = spec.metric_module("device.compile_s").read
