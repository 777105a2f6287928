"""Backend compiles that ended inside the window:
``jax.compiles_in_window``'s count, for a cell that moves
``get_tokens_p50_ms``."""

import spec

read = spec.metric_module("jax.compiles_in_window").read
