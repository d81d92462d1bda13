"""decode_cache_ms: device time a decode step spends on its cache's layout.

Self time under the program's ``kv_write`` scope (each layer's new entry
written into its cache) and the ``layers`` scan's own (each layer's weights
and cache sliced out of the stacked arrays, and the cache stacked back),
per decode step inside the traced window, in ms (``scopes.py``).  Moves
``decode_gap16_p95_ms``.
"""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "decode", ("kv_write", "layers"))
