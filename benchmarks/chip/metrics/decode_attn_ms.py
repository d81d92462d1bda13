"""decode_attn_ms: device time a decode step spends attending.

Self time under the program's ``attn_core`` scope (one query per slot over
the cache) per decode step inside the traced window, in ms
(``scopes.py``).  Moves ``decode_gap16_p95_ms``.
"""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "decode", ("attn_core",))
