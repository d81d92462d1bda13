"""decode_hbm_share: the decode step's share of HBM bandwidth.

Bytes that the decode steps inside the traced window need (``counts.
decode_step_bytes``: weights read once, the valid KV entries read and the
new one written; not the rest of the cache, nor a copy of it), over the
device time of the decode program's executions times HBM bandwidth.
Moves ``decode_gap16_p95_ms``.
"""

from benchmarks.chip import counts


def read(ctx):
    seconds, n = ctx.summary.module(ctx.decode_module)
    if n == 0 or n != len(ctx.decode_steps):
        return None
    need = sum(counts.decode_step_bytes(ctx.dims, b, kv)
               for b, kv in ctx.decode_steps)
    return 100.0 * need / (seconds * ctx.chips * ctx.peak["hbm_bytes_per_s"])
