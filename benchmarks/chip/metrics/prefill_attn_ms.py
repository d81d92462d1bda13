"""prefill_attn_ms: device time a prefill spends attending.

Self time under the program's ``attn_core`` scope (the flash kernel and
the layout changes around it, every layer) per prefill inside the traced
window, in ms (``scopes.py``).  Moves ``ttft_mean_ms``.  Every cell with
scope metrics reads this one, so it also prints the run's seconds per
scope of each program (``diagnostic scopes``).
"""

from benchmarks.chip import scopes


def read(ctx):
    scopes.diagnose(ctx)
    return scopes.per_call_ms(ctx, "prefill", ("attn_core",))
