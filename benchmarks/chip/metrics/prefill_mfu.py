"""prefill_mfu: the prefill program's share of the chip's bf16 peak.

FLOPs that the prefills inside the traced window need (``counts.
prefill_flops``: projections, MLP or routed experts at top-k, causal
attention, last-token logits), over the device time of the prefill
program's executions times the peak.  Moves ``ttft_mean_ms``.
"""

from benchmarks.chip import counts


def read(ctx):
    seconds, n = ctx.summary.module(ctx.prefill_module)
    if n == 0 or n != len(ctx.prefills):
        return None
    flops = sum(counts.prefill_flops(ctx.dims, b, s) for b, s in ctx.prefills)
    return 100.0 * flops / (seconds * ctx.chips * ctx.peak["bf16_flops_per_s"])
