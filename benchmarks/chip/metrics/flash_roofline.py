"""flash_roofline: the Pallas flash-attention kernel's share of its roofline.

The kernel's operations are found in the compiled prefill by the Mosaic
name of the kernel function.  For each call (one per layer per prefill)
the least time the chip could take is the larger of its FLOPs over the
bf16 peak and its bytes (q, k and v read once, o written once) over HBM
bandwidth.  Their sum over the calls
in the traced window, over the summed device time of the kernel's events.
Moves ``ttft_mean_ms``.
"""

from benchmarks.chip import counts
from benchmarks.chip import trace

KERNEL = "_fa_kernel"      # the kernel function's name, which Mosaic keeps


def read(ctx):
    ops = trace.mosaic_ops(ctx.prefill_hlo, KERNEL)
    seconds, n = ctx.summary.kernel(ctx.prefill_module, ops)
    if n == 0 or seconds <= 0:
        return None
    d, peak = ctx.dims, ctx.peak
    least = sum(d.layers * max(
        counts.flash_flops(d, b, s) / (ctx.chips * peak["bf16_flops_per_s"]),
        counts.flash_bytes(d, b, s) / (ctx.chips * peak["hbm_bytes_per_s"]))
        for b, s in ctx.prefills)
    return 100.0 * least / seconds
