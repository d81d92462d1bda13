"""moe_prefill_mfu: the expert layer's grouped matmuls' share of the
chip's bf16 peak in the prefill.

FLOPs that the prefills inside the traced window need of the experts
(``moe_scopes.expert_flops``: every layer, each of the B*S*k routed rows
through the gate, up and down projections of one expert), over the self
time of the grouped matmuls in those prefills (the ``moe_experts`` scope
and the copies of each layer's expert weights that the kernels read,
``moe_scopes``) times the peak.  Moves ``ttft_mean_ms``.
"""

from benchmarks.chip import moe_scopes


def read(ctx):
    seconds = moe_scopes.seconds(ctx, "prefill", moe_scopes.EXPERTS)
    if seconds is None:
        return None
    flops = sum(moe_scopes.expert_flops(ctx.dims, b, s) for b, s in
                ctx.prefills)
    return 100.0 * flops / (seconds * ctx.chips * ctx.peak["bf16_flops_per_s"])
