"""moe_decode_hbm_share: the expert layer's grouped matmuls' share of HBM
bandwidth in the decode step.

Bytes that the decode steps inside the traced window need of the experts
(``moe_scopes.expert_bytes``: per layer, the distinct experts a step's
tokens touch under uniform routing, gate, up and down weights read once),
over the self time of the grouped matmuls in those steps times HBM
bandwidth: the program's ``moe_experts`` scope and the copies of each
layer's expert weights that the kernels read (``moe_scopes``).  Moves
``decode_gap16_p95_ms``.
"""

from benchmarks.chip import moe_scopes


def read(ctx):
    seconds = moe_scopes.seconds(ctx, "decode", moe_scopes.EXPERTS)
    if seconds is None:
        return None
    need = sum(moe_scopes.expert_bytes(ctx.dims, b) for b, _ in
               ctx.decode_steps)
    return 100.0 * need / (seconds * ctx.chips * ctx.peak["hbm_bytes_per_s"])
