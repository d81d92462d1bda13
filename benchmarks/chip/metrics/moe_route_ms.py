"""moe_route_ms: device time a decode step spends in the expert layer
outside its grouped matmuls.

Self time under the program's ``moe`` scope but not ``moe_experts``
(routing, the sort by expert and the gather of the sorted rows, the return
to token order and the gated sum), per decode step inside the traced
window, in ms.  Moves ``decode_gap16_p95_ms``.  It also prints the run's
seconds per scope and per expert-layer scope of each program
(``diagnostic scopes``, ``diagnostic moe_scopes``).
"""

from benchmarks.chip import moe_scopes


def read(ctx):
    moe_scopes.diagnose(ctx)
    seconds = moe_scopes.seconds(ctx, "decode", moe_scopes.ROUTE)
    if seconds is None:
        return None
    return 1e3 * seconds / len(ctx.decode_steps)
