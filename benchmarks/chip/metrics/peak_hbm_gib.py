"""peak_hbm_gib: ``peak_bytes_in_use`` of the fullest chip after the window,
as the runtime counts it.  Moves ``tokens_per_s``: memory held and unused
is batch that could not be served.
"""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2 ** 30
