"""Share of the traced windows in which no operation ran on the chip."""
from bench import tracefile


def read(ctx):
    busy = tracefile.busy_s(ctx.samples, ctx.device)
    if busy == 0:
        return None
    return 100.0 * (1.0 - busy / tracefile.window_s(ctx.samples))
