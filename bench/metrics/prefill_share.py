"""Share of the chip's program time spent in prefill-chunk programs."""
from bench import tracefile

PATTERN = r"^jit__chunk"       # runtime/server.py: _mk_prefill_chunk


def read(ctx):
    pre = sum(e - s for tr in ctx.samples
              for _, s, e in tracefile.matching(tr, ctx.device, PATTERN))
    busy = tracefile.busy_s(ctx.samples, ctx.device, line="modules")
    if pre == 0 or busy == 0:
        return None
    return 100.0 * pre * 1e-9 / busy
