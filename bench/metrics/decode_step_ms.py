"""Mean device time of one decode-step program execution."""
from bench import tracefile

PATTERN = r"^jit__decode"      # runtime/server.py: _decode, _decode_alphas


def read(ctx):
    evs = [ev for tr in ctx.samples
           for ev in tracefile.matching(tr, ctx.device, PATTERN, whole=True)]
    if not evs:
        return None
    return sum(e - s for _, s, e in evs) / len(evs) * 1e-6
