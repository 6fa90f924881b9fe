"""Mean host time of one serve-loop pass: over the ``serve.iteration``
spans that lie wholly inside a traced window, the span's duration less
the time its ``serve.sync`` spans (the host waiting on the device)
cover.  A trace with no whole pass reads nothing."""
from bench import tracefile

ITERATION = "serve.iteration"   # runtime/server.py: Server.serve's spans
SYNC = "serve.sync"


def read(ctx):
    busy = []
    for tr in ctx.samples:
        a, b = tr.window
        syncs = [(s, e) for name, s, e in tr.host if name == SYNC]
        for name, s, e in tr.host:
            if name != ITERATION or s < a or e > b:
                continue
            waits = tracefile.union([(max(ws, s), min(we, e))
                                     for ws, we in syncs
                                     if we > s and ws < e])
            busy.append((e - s) - sum(we - ws for ws, we in waits))
    if not busy:
        return None
    return sum(busy) / len(busy) * 1e-6
