"""Share of the traced windows in which the chip is idle while the serve
loop does host work: no operation runs on the device and the innermost
``serve.*`` span open on the host is anything but ``serve.sync``.

Device idle is the complement of the union of ``XLA Ops`` intervals, as
in ``device_idle_share``, so this share cannot exceed it; the difference
is idle under ``serve.sync`` (the host waiting on the device) or outside
any span.  Host events of other names (the Python tracer's frames, the
benchmark's window annotation and tracer thread) are ignored.  A trace
with no ``serve.*`` span reads nothing.
"""
from bench import tracefile

PREFIX = "serve."      # runtime/server.py: Server.serve's spans
SYNC = "serve.sync"


def host_work(spans: list) -> list:
    """[(start, end)] in which the innermost open span is not ``SYNC``.
    The spans come from one thread, so they nest."""
    out, stack, t = [], [], 0

    def emit(a, b):
        if b > a and stack[-1][0] != SYNC:
            out.append((a, b))

    for name, s, e in sorted(spans, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            emit(t, stack[-1][2])
            t = max(t, stack.pop()[2])
        if stack:
            emit(t, s)
        stack.append((name, s, e))
        t = s
    while stack:
        emit(t, stack[-1][2])
        t = max(t, stack.pop()[2])
    return out


def idle(tr, device: int) -> list:
    """[(start, end)] of the window in which no operation runs."""
    a, b = tr.window
    busy = tracefile.union([(s, e) for _, s, e in
                            tr.clipped(tr.ops.get(device, []))])
    gaps, t = [], a
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if b > t:
        gaps.append((t, b))
    return gaps


def overlap_ns(xs: list, ys: list) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = tot = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            tot += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read(ctx):
    gap, seen = 0, False
    for tr in ctx.samples:
        spans = [ev for ev in tr.host if ev[0].startswith(PREFIX)]
        if not spans:
            continue
        seen = True
        work = [(s, e) for _, s, e in
                tr.clipped([("", s, e) for s, e in host_work(spans)])]
        gap += overlap_ns(idle(tr, ctx.device), work)
    if not seen:
        return None
    return 100.0 * gap * 1e-9 / tracefile.window_s(ctx.samples)
