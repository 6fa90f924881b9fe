"""Operations the decode steps needed over their device time, as a share
of the chip's bf16 peak.

For each decode-step execution in the trace the live requests are those
whose first token came before it started and whose last came after it
ended (request stamps on the host clock, moved onto the trace's clock);
each needs one token at its live context, counted by the configuration's
kit (``ctx.token_flops``)."""
from bench import tracefile

PATTERN = r"^jit__decode"      # runtime/server.py: _decode, _decode_alphas


def read(ctx):
    ops, dev_ns = 0, 0
    for tr in ctx.samples:
        reqs = [(tr.to_ns(r["t_first"]), tr.to_ns(r["t_end"]), r)
                for r in ctx.records]
        for _, s, e in tracefile.matching(tr, ctx.device, PATTERN,
                                          whole=True):
            dev_ns += e - s
            for first, end, r in reqs:
                if first < s and end > e:
                    frac = (s - first) / max(end - first, 1)
                    pos = r["plen"] + int(frac * (r["n_out"] - 1))
                    ops += ctx.token_flops(pos + 1)
    if ops == 0:
        return None
    return 100.0 * ops / (dev_ns * 1e-9 * ctx.peaks["bf16_flops"])
