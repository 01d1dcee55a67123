"""The decode step's share of its roofline: the least time the chip
could take for the step's work (every weight once, plus the bf16 KV up to
each request's position; or its FLOPs, whichever bound is larger), over
the step's device time.  Both are means over the traced window."""

from servebench import peaks


def read(ctx):
    d = ctx.trace.module_time("jit_decode_step", "engine.decode")
    calls = [c for c in ctx.spans.calls if c[2] == "decode"]
    if not d or not calls:
        return None
    fam, conf = ctx.family, ctx.conf
    least = [peaks.least_time_s(fam.token_flops(conf, pos + 1),
                                fam.decode_bytes(conf, pos + 1), ctx.kind)
             for _, _, _, pos, _ in calls]
    return (sum(least) / len(least)) / (sum(d) / len(d)) * 100
