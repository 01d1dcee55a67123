"""The prefill programs' share of their roofline: for every prefill in
the traced slice, the least time of the work it must do (its prompt's
FLOPs with the output head once, ``servebench.work``; or every weight
once plus the bf16 KV it attends to, whichever bound is larger), summed,
over the summed device time of the ``jit_decode_step`` programs that ran
under ``ServeEngine.prefill`` (one program per prompt length)."""

from servebench import peaks, work


def read(ctx):
    d = ctx.trace.module_time("jit_decode_step", "engine.prefill")
    calls = [c for c in ctx.spans.calls if c[2] == "prefill"]
    if not d or not calls:
        return None
    fam, conf = ctx.family, ctx.conf
    least = sum(peaks.least_time_s(work.needed_flops(fam, conf, pos, n),
                                   fam.decode_bytes(conf, pos + n), ctx.kind)
                for _, _, _, pos, n in calls)
    return least / sum(d) * 100
